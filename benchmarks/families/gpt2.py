"""The `gpt2` family: how a GPT-2 configuration file becomes the program's
`TransformerLM`, with weights and inputs made by the benchmark from the
seed, and how the program's state is read back in the reference's names.

The weights are the reference's (`benchmarks/reference/gpt2.init`), made on
the device in ONE jitted call and handed to the program in its own tree
layout, so program and reference start from the same numbers without either
taking anything the other made. The program's own `TransformerLM.build`
runs 18 eager initialisers and is not used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import gpt2 as ref
from benchmarks.reference import optim as ref_optim
from benchmarks.reference.optim import u32  # noqa: F401 - drivers use it

# program leaf <- reference leaf (the rest of a block's leaves share names)
_RENAME = {"w1": "w_fc", "b1": "b_fc", "w2": "w_proj", "b2": "b_proj"}
_TOP = {"embed": "wte", "pos": "wpe", "lnf_g": "lnf_g", "lnf_b": "lnf_b"}


def to_program(r: dict) -> dict:
    blocks = {k: r[_RENAME.get(k, k)] for k in
              ("ln1_g", "ln1_b", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
               "bo", "ln2_g", "ln2_b", "w1", "b1", "w2", "b2")}
    return {**{p: r[q] for p, q in _TOP.items()}, "blocks": blocks}


def to_reference(p: dict) -> dict:
    out = {q: p[k] for k, q in _TOP.items()}
    out.update({_RENAME.get(k, k): v for k, v in p["blocks"].items()})
    return out


def program_model(cfg: dict, traffic: dict):
    from bigdl_tpu.models.transformer import TransformerConfig, TransformerLM

    e = cfg["n_embd"]
    ratio, rem = divmod(cfg.get("n_inner") or 4 * e, e)
    if rem:
        raise ValueError("n_inner must be a multiple of n_embd here")
    remat = traffic.get("remat")
    tc = TransformerConfig(
        vocab_size=cfg["vocab_size"], max_len=cfg["n_positions"], dim=e,
        num_heads=cfg["n_head"], num_layers=cfg["n_layer"], mlp_ratio=ratio,
        remat=bool(remat), remat_policy=remat or "full")
    return TransformerLM(tc)


def reference_params(seed: int, cfg: dict) -> dict:
    return jax.jit(lambda s: ref.init(s, cfg))(u32(seed))


def make_variables(seed: int, cfg: dict) -> dict:
    params = jax.jit(lambda s: to_program(ref.init(s, cfg)))(u32(seed))
    return {"params": params, "state": {}}


def _tokens(seed, cfg: dict, traffic: dict, n: int):
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 7919)
    return jax.random.randint(
        key, (n, traffic["batch"], traffic["seq_len"] + 1), 0,
        cfg["vocab_size"], jnp.int32)


def make_tokens(seed: int, cfg: dict, traffic: dict, n: int):
    return jax.jit(lambda s: _tokens(s, cfg, traffic, n))(u32(seed))


def make_batches(seed: int, cfg: dict, traffic: dict, n: int):
    """`n` distinct (tokens, next tokens) batches, uniform from the seed,
    resident on the device."""
    toks = make_tokens(seed, cfg, traffic, n)
    return [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(n)]


class TrainJob:
    """What the train driver needs of this family."""

    slot = "m"      # Adam's first moment after one step is (1 - b1) * g

    def __init__(self, seed, cfg, traffic, devices):
        from bigdl_tpu import nn
        from bigdl_tpu.optim import Adam

        opt = traffic["optimizer"]
        if opt["name"] != "adam":
            raise ValueError("the gpt2 family trains with adam")
        self.seed, self.cfg, self.traffic = seed, cfg, traffic
        self.model = program_model(cfg, traffic)
        self.model.variables = make_variables(seed, cfg)
        self.criterion = nn.ChunkedSoftmaxCE()
        self.method = Adam(opt["lr"])
        self.precision = traffic["precision"]
        self.mesh = None
        self.batches = make_batches(seed, cfg, traffic, traffic["pool"])

    def slot_leaves(self, slots, params=None) -> dict:
        return to_reference(slots[self.slot])

    def param_leaves(self, params) -> dict:
        return to_reference(params)

    def initial_leaves(self, seed) -> dict:
        """Traceable in `seed`: the reference-named initial parameters."""
        return ref.init(seed, self.cfg)

    def reference_steps(self, steps: int, precision=None) -> dict:
        return reference_steps(self.seed, self.cfg, self.traffic, 1, steps,
                               precision)


def reference_steps(seed, cfg, traffic, chips, steps, precision=None) -> dict:
    """The plain reference through `steps` optimizer steps on the same
    weights and batches: each step's loss, the leaves of Adam's first
    moment after step one, and of the parameters' change at the end.
    `precision` makes it the control instead. Needs no program."""
    lr = traffic["optimizer"]["lr"]
    p0 = reference_params(seed, cfg)
    toks = make_tokens(seed, cfg, traffic, steps)
    params, state = p0, ref_optim.adam_init(p0)
    update = jax.jit(ref_optim.adam_step, static_argnums=(3,),
                     static_argnames=("lr",))
    losses, slot1 = [], None
    with jax.default_matmul_precision("highest"):
        for i in range(steps):
            loss, grads = ref.loss_and_grad_rows(
                params, toks[i, :, :-1], toks[i, :, 1:], cfg, precision,
                rows_per_block=1)
            params, state = update(params, grads, state, i, lr=lr)
            losses.append(float(loss))
            if i == 0:
                slot1 = ref_optim.host_norms(state["m"])
    delta = ref_optim.host_norms(jax.tree_util.tree_map(jnp.subtract, params, p0))
    return {"loss": losses, "slot": slot1, "delta": delta}


class ServeJob:
    """What the serve driver needs of this family."""

    def __init__(self, seed, cfg, traffic, devices):
        from bigdl_tpu.serving import EngineRouter, InferenceEngine

        self.seed, self.cfg = seed, cfg
        eng = traffic["engine"]
        self.model = program_model(cfg, {})
        self.engine = InferenceEngine(
            self.model, make_variables(seed, cfg), slots=eng["slots"],
            max_len=cfg["n_positions"],
            prefill_buckets=tuple(eng["prefill_buckets"]),
            block_size=eng["block_size"],
            pool_blocks=eng.get("pool_blocks"))
        self.router = EngineRouter([self.engine])
        self.vocab = cfg["vocab_size"]

    def release(self) -> None:
        """Drop every device array of the program before the reference."""
        self.engine = self.router = self.model = None

    def reference_gaps(self, samples, control=None) -> list:
        """For each (prompt, served tokens): by how much each served
        token's reference logit lies below the reference's best at its
        position. With `control`, the tokens judged are not the served ones
        but those the lower-precision forward puts first at the same
        positions (it need not decode)."""
        cfg = self.cfg
        width = cfg["n_positions"]
        params = reference_params(self.seed, cfg)

        @jax.jit
        def gaps(params, toks, chosen, first, count):
            lg = ref.logits(params, toks, cfg)[0]
            idx = jnp.arange(width)
            live = (idx >= first) & (idx < first + count)
            if control is not None:
                chosen = jnp.argmax(
                    ref.logits(params, toks, cfg, control)[0], -1)
            picked = jnp.take_along_axis(lg, chosen[:, None], -1)[:, 0]
            return jnp.where(live, jnp.max(lg, -1) - picked, 0.0)

        out = []
        with jax.default_matmul_precision("highest"):
            for prompt, tokens in samples:
                seq = list(prompt) + list(tokens)
                n, first = len(tokens), len(prompt) - 1
                toks = np.zeros((1, width), np.int32)
                toks[0, :len(seq) - 1] = seq[:-1]
                chosen = np.zeros((width,), np.int32)
                chosen[first:first + n] = tokens
                g = np.asarray(gaps(params, toks, chosen, first, n))
                out.append(g[first:first + n])
        return out
