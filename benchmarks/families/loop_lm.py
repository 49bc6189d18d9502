"""The `loop_lm` family: how a configuration file of ByteDance Seed's Ouro
line (`model_type: ouro`: a stack of layers run `total_ut_steps` times over
ONE set of weights, each pass with keys and values of its own; here
Ouro-2.6B) becomes the program's `LoopLM`, with weights made by the
benchmark from the seed, and how served tokens are judged against the plain
reference (benchmarks/reference/loop_lm.py).

The weights are the reference's (`ref.init_top`, `ref.init_layer`), made on
the device a layer at a time (one small program, the seed and the layer's
number its arguments) and handed to the program under the program's names:
both start from the same bfloat16-valued numbers and neither takes anything
the other made. The layouts are the same on both sides (every matrix (in,
out)). There is ONE set a layer on either side: no pass has weights of its
own.

A `ServeJob` only, and no `TrainJob`: the family is in the benchmark on the
serving path (ISSUE 49; the program has no backward pass over the loop,
ROADMAP B-I).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import loop_lm as ref
from benchmarks.reference.optim import u32

# program leaf <- reference leaf
_LAYER = {"ln_in": "norm_1", "wq": "w_q", "wk": "w_k", "wv": "w_v",
          "wo": "w_o", "ln_post_attn": "norm_2", "ln_pre_mlp": "norm_3",
          "w_gate": "w_g", "w_up": "w_u", "w_down": "w_d",
          "ln_post_mlp": "norm_4"}
_TOP = ("embed", "head", "norm", "exit_w", "exit_b")
REFERENCE_WIDTH = 128       # the reference runs at multiples of this
HEAD_ROWS = 256             # positions a block of the reference's head


def _dtype(cfg: dict, what: str):
    return jnp.dtype(cfg.get("dtype", {}).get(what, "bfloat16"))


def _cast(a, weights):
    """What the reference holds in bfloat16 (the matrices, the embedding,
    the head) goes in the dtype the configuration states (the values are
    bfloat16's either way); norm gains and the exit gate stay float32."""
    return a.astype(weights) if a.dtype == jnp.bfloat16 else a


def layer_to_program(lp: dict, weights) -> dict:
    return {p: _cast(lp[q], weights) for p, q in _LAYER.items()}


def top_to_program(r: dict, weights) -> dict:
    return {k: _cast(r[k], weights) for k in _TOP}


def to_program(r: dict, weights=jnp.bfloat16) -> dict:
    """The reference's tree under the program's names."""
    return {**top_to_program(r, weights),
            "layers": tuple(layer_to_program(lp, weights)
                            for lp in r["layers"])}


def program_model(cfg: dict):
    from bigdl_tpu.models.loop_lm import LoopLM, LoopLMConfig

    return LoopLM(LoopLMConfig.from_source(cfg))


def _made(seed: int, cfg: dict, top, layer) -> dict:
    """`ref.init`'s tree, a part at a time through `top` and `layer`: one
    jitted program for every layer (the seed and the layer's number are
    ARGUMENTS) and one for the top, in place of one program of all."""
    s = u32(seed)
    made = jax.jit(lambda s, n: layer(ref.init_layer(s, cfg, n)))
    out = jax.jit(lambda s: top(ref.init_top(s, cfg)))(s)
    return {**out, "layers": [made(s, jnp.int32(n))
                              for n in range(cfg["num_hidden_layers"])]}


def reference_params(seed: int, cfg: dict) -> dict:
    return _made(seed, cfg, lambda r: r, lambda lp: lp)


def make_variables(seed: int, cfg: dict) -> dict:
    weights = _dtype(cfg, "weights")
    params = _made(seed, cfg, lambda r: top_to_program(r, weights),
                   lambda lp: layer_to_program(lp, weights))
    return {"params": dict(params, layers=tuple(params["layers"])),
            "state": {}}


class ServeJob:
    """What the serve driver needs of this family."""

    def __init__(self, seed, cfg, traffic, devices):
        self.model = program_model(cfg)     # first: a program without
        # the model stops here, before any weight is made
        from bigdl_tpu.serving import EngineRouter, InferenceEngine

        self.seed, self.cfg = seed, cfg
        eng = traffic["engine"]
        self.judged = traffic["output_len"]["max"]
        self.engine = InferenceEngine(
            self.model, make_variables(seed, cfg), slots=eng["slots"],
            max_len=eng["max_len"],
            prefill_buckets=tuple(eng["prefill_buckets"]),
            block_size=eng["block_size"],
            pool_blocks=eng.get("pool_blocks"),
            cache_dtype=_dtype(cfg, "cache"),
            # served by the model (every entry a "table"), off in the
            # cells: their prompts are random, so retention would only
            # evict in a pool sized to the last block
            prefix_cache=False)
        self.router = EngineRouter([self.engine])
        self.vocab = cfg["vocab_size"]

    def release(self) -> None:
        """Drop every device array of the program before the reference."""
        self.engine = self.router = self.model = None

    def reference_gaps(self, samples, control=None) -> list:
        """For each (prompt, served tokens): by how much each served
        token's reference logit lies below the reference's best at its
        position, AS A SHARE of the distance from that best to the
        position's mean logit: 0 is the reference's own choice, 1 what a
        token picked blindly reads (`families/mla_moe.py` says why a share
        and not logits: the logits' scale is the initialiser's). With
        `control`, the tokens judged are those the lower-precision forward
        puts first. The reference's loop over passes and layers
        (`ref.hidden`) runs on one sequence at a time, padded to a multiple
        of REFERENCE_WIDTH tokens (padding follows the sequence, and the
        causal attention looks back only), its two parts jitted a layer at
        a time: two programs a width in place of one of 192 layer bodies.
        The head runs only on the positions that are judged, the `judged`
        (the longest answer of the traffic) from the prompt's last on,
        HEAD_ROWS positions at a time."""
        cfg = self.cfg
        rows = min(HEAD_ROWS, self.judged)
        judged = -(-self.judged // rows) * rows
        params = reference_params(self.seed, cfg)
        top = {k: v for k, v in params.items() if k != "layers"}
        parts = {precision: (
            jax.jit(lambda lp, x, precision=precision: ref.keys_and_values(
                lp, x, cfg, precision)),
            jax.jit(lambda lp, x, k, v, precision=precision: ref.layer(
                lp, x, k, v, cfg, precision)))
            for precision in {None, control}}

        def judged_hidden(hid, first):
            hid = jax.lax.dynamic_slice_in_dim(
                jnp.pad(hid, ((0, judged), (0, 0))), first, judged)
            return hid.reshape(judged // rows, rows, -1)

        @jax.jit
        def gaps(top, exact, lower, chosen, first, count):
            def block(args):
                hid, low, picks = args
                lg = ref.head(top, hid, cfg)
                if control is not None:
                    picks = jnp.argmax(ref.head(top, low, cfg, control), -1)
                picked = jnp.take_along_axis(lg, picks[:, None], -1)[:, 0]
                best = jnp.max(lg, -1)
                return (best - picked) / (best - jnp.mean(lg, -1))

            hid = judged_hidden(exact, first)
            low = hid if control is None else judged_hidden(lower, first)
            share = jax.lax.map(
                block, (hid, low, chosen.reshape(-1, rows))).reshape(-1)
            return jnp.where(jnp.arange(judged) < count, share, 0.0)

        out = []
        with jax.default_matmul_precision("highest"):
            for prompt, tokens in samples:
                seq = list(prompt) + list(tokens)
                n, first = len(tokens), len(prompt) - 1
                width = -(-(len(seq) - 1) // REFERENCE_WIDTH) \
                    * REFERENCE_WIDTH
                toks = np.zeros((width,), np.int32)
                toks[:len(seq) - 1] = seq[:-1]
                chosen = np.zeros((judged,), np.int32)
                chosen[:n] = tokens
                exact = ref.hidden(params, toks, cfg, None, parts[None])[0]
                lower = exact if control is None else ref.hidden(
                    params, toks, cfg, control, parts[control])[0]
                out.append(np.asarray(
                    gaps(top, exact, lower, chosen, first, n))[:n])
        return out
