"""The `mla_moe` family: how a configuration file of the DeepSeek-V3 line
(latent attention, sigmoid-routed experts with a shared one; here
JoyAI-LLM-Flash) becomes the program's `LatentMoELM`, with weights made by
the benchmark from the seed, and how served tokens are judged against the
plain reference (benchmarks/reference/mla_moe.py).

The weights are the reference's (`ref.init`), made on the device in ONE
jitted call and handed to the program under the program's names: both start
from the same bfloat16-valued numbers and neither takes anything the other
made. The program's layout is per layer from the start, so the engine holds
them once.

A `ServeJob` only, and no `TrainJob`: the family is in the benchmark on the
serving path (ISSUE 28; the program has no loss for it, ROADMAP B-I), and a
train cell of this family would find no `TrainJob` here and stop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import mla_moe as ref
from benchmarks.reference.optim import u32

# program leaf <- reference leaf
_LAYER = {"ln1": "input_norm", "wq_a": "w_dq", "q_norm": "q_norm",
          "wq_b": "w_uq", "wkv_a": "w_dkv", "kv_norm": "kv_norm",
          "wkv_b": "w_ukv", "wo": "w_o", "ln2": "post_norm",
          "w_gate": "w_g", "w_up": "w_u", "w_down": "w_d"}
_MOE = {"router": "w_r", "router_bias": "b_r", "w_gate": "e_g",
        "w_up": "e_u", "w_down": "e_d", "ws_gate": "s_g", "ws_up": "s_u",
        "ws_down": "s_d"}
REFERENCE_WIDTH = 1024      # the reference runs at multiples of this


def _dtype(cfg: dict, what: str):
    return jnp.dtype(cfg.get("dtype", {}).get(what, "bfloat16"))


def to_program(r: dict, weights=jnp.bfloat16) -> dict:
    """The reference's tree under the program's names; matrices in the
    dtype the configuration states (the values are bfloat16's either
    way), norm gains, router and its bias in float32."""
    def mat(a):
        return a.astype(weights)

    def layer(lp):
        out = {p: (lp[q] if lp[q].ndim == 1 else mat(lp[q]))
               for p, q in _LAYER.items() if q in lp}
        if "w_r" in lp:
            out["moe"] = {p: (lp[q].astype(jnp.float32)
                              if p.startswith("router") else mat(lp[q]))
                          for p, q in _MOE.items()}
        return out

    return {"embed": mat(r["embed"]), "head": mat(r["head"]),
            "norm": r["norm"], "layers": tuple(layer(lp)
                                               for lp in r["layers"])}


def program_model(cfg: dict):
    from bigdl_tpu.models.latent_moe import LatentMoEConfig, LatentMoELM

    return LatentMoELM(LatentMoEConfig.from_source(cfg))


def reference_params(seed: int, cfg: dict) -> dict:
    return jax.jit(lambda s: ref.init(s, cfg))(u32(seed))


def make_variables(seed: int, cfg: dict) -> dict:
    weights = _dtype(cfg, "weights")
    params = jax.jit(lambda s: to_program(ref.init(s, cfg), weights))(
        u32(seed))
    return {"params": params, "state": {}}


class ServeJob:
    """What the serve driver needs of this family."""

    def __init__(self, seed, cfg, traffic, devices):
        from bigdl_tpu.serving import EngineRouter, InferenceEngine

        self.seed, self.cfg = seed, cfg
        eng = traffic["engine"]
        self.model = program_model(cfg)
        self.engine = InferenceEngine(
            self.model, make_variables(seed, cfg), slots=eng["slots"],
            max_len=eng["max_len"],
            prefill_buckets=tuple(eng["prefill_buckets"]),
            block_size=eng["block_size"],
            pool_blocks=eng.get("pool_blocks"),
            cache_dtype=_dtype(cfg, "cache"))
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
        token picked blindly reads. (`families/gpt2.py` gives the same
        gap in logits. Here the logits' scale is the initialiser's, a
        best stands 3.9 above the mean of 129,280, and one expert of a
        token's eight chosen otherwise, which bfloat16 does now and
        then, moves that token's logits by a quarter of that: PERF.md
        section 2 has the readings.) With `control`, the tokens judged
        are those the lower-precision forward puts first. The
        reference's full forward pass runs on one sequence at a time,
        padded to a multiple of REFERENCE_WIDTH tokens (padding follows
        the sequence, so causal attention never sees it)."""
        cfg = self.cfg
        params = reference_params(self.seed, cfg)

        @jax.jit
        def gaps(params, toks, chosen, first, count):
            lg = ref.logits(params, toks, cfg)[0]
            idx = jnp.arange(toks.shape[1])
            live = (idx >= first) & (idx < first + count)
            if control is not None:
                chosen = jnp.argmax(
                    ref.logits(params, toks, cfg, control)[0], -1)
            picked = jnp.take_along_axis(lg, chosen[:, None], -1)[:, 0]
            best = jnp.max(lg, -1)
            return jnp.where(
                live, (best - picked) / (best - jnp.mean(lg, -1)), 0.0)

        out = []
        with jax.default_matmul_precision("highest"):
            for prompt, tokens in samples:
                seq = list(prompt) + list(tokens)
                n, first = len(tokens), len(prompt) - 1
                width = -(-(len(seq) - 1) // REFERENCE_WIDTH) \
                    * REFERENCE_WIDTH
                toks = np.zeros((1, width), np.int32)
                toks[0, :len(seq) - 1] = seq[:-1]
                chosen = np.zeros((width,), np.int32)
                chosen[first:first + n] = tokens
                g = np.asarray(gaps(params, toks, chosen, first, n))
                out.append(g[first:first + n])
        return out
