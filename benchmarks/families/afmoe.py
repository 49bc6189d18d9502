"""The `afmoe` family: how a configuration file of Arcee's Trinity line
(window and full attention layers mixed, grouped-query gated attention,
sigmoid-routed experts with a shared one; here Trinity-Mini) becomes the
program's `WindowMoELM`, with weights made by the benchmark from the seed,
and how served tokens are judged against the plain reference
(benchmarks/reference/afmoe.py).

The weights are the reference's (`ref.init`), made on the device in ONE
jitted call and handed to the program under the program's names: both start
from the same bfloat16-valued numbers and neither takes anything the other
made. The program's layout is per layer from the start, so the engine holds
them once.

A `ServeJob` only, and no `TrainJob`: the family is in the benchmark on the
serving path (ISSUE 33; the program has no loss for it, ROADMAP B-I), and a
train cell of this family would find no `TrainJob` here and stop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import afmoe as ref
from benchmarks.reference.optim import u32

# program leaf <- reference leaf
_LAYER = {"ln_in": "input_norm", "wq": "w_q", "wk": "w_k", "wv": "w_v",
          "wg": "w_og", "q_norm": "q_norm", "k_norm": "k_norm",
          "wo": "w_o", "ln_post_attn": "post_attn_norm",
          "ln_pre_mlp": "pre_mlp_norm", "ln_post_mlp": "post_mlp_norm",
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
    """The program's model of the layers that are run: the file keeps the
    source's `layer_types` whole and names the published layers it runs
    (`kept_layers`); the program is given those layers' types."""
    from bigdl_tpu.models.window_moe import WindowMoEConfig, WindowMoELM

    kinds = [kind for kind, _ in ref.layer_plan(cfg)]
    return WindowMoELM(WindowMoEConfig.from_source(
        dict(cfg, layer_types=kinds)))


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
            # refused by the model, by name: a sliding layer's ring does
            # not keep a shared prefix's rows
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
        token picked blindly reads (`families/mla_moe.py` says why a
        share and not logits: the logits' scale is the initialiser's).
        With `control`, the tokens judged are those the lower-precision
        forward puts first. The reference's full forward pass runs on one
        sequence at a time, padded to a multiple of REFERENCE_WIDTH
        tokens (padding follows the sequence, so causal attention never
        sees it); the head only on the positions that are judged, the
        `judged` (the longest answer of the traffic) from the prompt's
        last on: the logits of 7,168 positions over 200,192 words are
        5.7 GB, those of 1,024 are 0.8."""
        cfg, judged = self.cfg, self.judged
        params = reference_params(self.seed, cfg)

        def judged_logits(params, toks, first, precision):
            hid = ref.hidden(params, toks, cfg, precision)
            hid = jax.lax.dynamic_slice_in_dim(
                jnp.pad(hid, ((0, judged), (0, 0))), first, judged)
            return ref.head(params, hid, precision)

        @jax.jit
        def gaps(params, toks, chosen, first, count):
            lg = judged_logits(params, toks, first, None)
            if control is not None:
                chosen = jnp.argmax(
                    judged_logits(params, toks, first, control), -1)
            picked = jnp.take_along_axis(lg, chosen[:, None], -1)[:, 0]
            best = jnp.max(lg, -1)
            return jnp.where(
                jnp.arange(judged) < count,
                (best - picked) / (best - jnp.mean(lg, -1)), 0.0)

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
                out.append(np.asarray(
                    gaps(params, toks, chosen, first, n))[:n])
        return out
