"""The `granite_hybrid` family: how a configuration file of IBM's Granite
4.0-H line at `num_local_experts` 0 (`model_type: granitemoehybrid`: Mamba-2
layers whose recurrent state a slot keeps, a few grouped-query attention
layers with no position signal, one gated MLP after each; here
granite-4.0-h-micro) becomes the program's `HybridSSMLM`, with weights made
by the benchmark from the seed, and how served tokens are judged against
the plain reference (benchmarks/reference/granite_hybrid.py).

The weights are the reference's (`ref.init_top`, `ref.init_layer`), made on
the device a layer at a time (two small programs, one a layer kind, the
seed and the layer's number their arguments) and handed to the program
under the program's names: both start from the same bfloat16-valued
numbers and neither takes anything the other made. The layouts are the
same on both sides (every matrix (in, out), the taps (K, C)).

A `ServeJob` only, and no `TrainJob`: the family is in the benchmark on the
serving path (ISSUE 42; the program has no backward pass of the scan,
ROADMAP B-I).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import granite_hybrid as ref
from benchmarks.reference.optim import u32

# program leaf <- reference leaf
_LAYER = {"ln_mixer": "input_norm", "ln_mlp": "post_norm", "w_in": "w_in",
          "w_out": "w_out",
          # a mamba layer's
          "in_proj": "in_proj", "conv_w": "conv", "conv_b": "conv_bias",
          "A_log": "A_log", "D": "D", "dt_bias": "dt_bias",
          "gate_norm": "gate_norm", "out_proj": "out_proj",
          # an attention layer's
          "wq": "w_q", "wk": "w_k", "wv": "w_v", "wo": "w_o"}
REFERENCE_WIDTH = 1024      # the reference runs at multiples of this
HEAD_ROWS = 256             # positions a block of the reference's head


def _dtype(cfg: dict, what: str):
    return jnp.dtype(cfg.get("dtype", {}).get(what, "bfloat16"))


def _cast(a, weights):
    """What the reference holds in bfloat16 (the matrices, the embedding)
    goes in the dtype the configuration states (the values are bfloat16's
    either way); what it holds in float32 (norm gains, the depthwise taps
    and their bias, A_log, D, dt_bias) stays float32."""
    return a.astype(weights) if a.dtype == jnp.bfloat16 else a


def layer_to_program(lp: dict, weights) -> dict:
    return {p: _cast(lp[q], weights) for p, q in _LAYER.items() if q in lp}


def top_to_program(r: dict, weights) -> dict:
    return {"embed": _cast(r["embed"], weights), "norm": r["norm"]}


def to_program(r: dict, weights=jnp.bfloat16) -> dict:
    """The reference's tree under the program's names."""
    return {**top_to_program(r, weights),
            "layers": tuple(layer_to_program(lp, weights)
                            for lp in r["layers"])}


def program_model(cfg: dict):
    from bigdl_tpu.models.hybrid_ssm import HybridSSMConfig, HybridSSMLM

    return HybridSSMLM(HybridSSMConfig.from_source(cfg))


def _made(seed: int, cfg: dict, top, layer) -> dict:
    """`ref.init`'s tree, a part at a time through `top` and `layer`: one
    jitted program a layer kind (the seed and the layer's number are
    ARGUMENTS) and one for the top, in place of one program of forty
    layers."""
    s = u32(seed)
    made = {kind: jax.jit(lambda s, n, kind=kind: layer(
        ref.init_layer(s, cfg, n, kind))) for kind in ref.LAYER_KINDS}
    out = jax.jit(lambda s: top(ref.init_top(s, cfg)))(s)
    return {**out, "layers": [made[kind](s, jnp.int32(n)) for n, kind
                              in enumerate(ref.layer_plan(cfg))]}


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
            # the attention layers' rows and the convolution's taps; the
            # recurrence's state is float32 whatever this says
            cache_dtype=_dtype(cfg, "cache"),
            # refused by the model, by name: a hit would need the slot's
            # state at the shared prefix's end
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
        puts first. The reference's full forward pass runs on one sequence
        at a time, padded to a multiple of REFERENCE_WIDTH tokens (padding
        follows the sequence, so neither the causal attention nor the
        convolution and the recurrence, which look back only, see it), a
        jitted layer at a time: two programs a width (a mamba layer, an
        attention layer) in place of one of forty layers. The head runs
        only on the positions that are judged, the `judged` (the longest
        answer of the traffic) from the prompt's last on, HEAD_ROWS
        positions at a time: the logits of 256 positions over 100,352
        words are 0.1 GB, and the control holds a second set."""
        cfg = self.cfg
        rows = min(HEAD_ROWS, self.judged)
        judged = -(-self.judged // rows) * rows
        params = reference_params(self.seed, cfg)
        top = {k: v for k, v in params.items() if k != "layers"}
        kinds = ref.layer_plan(cfg)
        one_layer = {(kind, precision): jax.jit(
            lambda lp, x, kind=kind, precision=precision: ref.layer(
                lp, x, kind, cfg, precision))
            for kind in set(kinds) for precision in {None, control}}
        embed = jax.jit(lambda top, toks: ref.embed(top, toks, cfg))

        def stream(toks, precision):
            x = embed(top, toks)
            for lp, kind in zip(params["layers"], kinds):
                x = one_layer[kind, precision](lp, x)
            return x

        def judged_hidden(top, x, first):
            hid = jax.lax.dynamic_slice_in_dim(
                jnp.pad(ref.final(top, x, cfg), ((0, judged), (0, 0))),
                first, judged)
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

            hid = judged_hidden(top, exact, first)
            low = hid if control is None else judged_hidden(
                top, lower, first)
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
                exact = stream(toks, None)
                lower = exact if control is None else stream(toks, control)
                out.append(np.asarray(
                    gaps(top, exact, lower, chosen, first, n))[:n])
        return out
