"""The `zaya` family: how a configuration file of Zyphra's ZAYA1 line
(compressed convolutional attention with a per-slot state beside its paged
rows, one expert a token chosen by a router MLP that carries its state from
layer to layer; here ZAYA1-8B) becomes the program's `CCAMoELM`, with weights
made by the benchmark from the seed, and how served tokens are judged against
the plain reference (benchmarks/reference/cca_moe.py).

The weights are the reference's (`ref.init`), made on the device a layer at
a time and handed to the program under the program's names and in the
program's layouts: both start from the same bfloat16-valued numbers and
neither takes anything the other made.

A `ServeJob` only, and no `TrainJob`: the family is in the benchmark on the
serving path (ISSUE 38; the program has no loss for it, ROADMAP B-I).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import cca_moe as ref
from benchmarks.reference.optim import u32

# program leaf <- reference leaf; the three whose layout differs are below
_LAYER = {"res_attn": "attn_merge", "ln_attn": "attn_norm", "wq": "w_q",
          "wk": "w_k", "wv1": "w_v1", "wv2": "w_v2", "wo": "w_o",
          "conv0_b": "conv0_bias", "tau": "tau", "res_moe": "moe_merge",
          "ln_moe": "moe_norm"}
_ROUTER = {"w_down": "r_down", "b_down": "r_down_bias", "norm": "r_norm",
           "w1": "r_1", "b1": "r_1_bias", "w2": "r_2", "b2": "r_2_bias",
           "w3": "r_3", "beta": "r_select_bias", "gamma": "r_gamma"}
_EXPERTS = {"w_gate": "e_g", "w_up": "e_u", "w_down": "e_d"}
REFERENCE_WIDTH = 1024      # the reference runs at multiples of this
HEAD_ROWS = 256             # positions a block of the reference's head


def _dtype(cfg: dict, what: str):
    return jnp.dtype(cfg.get("dtype", {}).get(what, "bfloat16"))


def _cast(a, weights):
    """What the reference holds in bfloat16 (the matrices) goes in the
    dtype the configuration states (the values are bfloat16's either way);
    what it holds in float32 (norm gains, merges, biases, the depthwise
    taps, tau, the whole router) stays float32."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(weights) if x.dtype == jnp.bfloat16 else x, a)


def layer_to_program(lp: dict, weights) -> dict:
    out = {p: _cast(lp[q], weights) for p, q in _LAYER.items()}
    out.update(
        conv0_w=lp["conv0"].T,                              # (C, taps)
        conv1_w=_cast(lp["conv1"], weights).transpose(1, 0, 2, 3),
        conv1_b=lp["conv1_bias"].reshape(-1),               # (H, taps, D, D)
        router={p: lp[q] for p, q in _ROUTER.items() if q in lp},
        experts={p: _cast(lp[q], weights) for p, q in _EXPERTS.items()})
    return out


def top_to_program(r: dict, weights) -> dict:
    return {"embed": _cast(r["embed"], weights), "res_out": r["out_merge"],
            "norm": r["norm"]}


def to_program(r: dict, weights=jnp.bfloat16) -> dict:
    """The reference's tree under the program's names and layouts."""
    return {**top_to_program(r, weights),
            "layers": tuple(layer_to_program(lp, weights)
                            for lp in r["layers"])}


def program_model(cfg: dict):
    """The program's model of the layers that are run: the file keeps the
    source's `layer_types` whole and names the published layers it runs
    (`kept_layers`); the program is given those layers' types."""
    from bigdl_tpu.models.cca_moe import CCAMoEConfig, CCAMoELM

    kinds = [cfg["layer_types"][i] for i in ref.kept_layers(cfg)]
    return CCAMoELM(CCAMoEConfig.from_source(dict(cfg, layer_types=kinds)))


def _made(seed: int, cfg: dict, top, layer) -> dict:
    """`ref.init`'s tree, a part at a time through `top` and `layer`: three
    small jitted programs (the top with the balancing rows, the first
    layer, any later layer, with the seed, the layer and the balancing
    rows' stream as ARGUMENTS) in place of one whose compile grows with
    the depth (twenty layers: minutes)."""
    s = u32(seed)

    def made(first):
        def one(s, n, stream):
            lp, stream = ref.init_layer(s, cfg, n, first, stream)
            return layer(lp), stream
        return jax.jit(one, donate_argnums=2)   # the rows pass through

    @jax.jit
    def start(s):
        made_top = ref.init_top(s, cfg)
        return top(made_top), ref.balance_stream(
            made_top, ref.balance_tokens(s, cfg))

    out, stream = start(s)
    first, later = made(True), made(False)
    layers = []
    for n in range(len(ref.kept_layers(cfg))):
        lp, stream = (later if n else first)(s, jnp.int32(n), stream)
        # one layer's program at a time: launched ahead, each holds its
        # 2.7 GB of rows beside the weights (16.8 GB at the peak, PR 38)
        layers.append(jax.block_until_ready(lp))
    return {**out, "layers": layers}


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
        convolutions, which look back only, see it). The head runs only
        on the positions that are judged, the `judged` (the longest answer
        of the traffic) from the prompt's last on, HEAD_ROWS positions at
        a time: the logits of 2,304 positions over 262,272 words are
        2.4 GB, those of 256 are 0.27, and the control holds a second
        set."""
        cfg = self.cfg
        rows = min(HEAD_ROWS, self.judged)
        judged = -(-self.judged // rows) * rows
        params = reference_params(self.seed, cfg)
        top = {k: v for k, v in params.items() if k != "layers"}
        # `ref.hidden`, a jitted layer at a time: two programs a width (the
        # first layer, any later one) in place of one of twenty layers
        one_layer = {precision: jax.jit(
            lambda lp, r, y, rho, precision=precision: ref.layer(
                lp, r, y, rho, cfg, precision))
            for precision in {None, control}}
        embed = jax.jit(ref.embed)

        def stream(toks, precision):
            r, rho = None, None
            y = embed(top, toks)
            for lp in params["layers"]:
                r, y, rho = one_layer[precision](lp, r, y, rho)
            return r, y

        def judged_hidden(top, stream, first):
            hid = jax.lax.dynamic_slice_in_dim(
                jnp.pad(ref.final(top, *stream, cfg),
                        ((0, judged), (0, 0))), first, judged)
            return hid.reshape(judged // rows, rows, -1)

        @jax.jit
        def gaps(top, exact, lower, chosen, first, count):
            def block(args):
                hid, low, picks = args
                lg = ref.head(top, hid)
                if control is not None:
                    picks = jnp.argmax(ref.head(top, low, control), -1)
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
