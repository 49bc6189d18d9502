"""Quantized serving-weight layout (ISSUE 17).

Reference parity: BigDL shipped low-precision inference as its
production serving lever (nn/quantized/ + bigquant, arXiv 1804.05839)
— weights quantized OFFLINE, symmetric per-output-channel, fp32
restored by one scale multiply. This module is that scheme applied to
the transformer serving layout: `quantize_serving_params` repacks the
gemm weights of a `TransformerLM.serving_params` dict into int8
`QuantWeight` leaves (same dict/tuple STRUCTURE — the engine's
jit/donation plumbing never notices), and the model dequantizes at use
through the duck-typed `_deq`/`_embed_rows` helpers in
models/transformer.py. Biases, LayerNorm gains and the positional
table stay fp32 — they are O(E) a layer, quantizing them saves nothing
and costs accuracy.

What this buys: the decode step is weight-STREAMING-bound
(~172 MB/token fp32 at 43M, counted from shapes), so int8 weights cut the
bytes the roofline charges per token ~4x on the gemm weights — the
`lmdecode_quant` bench row reports the measured bytes/token next to
ms/token. On CPU XLA the dequant multiply materializes fp32 tiles
(parity/correctness harness); the fused int8 MXU gemm is not measured
on the chip (ROADMAP A1).

Numerics contract: quantization is LOSSY — a quantized engine is NOT
bit-identical to fp32 and never claims to be. The repo's load-bearing
bitwise pins (warm==cold, tp, speculative acceptance, spill) stay
fp32-scoped; quantized engines carry a TOLERANCE contract instead
(tests/test_quant_serving.py: greedy tokens agree with fp32 over a
documented prefix of the decode horizon — autoregressive divergence
means one argmax flip ends agreement, so the contract is a prefix
length, not a distance). The router refuses cross-layout-family
failover (`layout_family` on the engine) for the same reason: rerouted
requests must land on an engine whose tokens the original engine
would have produced.

Per-engine constructor choice (`InferenceEngine(weight_dtype=
"int8")`), never env — graftlint trace-env-read.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.quantized import _quantize_weight

# per-layer gemm weights quantized per OUTPUT channel (axis=0): one
# scale per output column keeps the per-channel dynamic range the
# reference scheme relies on
_BLOCK_GEMMS = ("wq", "wk", "wv", "wo", "w1", "w2")


class QuantWeight(NamedTuple):
    """An int8 weight + its fp32 dequant scale, as ONE pytree node.

    NamedTuple on purpose: jit/donation/tree_map traverse (q, scale)
    as ordinary leaves, so a QuantWeight rides through the engine's
    `_decode_step` signature, `gather_serving_params`, and pytree
    provenance counting unchanged. models/transformer.py discovers it
    by duck type (`hasattr(w, "deq")`) — serving/ depends on models/,
    never the reverse."""

    q: jax.Array       # int8, the fp32 weight's shape
    scale: jax.Array   # f32, broadcast shape (keepdims amax / 127)

    def deq(self) -> jax.Array:
        """fp32 view: one multiply, fused into the consuming gemm."""
        return self.q.astype(jnp.float32) * self.scale

    @property
    def shape(self):
        return self.q.shape


def quantize_weight(w: jax.Array, axis: int = 0) -> QuantWeight:
    """Symmetric per-channel int8 repack of one fp32 weight
    (nn/quantized scheme: scale = max|w| / 127 over `axis`)."""
    q, scale = _quantize_weight(w, axis)
    return QuantWeight(q, scale)


def why_not(model) -> Optional[str]:
    """Why this module does not repack `model`'s serving weights, or
    None where it does (`ServedModel.serving_refusals` asks): it knows
    the block leaves `TransformerLM.serving_params` returns
    (`_BLOCK_GEMMS`) and no other layout."""
    from bigdl_tpu.models.transformer import TransformerLM

    if isinstance(model, TransformerLM):
        return None
    return ("serving/quant.py repacks the block leaves of an unsharded "
            "TransformerLM's serving_params and knows no other layout")


def quantize_serving_params(params):
    """Repack a serving_params dict (per-layer block tuples) into the
    int8 layout: block gemm weights and the embedding/head table
    become QuantWeight leaves, everything else passes through
    untouched. The embedding is scaled PER ROW (axis=1) so token
    lookups gather int8 rows + their scales instead of dequantizing
    the whole (V, E) table (models/transformer._embed_rows)."""
    from bigdl_tpu.parallel.param_layout import map_block_leaves

    p = params["params"] if "params" in params else params
    # the per-layer walk is the param-layout spine's block-leaf map
    # (ISSUE 18) — it raises on a stacked tree, keeping the "call
    # serving_params first" contract
    out = map_block_leaves(
        p, lambda k, v: (quantize_weight(v, axis=0)
                         if k in _BLOCK_GEMMS else v))
    out["embed"] = quantize_weight(p["embed"], axis=1)
    if "head" in p:
        out["head"] = quantize_weight(p["head"], axis=0)
    return out


def params_bytes(params) -> int:
    """Stored bytes of a params pytree (QuantWeight counts q AND
    scale) — the weight-streaming side of the lmdecode_quant bench
    row's bytes/token provenance."""
    return int(sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(params)))
