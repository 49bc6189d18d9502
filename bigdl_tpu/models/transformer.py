"""Decoder-only Transformer language model — the long-context flagship.

The reference's language-model family tops out at LSTM BPTT
(models/rnn/, SURVEY.md §2.5); this model is the TPU-first successor in
the same zoo slot, designed so every parallelism axis maps onto the mesh:

* **Stacked-parameter layers under `lax.scan`** — all L blocks share one
  pytree with a leading (L, ...) layer axis. One trace compiles once no
  matter the depth (XLA-friendly), tensor-parallel sharding is a single
  PartitionSpec per stacked leaf, and pipeline stages are contiguous
  slices of the layer axis (bigdl_tpu/parallel/pipeline.py).
* **Flash attention** on the hot path (bigdl_tpu/ops/flash_attention.py,
  Pallas on TPU), or **ring attention** over a mesh `seq` axis when
  `sp_axis` is set and apply() runs inside shard_map
  (bigdl_tpu/parallel/ring_attention.py).
* Pre-LayerNorm residual blocks, GELU MLP, learned positional embedding,
  weight-tied output head — standard GPT-2-style architecture.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.module import Module
from bigdl_tpu.serving.protocol import ServedModel


def _deq(w):
    """Duck-typed dequantize: a serving/quant.py QuantWeight knows how
    to `deq()` itself back to fp32; a plain array passes through. The
    serving paths call this at every gemm-weight use so one code path
    serves both layouts — and models/ never imports serving/."""
    return w.deq() if hasattr(w, "deq") else w


def _embed_rows(w, tokens):
    """Embedding-table row lookup for either layout. The quantized
    table is scaled PER ROW (axis=1 amax → scale (V, 1)), so a lookup
    gathers int8 rows and their scales and multiplies — O(rows·E)
    work, never the (V, E) fp32 dequant `_deq` would materialize."""
    if hasattr(w, "deq"):
        return w.q[tokens].astype(jnp.float32) * w.scale[tokens]
    return w[tokens]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_identity(x, axis):
    """Megatron's conjugate "f" operator: identity forward, psum backward.

    Placed where a replicated activation enters column-parallel compute,
    so its cotangent (which each TP shard holds only a partial of) is
    summed over the TP axis before reaching upstream replicated params —
    their grads then come out full and identical on every shard, needing
    no per-leaf corrections. The row-parallel psum in the forward is the
    conjugate "g" (psum forward; its transpose is already identity)."""
    return x


def _tpid_fwd(x, axis):
    return x, None


def _tpid_bwd(axis, _, ct):
    return (lax.psum(ct, axis),)


tp_identity.defvjp(_tpid_fwd, _tpid_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_reduce(x, axis):
    """Megatron's conjugate "g" operator: psum forward, identity backward.

    A bare lax.psum would not do: inside shard_map without replication
    tracking its AD transpose is another psum, which multiplies the
    (identical-per-shard) cotangents by the axis size. The custom VJP
    pins the backward to identity, which is the correct transpose here
    because the summed activation is replicated — each shard already
    holds the full cotangent."""
    return lax.psum(x, axis)


def _tpred_fwd(x, axis):
    return lax.psum(x, axis), None


def _tpred_bwd(axis, _, ct):
    return (ct,)


tp_reduce.defvjp(_tpred_fwd, _tpred_bwd)


def tp_shard_gather(x, axis):
    """Reconstruct a full activation from disjoint per-shard column
    slabs — the BIT-EXACT stand-in for Megatron's row-parallel psum on
    the serving path (ISSUE 10).

    A true row-split matmul psums PARTIAL sums, which changes the fp32
    accumulation order vs the unsharded gemm and breaks the serving
    plane's bitwise contract. Instead the sharded serving path keeps
    every contraction FULL-extent (the ops/kv_cache.py prefix-cache
    discipline) and uses ONE collective per layer half to concatenate
    the disjoint column shards back into the exact array the unsharded
    step holds — the zero2 discipline (all_gather of disjoint shards
    reconstructs the replicated value bit-for-bit) applied to
    activations. The downstream wo/w2 gemm then runs replicated over
    identical shapes, so its bits match the unsharded step exactly
    (pinned by tests/test_tp_serving.py and the tp_serve dryrun leg)."""
    return lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True)


@dataclass
class TransformerConfig:
    vocab_size: int = 256
    max_len: int = 512
    dim: int = 128
    num_heads: int = 4
    num_layers: int = 2
    mlp_ratio: int = 4
    dropout: float = 0.0
    causal: bool = True
    tie_embeddings: bool = True
    # rematerialize each block's activations in backward (jax.checkpoint):
    # memory O(layers + one block) instead of O(layers × acts) — the knob
    # that makes long-context training fit HBM (SURVEY.md §7 hard parts)
    remat: bool = False
    # remat policy: "full" recomputes the whole block (max memory
    # savings); "dots" saves matmul outputs and recomputes only the
    # cheap elementwise chain (jax.checkpoint_policies
    # .dots_with_no_batch_dims_saveable) — most of the memory win at a
    # fraction of the recompute FLOPs
    remat_policy: str = "full"
    # Switch/GShard-MoE FFN: moe_experts > 0 replaces EVERY block's MLP
    # with a routed mixture of moe_experts expert MLPs (parallel/moe.py
    # routing math; homogeneous across layers so the block scan stays
    # one compiled body). The auxiliary load-balancing loss is summed
    # over layers and added to .loss() scaled by moe_aux_weight.
    moe_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # "top_k" (Switch/GShard, capacity dropping) or "expert_choice"
    # (dropless: experts pick tokens, perfectly balanced, aux==0;
    # NOT causally masked — see parallel/moe.py)
    moe_routing: str = "top_k"

    def __post_init__(self):
        if self.remat_policy not in ("full", "dots", "attn_saved"):
            raise ValueError(
                f"remat_policy {self.remat_policy!r}: expected 'full', "
                "'dots' or 'attn_saved'")
        if self.moe_experts and self.moe_top_k not in (1, 2):
            raise ValueError("moe_top_k must be 1 or 2")
        if self.moe_routing not in ("top_k", "expert_choice"):
            raise ValueError(
                f"moe_routing {self.moe_routing!r}: expected 'top_k' "
                "or 'expert_choice'")
        if (self.moe_experts and self.moe_routing == "expert_choice"
                and self.causal):
            # expert-choice routing selects tokens per expert over the
            # WHOLE sequence, so at train time an expert's choice for
            # position t depends on tokens after t — future-token
            # leakage under a causal LM objective (parallel/moe.py).
            # Surfaced here too, where the model is configured.
            import logging

            logging.getLogger("bigdl_tpu.models").warning(
                "moe_routing='expert_choice' with causal=True: "
                "expert-choice token selection reads the full sequence, "
                "leaking future tokens into the routing decision at "
                "train time; causal-LM eval/teacher-forcing metrics may "
                "be optimistic (see parallel/moe.py)")


class TransformerLM(Module, ServedModel):
    """apply(variables, tokens (B, S) int32) → log-probs (B, S, V).

    `sp_axis`: if set, attention runs as ring attention over that mesh
    axis — apply() must then be called inside shard_map with the
    sequence dimension sharded on `sp_axis` (positional embeddings are
    offset by the shard's global position automatically).

    `sp_mode`: "ring" (contiguous chunks) or "zigzag" — the causal
    load-balanced layout: device i holds global rows [i·h, (i+1)·h) ∪
    [(2n−1−i)·h, (2n−i)·h), every ring hop computes only visible
    half-blocks (half the causal flops, equal per-device work;
    parallel/ring_attention.py). Callers must feed tokens/targets
    PERMUTED into that layout — make_transformer_train_step does this
    when built with sp_mode="zigzag" (the LM loss is a mean over
    positions, so the permutation leaves it unchanged); positional
    embeddings are gathered by the zigzag position vector here.
    """

    def __init__(self, config: TransformerConfig,
                 sp_axis: Optional[str] = None,
                 tp_axis: Optional[str] = None,
                 attn_impl: Optional[str] = None,
                 sp_mode: str = "ring",
                 ep_axis: Optional[str] = None,
                 name: Optional[str] = None):
        super().__init__(name=name)
        self.cfg = config
        self.sp_axis = sp_axis
        self.tp_axis = tp_axis
        self.attn_impl = attn_impl
        self.ep_axis = ep_axis
        if ep_axis is not None and not config.moe_experts:
            raise ValueError("ep_axis requires moe_experts > 0")
        if sp_mode not in ("ring", "zigzag"):
            raise ValueError(f"sp_mode must be ring|zigzag, got {sp_mode}")
        if sp_mode == "zigzag" and not config.causal:
            raise ValueError("zigzag sp_mode requires a causal model")
        self.sp_mode = sp_mode
        if config.moe_experts:
            if tp_axis is not None:
                raise NotImplementedError(
                    "MoE FFN under tensor parallelism (expert "
                    "parallelism shards experts instead; see "
                    "parallel/moe.py)")
            from bigdl_tpu.parallel.moe import MoE

            # routing/dispatch math only; its params are the per-layer
            # slices of the stacked block weights
            self._moe = MoE(config.dim, config.dim * config.mlp_ratio,
                            config.moe_experts,
                            capacity_factor=config.moe_capacity_factor,
                            top_k=config.moe_top_k,
                            routing=config.moe_routing,
                            expert_axis=ep_axis, name="moe_ffn")
        if config.dim % config.num_heads:
            raise ValueError("dim must be divisible by num_heads")
        self.head_dim = config.dim // config.num_heads

    # ------------------------------------------------------------ params
    def init_params(self, rng):
        c = self.cfg
        e, f, l = c.dim, c.dim * c.mlp_ratio, c.num_layers
        keys = iter(jax.random.split(rng, 16))

        def norm(key, shape, fan_in):
            return jax.random.normal(key, shape, jnp.float32) * (
                fan_in ** -0.5)

        blocks = {
            "ln1_g": jnp.ones((l, e)), "ln1_b": jnp.zeros((l, e)),
            "wq": norm(next(keys), (l, e, e), e),
            "wk": norm(next(keys), (l, e, e), e),
            "wv": norm(next(keys), (l, e, e), e),
            "wo": norm(next(keys), (l, e, e), e),
            "bq": jnp.zeros((l, e)), "bk": jnp.zeros((l, e)),
            "bv": jnp.zeros((l, e)), "bo": jnp.zeros((l, e)),
            "ln2_g": jnp.ones((l, e)), "ln2_b": jnp.zeros((l, e)),
        }
        if c.moe_experts:
            ex = c.moe_experts
            blocks.update({
                "router": norm(next(keys), (l, e, ex), e),
                "w1": norm(next(keys), (l, ex, e, f), e),
                "b1": jnp.zeros((l, ex, f)),
                "w2": norm(next(keys), (l, ex, f, e), f),
                "b2": jnp.zeros((l, ex, e)),
            })
        else:
            blocks.update({
                "w1": norm(next(keys), (l, e, f), e),
                "b1": jnp.zeros((l, f)),
                "w2": norm(next(keys), (l, f, e), f),
                "b2": jnp.zeros((l, e)),
            })
        p = {
            "embed": jax.random.normal(next(keys),
                                       (c.vocab_size, e)) * 0.02,
            "pos": jax.random.normal(next(keys), (c.max_len, e)) * 0.02,
            "blocks": blocks,
            "lnf_g": jnp.ones((e,)), "lnf_b": jnp.zeros((e,)),
        }
        if not c.tie_embeddings:
            p["head"] = norm(next(keys), (e, c.vocab_size), e)
        return p

    # ----------------------------------------------------------- forward
    @staticmethod
    def _ln(x, g, b, eps=1e-5):
        from bigdl_tpu.nn.normalization import layer_norm

        return layer_norm(x, g, b, eps)

    def _attention(self, q, k, v):
        from bigdl_tpu.ops.flash_attention import flash_attention
        from bigdl_tpu.parallel.ring_attention import (
            ring_attention, zigzag_ring_attention)

        if self.sp_axis is not None:
            if self.sp_mode == "zigzag":
                return zigzag_ring_attention(q, k, v, axis=self.sp_axis)
            return ring_attention(q, k, v, axis=self.sp_axis,
                                  causal=self.cfg.causal)
        return flash_attention(q, k, v, causal=self.cfg.causal,
                               impl=self.attn_impl)

    def _block(self, x, bp, dropout_rng, training, remat_mlp=False):
        """One pre-LN block. Works unchanged under tensor parallelism:
        with `tp_axis` set (inside shard_map), wq/wk/wv/w1 arrive
        column-sharded and wo/w2 row-sharded, so the local head count is
        inferred from the weight shape and the two row-parallel matmuls
        are followed by a psum — the Megatron-style split expressed as
        per-device code + XLA collectives.

        remat_mlp=True (the "attn_saved" policy) checkpoints ONLY the
        FFN half: the attention half runs outside any remat region, so
        the flash kernel's custom-vjp residuals (q,k,v,out,lse) stay
        saved and the backward does NOT re-run the forward kernel —
        under a whole-block policy nothing saves the Pallas call's
        outputs (it is not a dot_general), so the fwd kernel reruns
        once per layer in the backward (PROFILE_r05)."""
        c = self.cfg
        b, s, e = x.shape
        d = self.head_dim
        h_local = bp["wq"].shape[-1] // d     # = num_heads / tp_size

        y = self._ln(x, bp["ln1_g"], bp["ln1_b"])
        if self.tp_axis is not None:
            y = tp_identity(y, self.tp_axis)
        # NOTE: a fused qkv matmul (concat weights → one (E, 3HD) gemm →
        # split) was MEASURED SLOWER at 186M — 53.2k vs 55.3k tok/s
        # (July records, another stack): the per-scan-step weight concat and
        # qkv split cost more than the gemm fusion saves. Three gemms
        # at M=B·S are already MXU-efficient; don't re-fuse.
        q = (y @ bp["wq"] + bp["bq"]).reshape(b, s, h_local, d).transpose(0, 2, 1, 3)
        k = (y @ bp["wk"] + bp["bk"]).reshape(b, s, h_local, d).transpose(0, 2, 1, 3)
        v = (y @ bp["wv"] + bp["bv"]).reshape(b, s, h_local, d).transpose(0, 2, 1, 3)
        a = self._attention(q, k, v)
        a = a.transpose(0, 2, 1, 3).reshape(b, s, h_local * d)
        a = a @ bp["wo"]                      # row-parallel: partial sums
        if self.tp_axis is not None:
            a = tp_reduce(a, self.tp_axis)
        a = a + bp["bo"]
        if training and c.dropout > 0.0:
            keep = 1.0 - c.dropout
            k1, dropout_rng = jax.random.split(dropout_rng)
            a = jnp.where(jax.random.bernoulli(k1, keep, a.shape),
                          a, 0.0) / keep
        x = x + a

        def ffn(xres):
            y = self._ln(xres, bp["ln2_g"], bp["ln2_b"])
            aux = jnp.zeros((), jnp.float32)
            if c.moe_experts:
                moe_p = {"router": bp["router"], "w1": bp["w1"],
                         "b1": bp["b1"], "w2": bp["w2"], "b2": bp["b2"]}
                (y, aux), _ = self._moe.apply(
                    {"params": moe_p, "state": {}}, y)
            else:
                if self.tp_axis is not None:
                    y = tp_identity(y, self.tp_axis)
                y = jax.nn.gelu(y @ bp["w1"] + bp["b1"])
                y = y @ bp["w2"]              # row-parallel: partial sums
                if self.tp_axis is not None:
                    y = tp_reduce(y, self.tp_axis)
                y = y + bp["b2"]
            if training and c.dropout > 0.0:
                keep = 1.0 - c.dropout
                k2, _ = jax.random.split(dropout_rng)
                y = jnp.where(jax.random.bernoulli(k2, keep, y.shape),
                              y, 0.0) / keep
            return y, aux

        y, aux = (jax.checkpoint(ffn) if remat_mlp else ffn)(x)
        return x + y, aux

    def apply_hidden(self, variables, tokens, training=False, rng=None,
                     with_aux=False):
        """Forward up to the final LayerNorm: (B, S) int → (B, S, E).
        `with_aux=True` also returns the summed MoE load-balancing
        auxiliary (0.0 for dense configs).

        The training hot path: pair with `head(variables)` and
        `ops.losses.softmax_cross_entropy_chunked` so the (B, S, V)
        log-prob tensor is never materialized (the full `apply` keeps
        the reference-parity LogSoftMax output for eval/predict)."""
        c = self.cfg
        p = variables["params"]
        s = tokens.shape[-1]

        if self.sp_axis is not None and self.sp_mode == "zigzag":
            # zigzag layout: gather positions for half-chunks my and
            # 2n-1-my (rows arrive already permuted by the caller;
            # layout invariant lives in parallel/ring_attention.py)
            from bigdl_tpu.parallel.ring_attention import zigzag_positions

            if s % 2:
                raise ValueError(
                    f"zigzag sp_mode needs an even local sequence "
                    f"length, got {s}")
            n = lax.axis_size(self.sp_axis)
            my = lax.axis_index(self.sp_axis)
            # positions(i) for traced i: both half starts are affine
            # in the device index, so index the stacked table
            zpos_table = jnp.stack(zigzag_positions(n, s))
            pos = p["pos"][zpos_table[my]]
        elif self.sp_axis is not None:
            pos_off = lax.axis_index(self.sp_axis) * s
            pos = lax.dynamic_slice_in_dim(p["pos"], pos_off, s, axis=0)
        else:
            pos = p["pos"][:s]
        x = p["embed"][tokens] + pos

        if training and c.dropout > 0.0 and rng is None:
            raise ValueError(f"{self.name}: dropout needs rng in training")
        base_rng = rng if rng is not None else jax.random.PRNGKey(0)

        remat_mlp = c.remat and c.remat_policy == "attn_saved"

        def body(carry, layer):
            x, aux_sum = carry
            bp, lrng = layer
            x, aux = self._block(x, bp, lrng, training,
                                 remat_mlp=remat_mlp)
            return (x, aux_sum + aux), None

        if c.remat:
            if c.remat_policy == "dots":
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies
                    .dots_with_no_batch_dims_saveable)
            elif c.remat_policy == "attn_saved":
                pass  # per-block FFN checkpoint only (see _block)
            else:
                body = jax.checkpoint(body)
        layer_rngs = jax.random.split(base_rng, c.num_layers)
        (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               (p["blocks"], layer_rngs))

        h = self._ln(x, p["lnf_g"], p["lnf_b"])
        if with_aux:
            return h, aux
        return h

    def head(self, variables):
        """The (E, V) output projection (weight-tied to the embedding
        unless cfg.tie_embeddings=False). Dequantizes a quantized
        embedding/head leaf (serving/quant.py) — fp32 passes through."""
        p = variables["params"]
        return _deq(p["embed"]).T if self.cfg.tie_embeddings \
            else _deq(p["head"])

    def loss(self, variables, tokens, targets, training=False, rng=None,
             chunk: int = 256):
        """Fused mean-NLL training loss — never materializes (B, S, V)
        log-probs (ops/losses.softmax_cross_entropy_chunked)."""
        from bigdl_tpu.ops.losses import softmax_cross_entropy_chunked

        hidden, aux = self.apply_hidden(variables, tokens,
                                        training=training, rng=rng,
                                        with_aux=True)
        nll = softmax_cross_entropy_chunked(hidden, self.head(variables),
                                            targets, chunk=chunk)
        if self.cfg.moe_experts:
            return nll + self.cfg.moe_aux_weight * aux
        return nll

    def apply(self, variables, tokens, training=False, rng=None):
        x = self.apply_hidden(variables, tokens, training=training,
                              rng=rng)
        logits = x @ self.head(variables)
        return jax.nn.log_softmax(logits, axis=-1), variables["state"]

    # ------------------------------------------------- incremental decode
    # The serving plane (bigdl_tpu/serving/): a static-shape per-layer
    # KV cache + a one-row decode step, so generating T tokens costs
    # O(T·S) attention instead of the O(T·S²) of re-forwarding the whole
    # sequence per token — and both steps compile exactly once (fixed
    # max_len, position-indexed dynamic_update_slice writes; shared
    # primitives in bigdl_tpu/ops/kv_cache.py).
    #
    # Quantized serving (ISSUE 17): serving/quant.py repacks
    # serving_params' gemm weights into int8 QuantWeight leaves. The
    # paged trio dequantizes at use via the duck-typed helpers below —
    # models/ never imports serving/ (layering), it just honors any
    # leaf that knows how to `deq()` itself. fp32 leaves pass through
    # untouched, so the fp32 layout stays the bit-identity reference;
    # training paths (apply_hidden/loss) never see QuantWeight.

    def _serving_guard(self, tp_ok=False):
        """`tp_ok=True` on the PAGED trio: those paths are tp-aware
        (ISSUE 10 — head-parallel attention + column-split MLP with
        tp_shard_gather keeping every reduction full-extent) and run
        inside shard_map via bigdl_tpu/serving/tp.py. The dense cache
        path stays single-mesh."""
        if self.sp_axis is not None \
                or (self.tp_axis is not None and not tp_ok):
            raise NotImplementedError(
                "incremental decode runs single-mesh (no sp axis; tp "
                "only on the paged trio via serving/tp.py); build a "
                "plain TransformerLM for dense-cache serving")
        if self.cfg.moe_experts:
            raise NotImplementedError(
                "incremental decode for MoE FFNs (routing is per-token; "
                "not wired yet)")
        if not self.cfg.causal:
            raise ValueError("incremental decode requires causal=True")

    def init_cache(self, batch: int, max_len: Optional[int] = None,
                   dtype=jnp.float32):
        """Per-layer KV cache: a TUPLE of L dicts {'k','v'}, each
        (B, H, S, D). Per-layer (not (L, ...)-stacked) on purpose:
        decode unrolls the layer loop at trace time, and distinct
        buffers let XLA stream each layer's cache in place — a stacked
        cache pays a slice + re-stack copy of the whole thing every
        step (measured on the weights: 148 → 46 ms/token at 43M CPU,
        see serving_params). Batch-major so a serving engine splices
        one request into slot `b` with one dynamic_update_slice per
        layer. `dtype` may be bf16 (halves cache bytes; scores still
        accumulate fp32)."""
        from bigdl_tpu.ops.kv_cache import init_layer_cache

        self._serving_guard()
        c = self.cfg
        s = c.max_len if max_len is None else max_len
        if s > c.max_len:
            raise ValueError(f"cache max_len {s} > positional table "
                             f"{c.max_len}")
        return tuple(
            dict(zip(("k", "v"), init_layer_cache(
                batch, c.num_heads, s, self.head_dim, dtype)))
            for _ in range(c.num_layers))

    def serving_params(self, variables):
        """Repack the stacked (L, ...) training layout into per-layer
        tuples — the fast serving layout. The training stack is what
        makes lax.scan compile once and shard cleanly, but at decode
        time XLA cannot hoist `blocks[l]` slices of a jit argument: it
        copies every layer's weights out of the stack on every token
        (43M CPU: 148 ms/token stacked vs 46 unstacked). One-time
        O(params) repack; pass the result anywhere `variables` goes:
        `model.prefill({"params": sp}, ...)`."""
        from bigdl_tpu.parallel.param_layout import unstack_blocks

        p = variables["params"] if "params" in variables else variables
        if isinstance(p["blocks"], (tuple, list)):
            return p
        out = dict(p)
        out["blocks"] = unstack_blocks(p, self.cfg.num_layers)
        return out

    def _layer_blocks(self, p):
        """Per-layer block params from either layout (tuple passthrough;
        stacked → traced per-layer slices, correct but slow — use
        serving_params for the hot path). Routes through the
        param-layout spine's unstack walk (ISSUE 18)."""
        from bigdl_tpu.parallel.param_layout import unstack_blocks

        return unstack_blocks(p, self.cfg.num_layers)

    def _dense_ffn(self, y, bp):
        """Serving FFN. Under `tp_axis` (paged trio inside shard_map)
        w1/b1 arrive column-sharded: the gelu hidden is computed
        locally (1/tp of the up-projection flops), then
        tp_shard_gather concatenates the disjoint hidden shards so the
        w2 gemm keeps its FULL contraction extent over a replicated
        w2 — bitwise identical to the unsharded step (the down-proj
        flops are the price of bit-identity; see tp_shard_gather)."""
        y = jax.nn.gelu(y @ _deq(bp["w1"]) + bp["b1"])
        if self.tp_axis is not None:
            y = tp_shard_gather(y, self.tp_axis)
        return y @ _deq(bp["w2"]) + bp["b2"]

    def prefill(self, variables, tokens, cache, lengths=None):
        """Fill cache positions [0, S_p) from a right-padded prompt
        batch tokens (B, S_p) and return (logits (B, V) of each row's
        LAST REAL token, cache). `lengths` (B,) int32 — real prompt
        lengths (default: all S_p). Causal attention makes positions
        < length independent of the padding after them; the garbage
        keys/values the pad positions write are never read (decode
        masks beyond the row clock, then overwrites them in place)."""
        from bigdl_tpu.ops.flash_attention import flash_attention
        from bigdl_tpu.ops.kv_cache import write_prefill

        self._serving_guard()
        c = self.cfg
        p = variables["params"] if "params" in variables else variables
        bsz, s = tokens.shape
        if lengths is None:
            lengths = jnp.full((bsz,), s, jnp.int32)
        d = self.head_dim
        x = p["embed"][tokens] + p["pos"][:s]

        new_cache = []
        for bp, lc in zip(self._layer_blocks(p), cache):
            y = self._ln(x, bp["ln1_g"], bp["ln1_b"])
            q = (y @ bp["wq"] + bp["bq"]).reshape(
                bsz, s, c.num_heads, d).transpose(0, 2, 1, 3)
            k = (y @ bp["wk"] + bp["bk"]).reshape(
                bsz, s, c.num_heads, d).transpose(0, 2, 1, 3)
            v = (y @ bp["wv"] + bp["bv"]).reshape(
                bsz, s, c.num_heads, d).transpose(0, 2, 1, 3)
            new_cache.append(dict(zip(
                ("k", "v"), write_prefill(lc["k"], lc["v"], k, v))))
            a = flash_attention(q, k, v, causal=True, impl=self.attn_impl)
            a = a.transpose(0, 2, 1, 3).reshape(bsz, s, c.num_heads * d)
            x = x + a @ bp["wo"] + bp["bo"]
            x = x + self._dense_ffn(
                self._ln(x, bp["ln2_g"], bp["ln2_b"]), bp)

        h = self._ln(x, p["lnf_g"], p["lnf_b"])
        last = jnp.take_along_axis(
            h, (lengths - 1)[:, None, None], axis=1)[:, 0]
        return last @ self.head({"params": p}), tuple(new_cache)

    # ------------------------------------------------- paged KV (ISSUE 8)
    # The serving engine's cache spine: per-layer block POOLS plus a
    # per-slot block TABLE instead of contiguous per-slot buffers
    # (ops/kv_cache.py paged primitives; allocator in
    # serving/kv_pool.py, radix prefix reuse in serving/prefix_cache
    # .py). Same compile contract as the dense path — one suffix
    # prefill executable per bucket + one decode executable — and the
    # full-table attention extent makes every KV row's value bitwise
    # independent of which bucket (or which request) computed it.

    def init_block_pool(self, num_blocks: int, block_size: int,
                        dtype=jnp.float32, slots: int = 1):
        """Per-layer paged KV pools: a TUPLE of L dicts {'k','v'},
        each (num_blocks, block_size, H*D): blocks are axis 0, a
        block's rows are tokens, a row holds the heads side by side
        (ops/kv_cache.init_block_pool says why). Per-layer (not
        stacked) for the same reason as init_cache; block 0 is the
        reserved scratch block (ops/kv_cache.py). `slots` is not used:
        every entry is a "table" (serving/protocol.py)."""
        from bigdl_tpu.ops.kv_cache import init_block_pool

        self._serving_guard(tp_ok=True)
        c = self.cfg
        return tuple(
            dict(zip(("k", "v"), init_block_pool(
                num_blocks, c.num_heads, block_size, self.head_dim,
                dtype)))
            for _ in range(c.num_layers))

    def prefill_paged(self, variables, tokens, pools, table, block_ids,
                      start):
        """Prefill ONE request's SUFFIX into the paged pools: tokens
        (1, bucket) right-padded suffix tokens at global positions
        [start, start+bucket); `table` (1, max_blocks) the slot's full
        block table (reused prefix blocks + the fresh `block_ids`
        (nb,) this call writes); `start` a traced int32 scalar — the
        block-aligned cached-prefix length (0 = cold prefill, the same
        executable). Returns the updated pools; the engine takes its
        first token by re-decoding the last prompt token, so no logits
        head runs here.

        Suffix queries attend through the gathered table — prefix keys
        included — over the FULL table extent with mask j <= start+i,
        which is what makes the written KV bitwise identical whether a
        position is computed cold (start=0, one big bucket) or warm
        (nonzero start, a small suffix bucket): all reductions keep
        the same shape (ops/kv_cache.py module docstring).

        Tensor parallelism (ISSUE 10, inside shard_map via
        serving/tp.py): wq/wk/wv arrive column-sharded by HEAD and the
        pools head-sharded, so each shard prefills its own heads'
        blocks — the attention reductions are per-head (a pure batch
        split, bitwise invariant) and the block table is a replicated
        host-side operand, identical on every shard. tp_shard_gather
        then rebuilds the full attention output so the wo gemm keeps
        its full contraction extent (bitwise == unsharded)."""
        from bigdl_tpu.ops.kv_cache import (block_attention,
                                            gather_block_cache,
                                            write_prompt_blocks)

        self._serving_guard(tp_ok=True)
        p = variables["params"] if "params" in variables else variables
        bsz, s = tokens.shape
        if bsz != 1:
            raise ValueError("prefill_paged fills one request (batch "
                             f"1), got batch {bsz}")
        d = self.head_dim
        start = jnp.asarray(start, jnp.int32)
        x = _embed_rows(p["embed"], tokens) \
            + lax.dynamic_slice_in_dim(p["pos"], start, s, axis=0)

        new_pools = []
        visible = valid = None
        for bp, pl in zip(self._layer_blocks(p), pools):
            h = bp["wq"].shape[-1] // d     # local heads (= H/tp)
            y = self._ln(x, bp["ln1_g"], bp["ln1_b"])
            q = (y @ _deq(bp["wq"]) + bp["bq"]).reshape(
                bsz, s, h, d).transpose(0, 2, 1, 3)
            k = (y @ _deq(bp["wk"]) + bp["bk"]).reshape(
                bsz, s, h, d).transpose(0, 2, 1, 3)
            v = (y @ _deq(bp["wv"]) + bp["bv"]).reshape(
                bsz, s, h, d).transpose(0, 2, 1, 3)
            kp, vp = write_prompt_blocks(pl["k"], pl["v"], k, v,
                                         block_ids)
            new_pools.append({"k": kp, "v": vp})
            kc = gather_block_cache(kp, table, h)   # (1, H, S_tab, D)
            vc = gather_block_cache(vp, table, h)
            if visible is None:                     # same every layer
                jpos = jnp.arange(kc.shape[-2])
                ipos = start + jnp.arange(s)
                visible = (jpos[None, None, :]
                           <= ipos[None, :, None])  # (1, s, S_tab)
                valid = (jpos[None, :] < start + s)  # (1, S_tab)
            a = block_attention(q, kc, vc, visible, valid)
            a = a.transpose(0, 2, 1, 3).reshape(bsz, s, h * d)
            if self.tp_axis is not None:
                a = tp_shard_gather(a, self.tp_axis)
            x = x + a @ _deq(bp["wo"]) + bp["bo"]
            x = x + self._dense_ffn(
                self._ln(x, bp["ln2_g"], bp["ln2_b"]), bp)
        return tuple(new_pools)

    def decode_attn_form(self, tp: int = 1) -> str:
        """The label `InferenceEngine` reports as `attn_form`: the
        operand layout `decode_step_paged` attends the cache in, at
        the heads one of `tp` shards holds
        (ops/kv_cache.paged_attention_form)."""
        from bigdl_tpu.ops.kv_cache import paged_attention_form

        return paged_attention_form(self.cfg.num_heads // tp,
                                    self.head_dim)

    def decode_step_paged(self, variables, tokens, pos, pools, table):
        """One incremental step over the paged pools: tokens/pos (B,)
        as decode_step, `table` (B, max_blocks) int32 block tables.
        Writes each row's k/v at (table[pos // bs], pos % bs) — always
        an exclusive block (copy-on-write: the engine never points a
        row's write position at a shared block) — then attends through
        the gathered table. Same per-ROW isolation contract as
        decode_step: a non-finite row contaminates only its own logits
        and its own exclusive blocks.

        Tensor parallelism (ISSUE 10): same construction as
        prefill_paged — head-sharded pools and head-column-sharded qkv
        make the attention a pure per-head batch split over a
        REPLICATED host-side block table; tp_shard_gather rebuilds the
        full attention output (and _dense_ffn the full mlp hidden) so
        every downstream contraction keeps its unsharded extent and
        the logits come out replicated AND bitwise identical to
        tp=1.

        Speculative verify (ISSUE 15): this step doubles as the
        target's k+1-position scoring entry — serving/speculative.py
        batches a slot's chain positions pos..pos+k as k+1 ROWS of
        one call, every row pointing at the SAME slot's table. Each
        layer writes all rows' k/v (write_decode_blocks, distinct
        (block, offset) destinations) before any row's attention
        gathers the pool, so row j SEES rows < j's writes — and
        because every op here is per-row and a row's attention hangs
        on its own clock alone (the full table extent in the
        head-split form, the row's own live chunks in the rows form:
        ops/kv_cache.py, "Bit-identity contract"), a verify row's
        logits are BITWISE the logits the sequential one-row call
        computes for that position (per-row bits are
        batch-extent-independent on this backend; verified at the
        tiny and 43M shapes). Scoring positions as
        Q=1 rows rather than as a Q=k+1 prefill is deliberate: Q=1
        and Q>=2 gemms lower to different kernels (ops/kv_cache.py),
        so a prefill-shaped verify would score in the wrong regime
        and the spec-vs-target-only token identity would be luck, not
        construction.

        How the cache is attended is `ops/kv_cache.paged_attention`'s
        decision alone, made from the (local) head shape: plain
        decode, draft decode and the k+1-row verify all take it."""
        from bigdl_tpu.ops.kv_cache import (paged_attention,
                                            write_decode_blocks)

        self._serving_guard(tp_ok=True)
        p = variables["params"] if "params" in variables else variables
        bsz = tokens.shape[0]
        d = self.head_dim
        bs = pools[0]["k"].shape[1]
        rows = jnp.arange(bsz)
        block_ids = table[rows, pos // bs]          # (B,)
        offsets = pos % bs
        x = _embed_rows(p["embed"], tokens) + p["pos"][pos]  # (B, E)

        new_pools = []
        for bp, pl in zip(self._layer_blocks(p), pools):
            h = bp["wq"].shape[-1] // d     # local heads (= H/tp)
            y = self._ln(x, bp["ln1_g"], bp["ln1_b"])
            q = (y @ _deq(bp["wq"]) + bp["bq"]).reshape(
                bsz, 1, h, d).transpose(0, 2, 1, 3)
            k = (y @ _deq(bp["wk"]) + bp["bk"]).reshape(
                bsz, 1, h, d).transpose(0, 2, 1, 3)
            v = (y @ _deq(bp["wv"]) + bp["bv"]).reshape(
                bsz, 1, h, d).transpose(0, 2, 1, 3)
            kp, vp = write_decode_blocks(pl["k"], pl["v"], k, v,
                                         block_ids, offsets)
            new_pools.append({"k": kp, "v": vp})
            a = paged_attention(q, kp, vp, table, pos)  # (B, h, 1, D)
            a = a.transpose(0, 2, 1, 3).reshape(bsz, h * d)
            if self.tp_axis is not None:
                a = tp_shard_gather(a, self.tp_axis)
            x = x + a @ _deq(bp["wo"]) + bp["bo"]
            x = x + self._dense_ffn(
                self._ln(x, bp["ln2_g"], bp["ln2_b"]), bp)

        h = self._ln(x, p["lnf_g"], p["lnf_b"])
        return h @ self.head({"params": p}), tuple(new_pools)

    def decode_step(self, variables, tokens, pos, cache):
        """One incremental step: tokens (B,) int32 — the current token
        per row — written at per-row clock `pos` (B,) int32, attended
        against the cache. Returns (logits (B, V) predicting the NEXT
        token, cache). O(S) per token; compiles once for a given cache
        shape (the layer loop unrolls at trace time).

        Reliability contract (serving/engine.py poison isolation):
        every op in this step is per-ROW — embedding lookup, LN,
        per-row cache write, masked cached_attention, gemv — so a
        non-finite row contaminates only its own logits and cache
        rows. The serving engine reduces the returned logits to a (B,)
        finite flag inside its jitted wrapper (utils/anomaly
        .rows_finite) and evicts only the poisoned request; masked
        stale rows in a recycled slot cannot leak because
        cached_attention nan-scrubs invisible value rows."""
        from bigdl_tpu.ops.kv_cache import cached_attention, update_cache

        self._serving_guard()
        c = self.cfg
        p = variables["params"] if "params" in variables else variables
        bsz = tokens.shape[0]
        d = self.head_dim
        x = p["embed"][tokens] + p["pos"][pos]    # (B, E)

        new_cache = []
        for bp, lc in zip(self._layer_blocks(p), cache):
            y = self._ln(x, bp["ln1_g"], bp["ln1_b"])
            q = (y @ bp["wq"] + bp["bq"]).reshape(
                bsz, 1, c.num_heads, d).transpose(0, 2, 1, 3)
            k = (y @ bp["wk"] + bp["bk"]).reshape(
                bsz, 1, c.num_heads, d).transpose(0, 2, 1, 3)
            v = (y @ bp["wv"] + bp["bv"]).reshape(
                bsz, 1, c.num_heads, d).transpose(0, 2, 1, 3)
            kc, vc = update_cache(lc["k"], lc["v"], k, v, pos)
            new_cache.append({"k": kc, "v": vc})
            a = cached_attention(q, kc, vc, pos)  # (B, H, 1, D)
            a = a.transpose(0, 2, 1, 3).reshape(bsz, c.num_heads * d)
            x = x + a @ bp["wo"] + bp["bo"]
            x = x + self._dense_ffn(
                self._ln(x, bp["ln2_g"], bp["ln2_b"]), bp)

        h = self._ln(x, p["lnf_g"], p["lnf_b"])
        return h @ self.head({"params": p}), tuple(new_cache)


def build_lm(vocab_size: int = 256, dim: int = 128, num_heads: int = 4,
             num_layers: int = 2, max_len: int = 512,
             **kw) -> TransformerLM:
    return TransformerLM(TransformerConfig(
        vocab_size=vocab_size, dim=dim, num_heads=num_heads,
        num_layers=num_layers, max_len=max_len), **kw)


def lm_train_matmul_flops_per_token(cfg: TransformerConfig,
                                    ) -> float:
    """Training (fwd+bwd = 3x fwd) matmul FLOPs per token — the
    analytic model-flops count behind every LM MFU number (bench.py,
    scripts/profile_lm.py). Remat recompute is NOT credited (standard
    MFU convention).

    Per layer fwd: qkv+o projections 4*2*e^2, mlp 2*2*e*4e -> 24*e^2;
    attention scores+values 2*2*S*e (halved when causal);
    head 2*e*V. Embedding gather is not a matmul (excluded).
    """
    e, L, S, V = cfg.dim, cfg.num_layers, cfg.max_len, cfg.vocab_size
    per_layer = 24 * e * e + (2 * 2 * S * e) * (0.5 if cfg.causal else 1)
    head = 2 * e * V
    return 3 * (L * per_layer + head)
