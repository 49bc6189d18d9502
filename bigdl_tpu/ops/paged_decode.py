"""One-launch Pallas paged-attention decode kernel (ISSUE 17).

No reference counterpart (like ops/kv_cache.py: the reference's
inference surface is batch `Predictor.scala`). This is the serving
plane's decode-attention hot op in kernel form — the vLLM
PagedAttention shape on TPU: ONE `pl.pallas_call` whose BlockSpec
index maps read the block table DIRECTLY (scalar-prefetch operand), so
each grid step streams one pool block through VMEM. The XLA path pays
a `gather_block_cache` relayout — a full (B, H, nb*bs, D) HBM
materialization of every row's logical cache — on EVERY decode step;
here the gather happens block-by-block into a VMEM scratch and nothing
cache-shaped ever lands in HBM.

Grid: (batch, head-tiles, KV-block-tiles) — batch and heads parallel,
the KV sweep 'arbitrary' (it carries the scratch). Tiles come from the
`BIGDL_PAGED_DECODE_TILES` ("BTxHT") import-time snapshot
(utils/envknobs — never read env at trace time, graftlint
trace-env-read) or per-call arguments; both must divide the launch's
table width / head count (fail-fast, like the flash tiles).

Bit-identity contract: the kernel accumulates the FULL table extent
(nb*bs) in VMEM and runs ONE full-extent softmax per (row, head) —
deliberately NOT a streamed online softmax. Online accumulation
re-orders the fp32 sums block by block, which would detach the kernel
from `ops/kv_cache.paged_attention` (the oracle) and with it every
load-bearing bitwise pin built on the full-extent reduction discipline
(warm==cold, tp, speculative acceptance — ops/kv_cache.py module
docstring). The same Q=1 row is tiny (S·D floats per head), so the
full-extent scratch is cheap; what the kernel saves is the per-step
HBM relayout, not the softmax. Interpret-mode fp32 parity vs the
oracle is BITWISE and pinned by tests/test_paged_decode.py; bf16
pools carry a tolerance contract instead (the cast to fp32 happens at
VMEM load here vs post-gather there — same values, so fp32 stays
bitwise; bf16 is bitwise too but pinned only to tolerance). Compiled
by Mosaic on the chip the kernel is held to a TOLERANCE against the
oracle, not to bits (chip_smoke.py, kernel leg; what the bitwise pins
cost there is ROADMAP C4). This is a FORM AGAINST FORM pin
(ops/kv_cache.py, "Two operand layouts"): the kernel splits heads in
VMEM, so its interpret-mode bits are `paged_attention_heads`'s, at
every width; against `paged_attention` itself they are bitwise where
that takes the head-split form and within 1e-5 where it attends the
rows as stored (a row of whole 128-lane tiles, a narrower head).

Masking matches the oracle exactly: scores masked to -1e30 AFTER the
q·K^T dot (NaN laundering of poisoned masked keys), value rows beyond
the row clock zeroed at VMEM load (0.0 * NaN = NaN poison hygiene —
`block_attention`'s `valid` mask).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from bigdl_tpu.ops.pallas_names import named_pallas_call
from bigdl_tpu.utils import envknobs

_NEG_INF = -1e30


def _default_impl() -> str:
    """'pallas' on a TPU backend, 'interpret' elsewhere (CPU tests run
    the same kernel body through the Pallas interpreter). A backend
    that fails to initialise raises — it is never answered 'cpu'."""
    return "pallas" if jax.devices()[0].platform == "tpu" \
        else "interpret"


def _lane_aligned_head_tile(num_heads: int, head_dim: int) -> int:
    """The fewest heads whose lanes a compiled launch may stream: a
    pool row holds the heads side by side (ops/kv_cache
    .init_block_pool), and Mosaic takes a block of its last axis only
    in multiples of 128 lanes or whole. 64-wide heads go in pairs."""
    for ht in range(1, num_heads):
        if num_heads % ht == 0 and (ht * head_dim) % 128 == 0:
            return ht
    return num_heads


def resolve_tiles(num_blocks: int, num_heads: int,
                  block_tile: Optional[int] = None,
                  head_tile: Optional[int] = None,
                  head_dim: Optional[int] = None) -> Tuple[int, int]:
    """(block_tile, head_tile) for a launch: explicit args win, then
    the `BIGDL_PAGED_DECODE_TILES` import-time snapshot, then (1, the
    lane-aligned head tile for `head_dim`, 1 where none is given).
    Both must DIVIDE the launch's table width / head count — the
    index-map routing streams whole pool blocks, so a ragged tile
    would either read past the table or silently widen the reduction
    extent (breaking oracle parity). Raise instead."""
    env = envknobs.PAGED_DECODE_TILES
    if block_tile is None:
        block_tile = env[0] if env is not None else 1
    if head_tile is None and env is not None:
        head_tile = env[1]
    elif head_tile is None:
        head_tile = 1 if head_dim is None \
            else _lane_aligned_head_tile(num_heads, head_dim)
    if block_tile < 1 or num_blocks % block_tile:
        raise ValueError(
            f"block_tile {block_tile} must divide the table width "
            f"{num_blocks} (BIGDL_PAGED_DECODE_TILES is 'BTxHT')")
    if head_tile < 1 or num_heads % head_tile:
        raise ValueError(
            f"head_tile {head_tile} must divide the head count "
            f"{num_heads} (BIGDL_PAGED_DECODE_TILES is 'BTxHT')")
    return block_tile, head_tile


def _pd_kernel(tbl_ref, pos_ref, q_ref, *refs, block_tile, head_tile,
               num_j, block_size, seq, sm_scale, dup_batch):
    """One grid cell: stream `block_tile` table-routed pool blocks —
    each (block_size, head_tile*D), the cell's heads side by side —
    into the (head_tile, seq, D) VMEM scratch, one head's lanes at a
    time; on the final KV sweep run the oracle's full-extent masked
    softmax per head."""
    k_refs = refs[:block_tile]
    v_refs = refs[block_tile:2 * block_tile]
    o_ref = refs[2 * block_tile]
    k_scr = refs[2 * block_tile + 1]
    v_scr = refs[2 * block_tile + 2]

    b = pl.program_id(0)
    j = pl.program_id(2)
    row_pos = pos_ref[b]
    d = k_scr.shape[-1]

    for i in range(block_tile):
        # whole blocks only, so the sublane offset of every scratch
        # store is block-aligned — say so, Mosaic cannot see it
        # through program_id arithmetic
        base = pl.multiple_of((j * block_tile + i) * block_size,
                              block_size)
        kblk = k_refs[i][0].astype(jnp.float32)      # (bs, ht*D)
        vblk = v_refs[i][0].astype(jnp.float32)
        off = lax.broadcasted_iota(jnp.int32, (block_size, 1), 0)
        valid = (base + off) <= row_pos              # (bs, 1)
        # zero value rows beyond the clock at load: 0-probability rows
        # must contribute exactly 0.0, never 0.0 * NaN (the oracle's
        # `valid` hygiene — block_attention)
        vblk = jnp.where(valid, vblk, 0.0)
        for hh in range(head_tile):
            lanes = slice(hh * d, (hh + 1) * d)
            k_scr[hh, pl.ds(base, block_size), :] = kblk[:, lanes]
            v_scr[hh, pl.ds(base, block_size), :] = vblk[:, lanes]

    @pl.when(j == num_j - 1)
    def _finalize():
        col = lax.broadcasted_iota(jnp.int32, (1, 1, seq), 2)
        visible = col <= row_pos                     # (1, 1, S)
        # batched dot_general over the head axis, q extent 1 — ONE
        # batch dim, which is all Mosaic's matmul takes (the oracle's
        # two batch dims (B, H) are refused: "Up to 1 batch dim
        # supported", libtpu 0.0.34); XLA CPU collapses batch dims
        # anyway, so the interpret-mode bits are the oracle's either
        # way. What XLA CPU does NOT do is treat a batch extent of 1
        # like the rest: it squeezes that dot onto a plain 2D path
        # whose fp32 accumulation bits differ. `dup_batch` (interpret
        # mode only) duplicates such a cell's row to extent 2 and
        # slices — one redundant (1, S) gemv for oracle bits on CPU;
        # the compiled kernel is held to a tolerance and skips it
        q3 = q_ref[0].astype(jnp.float32)            # (ht, 1, D)
        k3 = k_scr[...]                              # (ht, S, D)
        v3 = v_scr[...]
        if dup_batch:
            q3 = jnp.concatenate([q3, q3], axis=0)
            k3 = jnp.concatenate([k3, k3], axis=0)
            v3 = jnp.concatenate([v3, v3], axis=0)
        s = lax.dot_general(
            q3, k3, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)      # (n, 1, S)
        s = s * sm_scale
        # mask AFTER the dot — launders NaN scores a poisoned masked
        # key row would produce (oracle convention)
        s = jnp.where(visible, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        probs = p / jnp.sum(p, axis=-1, keepdims=True)
        out = lax.dot_general(
            probs, v3, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)      # (n, 1, D)
        o_ref[0] = out[:head_tile].astype(o_ref.dtype)


def _paged_decode_pallas(q, k_pool, v_pool, table, pos, sm_scale,
                         block_tile, head_tile, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, h, _, d = q.shape
    nb = table.shape[1]
    bs = k_pool.shape[1]
    if k_pool.shape[2] != h * d:
        raise ValueError(f"pool rows of {k_pool.shape[2]} do not hold "
                         f"{h} heads of {d}")
    seq = nb * bs
    num_j = nb // block_tile

    kernel = functools.partial(
        _pd_kernel, block_tile=block_tile, head_tile=head_tile,
        num_j=num_j, block_size=bs, seq=seq, sm_scale=float(sm_scale),
        # CPU bit-parity only: a cell whose dot batch extent would be
        # 1 must not take XLA-CPU's squeezed single-batch path when
        # the oracle's (B, H)-extent dot doesn't (see _finalize)
        dup_batch=(interpret and head_tile == 1 and b * h > 1))

    head_spec = pl.BlockSpec(
        (1, head_tile, 1, d), lambda bb, hh, jj, tbl, ps: (bb, hh, 0, 0))
    # one spec per streamed block: the index map routes pool block
    # tbl[b, j*bt + i] through VMEM — the table read happens at grid
    # scheduling time (scalar prefetch), never inside the kernel body.
    # A pool row holds the heads side by side, so a head tile is a
    # window of head_tile*D lanes of the last axis
    kv_specs = [
        pl.BlockSpec(
            (1, bs, head_tile * d),
            (lambda bb, hh, jj, tbl, ps, _i=i:
             (tbl[bb, jj * block_tile + _i], 0, hh)))
        for i in range(block_tile)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // head_tile, num_j),
        in_specs=[head_spec] + kv_specs + kv_specs,
        out_specs=head_spec,
        scratch_shapes=[
            pltpu.VMEM((head_tile, seq, d), jnp.float32),
            pltpu.VMEM((head_tile, seq, d), jnp.float32)],
    )
    return named_pallas_call(
        "paged_decode",
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        # batch/head cells are independent; only the kv sweep carries
        # the scratch (flash-forward's convention)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(table.astype(jnp.int32), pos.astype(jnp.int32), q,
      *([k_pool] * block_tile), *([v_pool] * block_tile))


def decode_attention_form(impl: str, num_heads: int, head_dim: int) -> str:
    """What `paged_decode_attention` does with the cache at these
    (local) widths, as a label: "kernel" for the Pallas launch, else
    the operand layout of the XLA arm, "rows" or "heads"
    (ops/kv_cache.paged_attention_form). Static per compiled
    program."""
    if impl != "xla":
        return "kernel"
    from bigdl_tpu.ops.kv_cache import paged_attention_form
    return paged_attention_form(num_heads, head_dim)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, table: jax.Array,
                           pos: jax.Array,
                           sm_scale: Optional[float] = None, *,
                           impl: Optional[str] = None,
                           block_tile: Optional[int] = None,
                           head_tile: Optional[int] = None) -> jax.Array:
    """Drop-in for `ops/kv_cache.paged_attention`: q (B, H, 1, D),
    pools (N, bs, H*D), table (B, nb) int32, pos (B,) row clocks →
    (B, H, 1, D).

    impl: None → auto ('pallas' on TPU, 'interpret' elsewhere);
    'xla' → the gather-then-attend oracle path (paged_attention
    verbatim, in the form its shape selects — the engine's default);
    'pallas' | 'interpret' → the one-launch kernel. fp32 kernel output
    is BITWISE the head-split oracle in interpret mode (module
    docstring); tiles via `block_tile` /
    `head_tile` or the `BIGDL_PAGED_DECODE_TILES` snapshot, else one
    block and the fewest heads whose lanes Mosaic can stream (a
    compiled launch needs head_tile*D to be a multiple of 128, or all
    heads; the interpreter takes any)."""
    if q.shape[-2] != 1:
        raise ValueError(f"paged_decode_attention decodes one row, "
                         f"got q length {q.shape[-2]}")
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    impl = impl or _default_impl()
    if impl == "xla":
        from bigdl_tpu.ops.kv_cache import paged_attention
        return paged_attention(q, k_pool, v_pool, table, pos, sm_scale)
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"impl {impl!r}: expected 'xla', 'pallas' or "
                         "'interpret'")
    bt, ht = resolve_tiles(table.shape[1], q.shape[1], block_tile,
                           head_tile, head_dim=q.shape[-1])
    return _paged_decode_pallas(q, k_pool, v_pool, table, pos,
                                float(sm_scale), bt, ht,
                                interpret=(impl == "interpret"))
