"""KV-cache primitives for incremental (autoregressive) decode.

No reference counterpart: the reference's inference surface is batch
`Predictor.scala` (full forwards only). This is the serving-plane hot
op: a static-shape per-layer key/value cache plus an O(S)-per-token
attention read, so generating T tokens costs O(T·S) attention instead
of the O(T·S²) a full re-forward per token pays. Everything here is
shape-static — `max_len` is fixed at cache creation, writes are
position-indexed `dynamic_update_slice`s — so prefill and decode each
compile exactly once regardless of request lengths (the
continuous-batching contract, bigdl_tpu/serving/engine.py).

Layout: caches are (B, H, S, D) — batch-major so a serving engine can
splice one request's rows into a slot with a single
`dynamic_update_slice` and per-row positions stay independent
(continuous batching: every slot advances its own clock).

Numerics match bigdl_tpu/ops/flash_attention: fp32 score accumulation,
masked logits at -1e30 (never -inf), softmax in fp32, output cast back
to the value dtype. The cache may be held in bf16 (`dtype=` at
creation) — scores still accumulate in fp32.

Paged layout (ISSUE 8): the second cache family here pages the
per-layer cache into fixed-size blocks held in ONE preallocated
`(num_blocks, block_size, H*D)` pool per layer (block-major storage,
`init_block_pool`). A sequence's cache is
then a BLOCK TABLE — a static `(max_blocks,)` int32 row of pool
indices — instead of a contiguous `(S, ...)` buffer: eviction, slot
elasticity and prefix sharing become integer surgery on the table plus
host-side ref-counts (serving/kv_pool.py, serving/prefix_cache.py),
never a cache copy. Block 0 is RESERVED as a scratch block: unused
table entries point at it, inactive batch rows write their garbage
into it, and no reader ever sees it unmasked.

Bit-identity contract (the load-bearing bar of the prefix cache):
every PREFILL read (the multi-row suffix prefill) spans the FULL
gathered table extent with per-query masking, so the reduction shapes
(and therefore the fp32 accumulation order) are independent of WHERE a
position was computed: a KV row produced by a cold bucket-64 prefill,
a warm bucket-16 suffix prefill after a prefix hit, or a donor
request's earlier prefill is bitwise the same array, and cached-prefix
decode emits tokens bit-identical to cold decode (pinned by
tests/test_kv_pool.py and the serve_prefix drill). The one-row DECODE
read writes nothing another request shares, so what it owes is less:
that a row's result hangs on that row's own query, clock and cache
rows and on nothing else of the call. The head-split form
(`paged_attention_heads`) keeps it by reading the full extent too; the
rows and latent forms read each slot's own live chunks, in order
(`_ragged_attention`, ISSUE 32), at shapes the table's shape fixes:
the live chunks of a batch are rounded up to one of three compiled
sizes, a sixteenth, half or all of the table's chunks (`_READ_SHARES`,
whose comment says which cell of the benchmark showed what each buys;
ISSUE 39 brought the half), and which one runs changes no bit of a
row's result.
The one deliberate asymmetry: Q=1 decode gemms lower to different
kernels than Q>=2 prefill gemms (measured on CPU XLA), so positions a
decode step wrote are NEVER shared — the serving engine caps reuse and
tree insertion at `(len(prompt) - 1) // block_size` full blocks,
keeping the re-decoded last prompt token (and everything generated)
out of shared blocks.

Two operand layouts, one algorithm (ISSUE 29): the decode read,
`paged_attention`, contracts either per head over a head-split copy of
the gathered table (`paged_attention_heads`, the form the prefill core
has) or over the gathered rows as the pool stores them, with a
block-diagonal query (`paged_attention_rows`): the second where a TPU
would pad the split head, chosen by `paged_attention_form` from the
shape and nothing else. Mask, float32 softmax and hygiene are the
same; the rows form's two contractions are matmuls at the backend's
default matmul precision, as the prefill's are, and its softmax is
taken a chunk at a time and folded (the same mathematics within
float32 rounding). What that does to the pins:
- PATH AGAINST THE SAME PATH hold bitwise in either form, because both
  sides run the one compiled program over bitwise-equal cache rows and
  a row's result hangs on nothing else: warm == cold (the prefill
  programs are untouched, decode-written positions are never shared),
  the spill / re-admit round trip, the speculative verify against
  sequential decode (every row of a call takes the same form and its
  own clock's chunks, whatever rows are called beside it:
  tests/test_paged_attention.py), a slot beside other neighbours, tp
  against tp=1 at equal local form.
- FORM AGAINST FORM are bitwise only where the head-split form runs
  (every toy width of the CPU suites; it is the prefill core's layout
  and the form for 128-wide heads): paged against the dense
  `cached_attention`. Where the rows form runs they agree to a
  tolerance: 1e-5 relative in float32 on the CPU
  (tests/test_rows_attention.py, tests/test_paged_attention.py), one
  bfloat16 pass on a TPU (the benchmark's `correct` judges the served
  tokens). The latent form (`latent_paged_attention`) is held to the
  same tolerance against the naive form (tests/test_latent_moe.py).

Two kinds of leaf (ISSUE 33): beside the table leaves above, whose
rows grow with a sequence, a RING leaf (`init_ring_pool`) is what a
layer that attends a sliding window keeps: each slot owns a fixed run
of `window / block_size + 1` blocks and position p overwrites what
stood there `ring_blocks` blocks of positions earlier. No table is
uploaded for it and no allocator hands its blocks out: the program
derives a slot's window table from its clock (`ring_window`) and reads
it through the same ragged core, with a LOWER bound of visibility
beside the clock's upper one. Grouped-query rows (G key-value heads
side by side, Hq/G query heads reading each) go through that core too
(`grouped_paged_attention`): the block-diagonal query is laid over the
groups. What indexes a leaf by a table block id (spill, handoff,
migration, the prefix tree) does not apply to a ring, and a model with
one refuses those options (models/window_moe.py).

Host spill tier (ISSUE 16): the bit-identity contract is what makes a
host-RAM block tier possible at all — a tree block's content is
immutable after its prefill (COW discipline) and position-invariant in
the reduction, so a refcount-0 block can be fetched to pinned host
numpy (`jax.device_get` of the per-layer k/v block rows — the
HandoffPackage wire format), its pool slot reused, and the bytes later
`device_put`-scattered into ANY free block with only a block-table
patch: the re-admitted read is the same array bitwise, never a
recomputation. The tier lives entirely above this module
(serving/prefix_cache.py parks/re-admits nodes, serving/engine.py
prices the one batched fetch per spill event) — nothing here reads or
writes host state, and the warm==cold pins extend verbatim across a
spill/re-admit round trip (tests/test_kv_pool.py TestSpillTier + the
serve_spill drill).
"""

from __future__ import annotations

import functools
import math
from fractions import Fraction
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_NEG_INF = -1e30


def init_layer_cache(batch: int, num_heads: int, max_len: int,
                     head_dim: int, dtype=jnp.float32
                     ) -> Tuple[jax.Array, jax.Array]:
    """One layer's (k, v) cache, each (B, H, max_len, D), zero-filled.
    Zeros are safe: reads mask every position > the row's clock."""
    shape = (batch, num_heads, max_len, head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def write_prefill(k_cache: jax.Array, v_cache: jax.Array,
                  k_new: jax.Array, v_new: jax.Array,
                  start: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Bulk-write a prompt's (B, H, S_p, D) keys/values at [start,
    start+S_p) — same offset for every row (prefill always lands a
    fresh slot at position 0)."""
    idx = (0, 0, start, 0)
    k_cache = lax.dynamic_update_slice(k_cache, k_new.astype(k_cache.dtype), idx)
    v_cache = lax.dynamic_update_slice(v_cache, v_new.astype(v_cache.dtype), idx)
    return k_cache, v_cache


def update_cache(k_cache: jax.Array, v_cache: jax.Array,
                 k_new: jax.Array, v_new: jax.Array,
                 pos: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Write one decode step's (B, H, 1, D) keys/values at per-row
    positions `pos` (B,) int32. vmapped dynamic_update_slice → a
    batched scatter; shape-static, so the decode step compiles once."""

    def row(kc, vc, kn, vn, p):
        idx = (0, p, 0)
        return (lax.dynamic_update_slice(kc, kn.astype(kc.dtype), idx),
                lax.dynamic_update_slice(vc, vn.astype(vc.dtype), idx))

    return jax.vmap(row)(k_cache, v_cache, k_new, v_new, pos)


def cached_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array,
                     sm_scale: Optional[float] = None) -> jax.Array:
    """One query row per sequence against the cache: q (B, H, 1, D),
    caches (B, H, S, D), pos (B,) — the row's clock, i.e. the index the
    current token was just written at. Attends to positions <= pos
    (earlier garbage beyond the clock is masked; later slots are
    overwritten before ever becoming visible). Returns (B, H, 1, D).

    O(S·D) per token — the decode-path replacement for the O(S²·D)
    full-sequence attention."""
    if q.shape[-2] != 1:
        raise ValueError(f"cached_attention decodes one row, got q "
                         f"length {q.shape[-2]}")
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    kf = k_cache.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf) * sm_scale
    seq = k_cache.shape[-2]
    visible = (jnp.arange(seq)[None, :] <= pos[:, None])  # (B, S)
    # the where AFTER the matmul also launders NaN scores a non-finite
    # masked KEY row would produce (poison hygiene, see below)
    s = jnp.where(visible[:, None, None, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    probs = p / jnp.sum(p, axis=-1, keepdims=True)
    # masked positions get probability exactly 0.0, but 0.0 * NaN = NaN:
    # a non-finite VALUE row beyond the clock (a poisoned request's
    # leftovers in a recycled slot — serving/engine.py poison
    # isolation) would leak into every later read of that slot unless
    # masked rows are zeroed before the weighted sum. Zeros leave
    # healthy traffic bit-identical (0-prob rows contributed 0 either
    # way); visible rows are untouched.
    vf = jnp.where(visible[:, None, :, None],
                   v_cache.astype(jnp.float32), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vf)
    return out.astype(q.dtype)


# --------------------------------------------------------------- paged

def init_block_pool(num_blocks: int, num_heads: int, block_size: int,
                    head_dim: int, dtype=jnp.float32
                    ) -> Tuple[jax.Array, jax.Array]:
    """One layer's paged (k, v) pool, each (num_blocks, block_size,
    H*D), zero-filled: a block is `block_size` token rows, a row holds
    the heads side by side (head h at lanes [h*D, (h+1)*D)). Block 0
    is the scratch block by convention (see module docstring); the
    host allocator (serving/kv_pool.py) never hands it out.

    THE CONTRACT every holder of a pool relies on: blocks are axis 0.
    Spill, re-admission, scrub, handoff and migration index axis 0
    only and never look inside a block; what is inside is this
    module's business (`write_*_blocks`, `gather_block_cache`) and
    serving/tp.py's, which splits the last axis by head.

    Why this shape: a TPU lays an array out in (8, 128) tiles over
    two dimensions of the compiler's choosing. Given a minor dimension
    of D = 64 it would rather make the BLOCK dimension minor-most than
    pad 64 lanes to 128, and every program that indexes the pool by
    block then transposes all of it on the way in and again for the
    donated output. With (block_size, H*D) minor, whole tiles when H*D
    is a multiple of 128 and block_size of 8, the default layout is
    row-major: block-major, unpadded, and a scatter by block id updates
    the donated leaf in place (tests/test_pool_layout.py compiles both
    serving programs for a v5e and holds them to that). Other widths
    get the same shape and a correct pool; how the device tiles them
    is the compiler's choice."""
    width = num_heads * head_dim
    return (init_row_pool(num_blocks, block_size, width, dtype),
            init_row_pool(num_blocks, block_size, width, dtype))


def init_row_pool(num_blocks: int, block_size: int, width: int,
                  dtype=jnp.float32) -> jax.Array:
    """One paged pool leaf, (num_blocks, block_size, width), zeros: a
    block is `block_size` token rows of `width` numbers, whatever a
    model keeps of a token there (a layer's keys or values with the
    heads side by side, or a latent row, models/latent_moe.py). The
    same contract as `init_block_pool`: blocks are axis 0, block 0 is
    scratch."""
    return jnp.zeros((num_blocks, block_size, width), dtype)


def write_prompt_rows(pool: jax.Array, rows: jax.Array,
                      block_ids: jax.Array) -> jax.Array:
    """Bulk-write one request's prefill rows (S, W) into the blocks
    `block_ids` (nb,) of a (N, bs, W) pool leaf; S pads up to nb*bs
    with zeros (beyond the row's clock, masked like any garbage).
    `block_ids` must be distinct (the allocator guarantees it)."""
    nb = block_ids.shape[0]
    s, w = rows.shape
    bs = pool.shape[1]
    pad = nb * bs - s
    if pad < 0:
        raise ValueError(f"{nb} blocks of {bs} cannot hold {s} tokens")
    rows = rows.astype(pool.dtype)
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    # (nb*bs, W) → (nb, bs, W): one row per destination block
    return pool.at[block_ids].set(rows.reshape(nb, bs, w))


def write_decode_rows(pool: jax.Array, rows: jax.Array,
                      block_ids: jax.Array, offsets: jax.Array
                      ) -> jax.Array:
    """Write one decode step's rows (B, W) at per-row (block, offset)
    destinations of a (N, bs, W) pool leaf (see write_decode_blocks
    for which rows may collide, and why that is harmless)."""
    return pool.at[block_ids, offsets].set(rows.astype(pool.dtype))


def gather_block_rows(pool: jax.Array, table: jax.Array) -> jax.Array:
    """Each row's logical cache through its block table: pool
    (N, bs, W) gathered by table (B, nb) → (B, nb*bs, W). A pure
    gather: values pass through bitwise."""
    g = pool[table]                                 # (B, nb, bs, W)
    b, nb, bs, w = g.shape
    return g.reshape(b, nb * bs, w)


def write_prompt_blocks(k_pool: jax.Array, v_pool: jax.Array,
                        k_new: jax.Array, v_new: jax.Array,
                        block_ids: jax.Array
                        ) -> Tuple[jax.Array, jax.Array]:
    """Bulk-write one request's prefill keys/values (1, H, S, D) into
    the blocks `block_ids` (nb,) int32, nb = ceil(S / block_size).
    S pads up to nb*block_size with zeros inside the op (the pad
    positions sit beyond the row's clock, masked like any garbage).
    Shape-static per (S, nb): one executable per prefill bucket.
    `block_ids` must be distinct (the allocator guarantees it) — the
    scatter is then order-independent and deterministic."""
    if k_new.shape[0] != 1:
        raise ValueError("write_prompt_blocks writes one request "
                         f"(batch 1), got batch {k_new.shape[0]}")
    _, h, s, d = k_new.shape

    def rows(x, pool):
        # (H, S, D) → (S, H*D): a token's heads side by side
        return x[0].astype(pool.dtype).transpose(1, 0, 2).reshape(s, h * d)

    return (write_prompt_rows(k_pool, rows(k_new, k_pool), block_ids),
            write_prompt_rows(v_pool, rows(v_new, v_pool), block_ids))


def write_decode_blocks(k_pool: jax.Array, v_pool: jax.Array,
                        k_new: jax.Array, v_new: jax.Array,
                        block_ids: jax.Array, offsets: jax.Array
                        ) -> Tuple[jax.Array, jax.Array]:
    """Write one decode step's (B, H, 1, D) keys/values at per-row
    (block, offset) destinations — block_ids/offsets (B,) int32,
    derived from the block table and the row clocks. Active rows
    target distinct exclusive blocks (copy-on-write: shared blocks are
    read-only, the engine never routes a write at one); inactive rows
    all target the scratch block, whose content no reader ever sees
    unmasked, so colliding garbage writes there are harmless."""
    b = k_new.shape[0]
    kv = k_new.astype(k_pool.dtype).reshape(b, -1)      # (B, H*D)
    vv = v_new.astype(v_pool.dtype).reshape(b, -1)
    return (write_decode_rows(k_pool, kv, block_ids, offsets),
            write_decode_rows(v_pool, vv, block_ids, offsets))


def gather_block_cache(pool: jax.Array, table: jax.Array,
                       num_heads: int) -> jax.Array:
    """Materialize each row's logical cache through its block table:
    pool (N, bs, H*D) gathered by table (B, nb) → (B, H, nb*bs, D),
    H = `num_heads` (the pool does not say where one head ends).
    A pure gather — values pass through bitwise, so attention over the
    gathered array equals attention over an equivalent contiguous
    cache bit-for-bit (tests/test_kv_pool.py pins it)."""
    g = gather_block_rows(pool, table)              # (B, nb*bs, H*D)
    b, s, hd = g.shape
    return g.reshape(b, s, num_heads, hd // num_heads) \
        .transpose(0, 2, 1, 3)


def block_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    visible: jax.Array, valid: jax.Array,
                    sm_scale: Optional[float] = None) -> jax.Array:
    """Masked attention over a gathered block cache — the shared core
    of paged decode AND paged suffix prefill. q (B, H, Q, D), k/v
    (B, H, S, D), `visible` (B, Q, S) bool — per-query causal
    visibility; `valid` (B, S) bool — the union of visibility (the
    row's written region): value rows outside it are zeroed exactly,
    so garbage beyond the clock (scratch blocks, recycled content,
    a poisoned former occupant's NaN) can never ride a 0-probability
    into the weighted sum (0.0 * NaN = NaN — same hygiene as
    cached_attention). Same fp32 conventions as above."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    kf = k.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk",
                   q.astype(jnp.float32), kf) * sm_scale
    # the where AFTER the matmul launders NaN scores a non-finite
    # masked KEY row would produce
    s = jnp.where(visible[:, None, :, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    probs = p / jnp.sum(p, axis=-1, keepdims=True)
    vf = jnp.where(valid[:, None, :, None], v.astype(jnp.float32), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vf)
    return out.astype(q.dtype)


def paged_attention_form(num_heads: int, head_dim: int) -> str:
    """Which operand layout `paged_attention` contracts in, from the
    shape alone: "rows" where a row is whole 128-lane tiles and a head
    is not (H*D % 128 == 0, D % 128 != 0: gpt2-medium's 16 x 64), i.e.
    exactly where a TPU would pad the split head (below); "heads"
    otherwise. A 128-wide head splits without padding and the
    block-diagonal query would only waste matmul work; rows that are
    not whole tiles are the compiler's business already
    (`init_block_pool`). Static per compiled program: the engine
    reports it as a label (`health()["attn_form"]`, the `round`
    span).

    This is `paged_attention`'s rule, `TransformerLM`'s read. The list
    models' rows are grouped-query rows and they read them through
    `grouped_paged_attention`, the rows form at ANY head width, because
    what that form buys is not the padding alone: it reads each slot's
    live chunks where the head-split form gathers the table's full
    extent, relays it by head and widens it to float32. At 16 heads of
    128 (no padding either way), 16 slots of 48 blocks at mixed depths
    (the half read), bfloat16 rows, one layer's read took 0.247 ms in
    the grouped rows form and 0.951 ms in the head-split form (chip
    readings, PERF.md, PR 49: the first model with 128-wide heads,
    `models/loop_lm.py`, 192 such reads a step). The head-split form
    stays `TransformerLM`'s for a 128-wide head: it is the dense
    `cached_attention` bit for bit, which its tests hold it to."""
    rows = (num_heads * head_dim) % 128 == 0 and head_dim % 128 != 0
    return "rows" if rows else "heads"


def paged_attention_heads(q: jax.Array, k_pool: jax.Array,
                          v_pool: jax.Array, table: jax.Array,
                          pos: jax.Array,
                          sm_scale: Optional[float] = None) -> jax.Array:
    """`paged_attention` in the head-split form: the gathered rows are
    relaid (B, H, S, D) and attended per head by `block_attention`,
    the prefill's core: the dense `cached_attention` bit for bit when
    the visible content matches."""
    kc = gather_block_cache(k_pool, table, q.shape[1])
    vc = gather_block_cache(v_pool, table, q.shape[1])
    seq = kc.shape[-2]
    visible = (jnp.arange(seq)[None, :] <= pos[:, None])    # (B, S)
    return block_attention(q, kc, vc, visible[:, None, :], visible,
                           sm_scale)


# chunks a slot's table is read in, and the shares of all the chunks of
# a batch that the read is compiled for: constants chosen once from
# chip measurements (PERF.md, PR 32 and PR 39), never knobs. Every size
# is a copy of the read in every layer's program (1.3 MB of device code
# each at gpt2-medium's widths, 32 MB over 24 layers, and seconds of
# compile), and a compiled size is a cliff (a step one chunk over it
# falls to the next), so a size is here only where a cell of the
# benchmark has shown end to end what it buys:
# - 1/16, a batch that is mostly empty seats: gpt2m-serve-chat (3% of
#   the seats taken), `serve_tpot_mean` 47.80 -> 12.48 ms (ledger, PR 32);
# - 1/2, a full batch of slots at mixed depths: the four backlog cells,
#   whose live chunks are 23-45% of all in every step (PERF.md §7):
#   gpt2m-serve-backlog 1,371 -> 1,911 tokens/s, zaya1-8b-serve-backlog
#   1,616 -> 2,125 (builders' chip runs, PR 39; the two other backlog
#   cells in PERF.md §6). 3/8 would read a quarter less and drop 6% of
#   the GPT-2 backlog's steps and 78% of Trinity's to the read of all;
# - all of it, whatever is fuller than that (Trinity's rings, 84% live).
_CHUNKS_PER_SLOT = 8
_READ_SHARES = (1 / 16, 1 / 2, 1.0)
# the shares by name, as a step's read is counted ("1/16", "1/2", "1")
READ_SHARE_NAMES = tuple(str(Fraction(s)) for s in _READ_SHARES)


def _share_sizes(slots: int, blocks_per_slot: int
                 ) -> Tuple[int, Tuple[int, ...]]:
    """(blocks a chunk, the chunk count each of `_READ_SHARES` is
    compiled at, in their order: two may be equal at a small table)."""
    chunk_blocks = -(-blocks_per_slot // _CHUNKS_PER_SLOT)
    most = slots * -(-blocks_per_slot // chunk_blocks)
    return chunk_blocks, tuple(max(1, math.ceil(most * s))
                               for s in _READ_SHARES)


def ragged_read_sizes(slots: int, blocks_per_slot: int
                      ) -> Tuple[int, Tuple[int, ...]]:
    """The static half of the ragged decode read, from the block
    table's shape alone: (blocks a chunk, the chunk counts a read is
    compiled for, ascending, the last one every chunk of every slot).
    A chunk is `blocks_per_slot / 8` blocks rounded up (8 blocks = 128
    rows at gpt2-medium's 64 x 16 table)."""
    chunk_blocks, by_share = _share_sizes(slots, blocks_per_slot)
    return chunk_blocks, tuple(sorted(set(by_share)))


def _live_chunks(pos, first_block, block_size: int, chunk_blocks: int):
    """Chunks each slot's clock reaches into, (B,): its blocks
    `pos // block_size + 1` rounded up to whole chunks; 0 for a row
    whose first table entry is the scratch block (not seated: a seated
    slot's first entry never is block 0). NumPy on the host, jax.numpy
    inside the program: the same arithmetic."""
    blocks = (pos // block_size + 1) * (first_block != 0)
    return (blocks + chunk_blocks - 1) // chunk_blocks


def _read_size_index(chunks, sizes: Tuple[int, ...]):
    """Index of the smallest compiled read that holds `chunks`."""
    return sum(chunks > n for n in sizes[:-1])


def decode_read(pos: np.ndarray, table: np.ndarray, block_size: int
                ) -> Tuple[str, int]:
    """The decode read of these clocks and this table (the engine's
    host copies, NumPy arrays: nothing here touches the device):
    which of `READ_SHARE_NAMES` it is compiled for, and the blocks it
    gathers: every seated slot's blocks rounded up to whole chunks,
    their sum rounded up to a compiled read. The ragged core takes both
    roundings from the same three functions, so this is what the
    program read. Never more blocks than the table holds: where a
    slot's last chunk is short of whole (`blocks_per_slot` no multiple
    of the chunk), the rest of it is the scratch block and not the
    table's. Where a small table makes two shares one size, the read
    goes by the larger one's name."""
    chunk_blocks, by_share = _share_sizes(*table.shape)
    chunks = int(_live_chunks(pos, table[:, 0], block_size,
                              chunk_blocks).sum())
    n = by_share[int(_read_size_index(chunks, by_share))]
    name = dict(zip(by_share, READ_SHARE_NAMES))[n]
    return name, min(n * chunk_blocks, table.size)


def attended_blocks(pos, table, block_size: int) -> int:
    """Blocks the decode read gathers for these clocks and this table
    (`decode_read`'s second): the engine's `attended_blocks`."""
    return decode_read(np.asarray(pos), np.asarray(table), block_size)[1]


@functools.partial(jax.jit, static_argnames=("lanes", "groups"))
def _ragged_attention(q: jax.Array, k_pool: jax.Array,
                      v_pool: Optional[jax.Array], table: jax.Array,
                      pos: jax.Array, sm_scale, lanes: Optional[int],
                      groups: Optional[int] = None,
                      lo: Optional[jax.Array] = None) -> jax.Array:
    """The decode read's one core: a query matrix q (B, Hq, W) against
    each slot's OWN live rows of the pools (N, bs, W), float32 masked
    softmax, `probs @ rows`. Returns what the caller keeps of the
    product (B, Hq, W), in float32: its first `lanes` lanes, or with
    `lanes=None` row h's own lanes, the diagonal that a block-diagonal
    query asks for: its W/Hq lanes (B, Hq, W/Hq), or with `groups` =
    G key-value heads shared by Hq/G query heads each (grouped-query
    attention) the W/G lanes of head h's group `h // (Hq/G)`
    (B, Hq, W/G). `v_pool=None`: a pool whose rows are key and value
    at once (one gather). `lo` (B,), or None: the lower bound of
    visibility, a slot's rows before `lo` are masked like those after
    its clock (a window: `pos - lo + 1` rows visible). With `groups`
    and `lo` None the program is what it was before they existed.

    Ragged per slot, in one program: the table is cut into chunks of
    `chunk_blocks` blocks, and the live chunks of the whole batch
    (those a seated slot's clock reaches into) are laid end to end,
    slot after slot, as the ITEMS of one batched read: item t gathers
    its chunk's blocks, contracts them with its slot's query, masks
    (-1e30 AFTER the contraction), zeroes value rows beyond the clock
    (0 * NaN) and takes a float32 softmax over its own rows: a (max,
    sum, product) a chunk. A slot's items are then folded in chunk
    order into its result. The number of items is rounded up to one of
    a few compiled sizes (`lax.switch`, one conditional a call); the
    items past the live ones point at the scratch block, are masked
    whole and folded nowhere.

    What a slot's result depends on: its own query, clock and rows.
    Every step of it is per item or per slot at a shape the table's
    shape fixes, so other slots' clocks, the compiled size that runs
    and the batch a row is called in change no bit of it. A row that
    is not seated reads nothing and returns zeros.

    Jitted here, so that a caller outside a jitted step (the tests,
    the benchmark's logit checks) compiles the conditional's branches
    once a shape and not once a call; inside a step it is inlined."""
    hq, width = q.shape[1:]

    def keep(o):                        # (T, Hq, W) -> what is kept
        if lanes is not None:
            return o[..., :lanes]
        if groups is not None:          # head (g, r) keeps group g's lanes
            return jnp.einsum(
                "tgrgd->tgrd", o.reshape(-1, groups, hq // groups, groups,
                                         width // groups)
            ).reshape(-1, hq, width // groups)
        return jnp.einsum("thhd->thd",
                          o.reshape(-1, hq, hq, width // hq))

    b, nb = table.shape
    bs = k_pool.shape[1]
    chunk_blocks, sizes = ragged_read_sizes(b, nb)
    per_slot = -(-nb // chunk_blocks)
    span = chunk_blocks * bs                        # rows of an item
    chunks = _live_chunks(pos, table[:, 0], bs, chunk_blocks)   # (B,)
    ends = jnp.cumsum(chunks)
    starts = ends - chunks
    item = jnp.arange(sizes[-1])
    # the slot an item belongs to: how many slots end at or before it
    slot = jnp.minimum(
        jnp.sum(item[:, None] >= ends[None, :], axis=1), b - 1)
    chunk = jnp.clip(item - starts[slot], 0, per_slot - 1)
    live = item < ends[-1]
    table = jnp.pad(table, ((0, 0), (0, per_slot * chunk_blocks - nb)))
    ids = jnp.where(live[:, None],
                    table.reshape(b, per_slot, chunk_blocks)[slot, chunk],
                    0)                              # (T, chunk_blocks)
    # rows of the item at or before its slot's clock (> span: all)
    seen = jnp.where(live, pos[slot] + 1 - chunk * span, 0)
    # rows of the item before its slot's lower bound (<= 0: none)
    below = None if lo is None else lo[slot] - chunk * span
    mine = jnp.arange(per_slot)[None, :] < chunks[:, None]  # (B, C)
    at = jnp.where(mine, starts[:, None] + jnp.arange(per_slot), 0)
    per_pass = max(1, sizes[-1] // 2)               # items a pass

    def items(first, last):
        """Items [first, last): each one's (max, sum, kept product)."""
        n = last - first
        k = k_pool[ids[first:last]].reshape(n, span, -1)    # (n, S, W)
        v = k if v_pool is None else \
            v_pool[ids[first:last]].reshape(n, span, -1)
        visible = jnp.arange(span)[None, :] < seen[first:last, None]
        if below is not None:
            visible &= jnp.arange(span)[None, :] >= below[first:last, None]
        s = jnp.einsum("thl,tsl->ths", q[slot[first:last]], k,
                       preferred_element_type=jnp.float32) * sm_scale
        # the where AFTER the matmul launders NaN scores a non-finite
        # masked KEY row would produce
        s = jnp.where(visible[:, None, :], s, _NEG_INF)
        m = jnp.max(s, axis=-1)                             # (n, Hq)
        p = jnp.exp(s - m[..., None])
        # 0.0 * NaN = NaN: value rows beyond the clock are zeroed
        # exactly (block_attention's `valid` hygiene)
        v = jnp.where(visible[:, :, None], v, jnp.zeros((), v.dtype))
        o = jnp.einsum("ths,tsl->thl", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return m, jnp.sum(p, axis=-1), keep(o)

    def read(n):
        def branch():
            # at most half of all chunks in one pass: nothing in the
            # program is as large as the whole gathered table
            passes = [items(at0, min(at0 + per_pass, n))
                      for at0 in range(0, n, per_pass)]
            m, l, o = (jnp.concatenate(x) for x in zip(*passes))
            # a slot's items folded in chunk order; entries that are
            # not its own weigh 0 and are zeroed (another slot's NaN)
            ms = jnp.where(mine[..., None], m[at], _NEG_INF)  # (B, C, Hq)
            w = jnp.exp(ms - jnp.max(ms, axis=1, keepdims=True))
            den = jnp.sum(jnp.where(mine[..., None], w * l[at], 0.0),
                          axis=1)
            num = jnp.sum(jnp.where(mine[..., None, None],
                                    w[..., None] * o[at], 0.0), axis=1)
            return num / jnp.where(den > 0, den, 1.0)[..., None]
        return branch

    return lax.switch(_read_size_index(ends[-1], sizes),
                      [read(n) for n in sizes])


def paged_attention_rows(q: jax.Array, k_pool: jax.Array,
                         v_pool: jax.Array, table: jax.Array,
                         pos: jax.Array,
                         sm_scale: Optional[float] = None) -> jax.Array:
    """`paged_attention` over the gathered rows AS THEY ARE STORED,
    heads side by side in the lanes: the head never becomes the minor
    dimension of anything cache-sized. Splitting 1,024 lanes into 16
    heads of 64 makes a TPU pad every 64 to a 128-lane tile, so the
    head-split form rewrites each gathered table at twice its size and
    reads that (48 reshapes, 58-60 of gpt2-medium's 134 ms decode
    step: PERF.md, PR 29). Here the QUERY is laid out instead, block-
    diagonally: row h of `qbd` (B, H, H*D) holds head h's D numbers at
    lanes [h*D, (h+1)*D) and zeros elsewhere, so `qbd . row` is head
    h's score, and head h's output is its own D lanes of
    `probs[b, h] @ v_rows` (the diagonal of (B, H, H, D)). Same
    mathematics, mask (-1e30 AFTER the score contraction), float32
    softmax and zeroed value rows beyond the clock as
    `block_attention`; H times the multiply-adds, on a matrix unit
    that is otherwise idle. The extent is each slot's own live chunks
    (`_ragged_attention`, since PR 32), not the table's.

    Precision: the two contractions are matmuls and run at the
    backend's default matmul precision like every other matmul of the
    engine (the prefill's attention over the same cache included): on
    a TPU one bfloat16 pass with float32 accumulation. Rows stay in
    the pool's dtype into the dot (a bf16 pool is never widened).

    Poison: with a block-diagonal query a non-finite number in ANY
    head's lanes of a VISIBLE key row makes the whole SLOT's scores
    non-finite (0 * NaN), where the head-split form loses that head;
    the slot's logits are non-finite either way and the engine evicts
    by slot. Rows beyond the clock are laundered as ever; other slots
    never see it (the contraction is per slot)."""
    b, h, _, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    diag = jnp.eye(h, dtype=bool)[None, :, :, None]
    qbd = jnp.where(diag, q[:, :, 0, None, :], 0.0) \
        .reshape(b, h, h * d).astype(k_pool.dtype)
    out = _ragged_attention(qbd, k_pool, v_pool, table, pos, sm_scale,
                            lanes=None)                     # (B, H, D)
    return out[:, :, None, :].astype(q.dtype)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    table: jax.Array, pos: jax.Array,
                    sm_scale: Optional[float] = None) -> jax.Array:
    """One query row per sequence against the paged pool: q
    (B, H, 1, D), pools (N, bs, H*D), table (B, nb), pos (B,) — the
    row clock, exactly as cached_attention. Gathers each row's blocks
    and attends positions <= pos: over the full table extent in the
    head-split form, over the row's own live chunks in the rows form.
    A row that is NOT SEATED (its first table entry is the scratch
    block 0, as the engine leaves an empty seat; a seated slot's first
    entry never is) gets the form's answer, not one answer: the rows
    form reads nothing for it and returns zeros, the head-split form
    attends the scratch block's rows up to `pos` like any other
    block's (garbage, non-finite if the scratch rows are). The engine
    emits for neither.
    Returns (B, H, 1, D). One algorithm in two operand layouts, chosen
    by `paged_attention_form` from the shape and nothing else: the
    head-split form is the dense cached_attention bit for bit when the
    visible content matches; the rows form is the same mathematics
    within the rounding of a matmul (module docstring, "Two operand
    layouts")."""
    if q.shape[-2] != 1:
        raise ValueError(f"paged_attention decodes one row, got q "
                         f"length {q.shape[-2]}")
    if paged_attention_form(q.shape[1], q.shape[-1]) == "rows":
        return paged_attention_rows(q, k_pool, v_pool, table, pos,
                                    sm_scale)
    return paged_attention_heads(q, k_pool, v_pool, table, pos, sm_scale)


def latent_paged_attention(q_lat: jax.Array, q_rope: jax.Array,
                           pool: jax.Array, table: jax.Array,
                           pos: jax.Array, rank: int,
                           sm_scale: float) -> jax.Array:
    """One query row per sequence against a pool of LATENT rows
    (multi-head latent attention, decode in the absorbed form). A
    token's row is `[c_kv ; k_rope]`: the `rank`-wide compressed
    key/value, shared by all heads, then the one rotary key. With the
    key up-projection absorbed into the query, `q_lat = q_nope W_UK^T`
    (B, H, rank), a head's score is one contraction over the row,
    `[q_lat ; q_rope] . [c_kv ; k_rope]`, and its output is
    `sum_j p_j c_kv_j` (B, H, rank) in float32, which the caller takes
    through W_UV: the same mathematics as expanding every row to
    per-head keys and values, without ever holding them. pool
    (N, bs, W >= rank + rope, the rest of a row zeros), table (B, nb),
    pos (B,) as paged_attention: positions <= pos visible, invisible
    rows zeroed before the weighted sum (the 0 * NaN hygiene of
    block_attention). The same contraction as `paged_attention_rows`
    with another query and another part of the product kept, so the
    same core: each slot's own live chunks (`_ragged_attention`)."""
    q = jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype)], axis=-1)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pool.shape[-1] - q.shape[-1]))
                ).astype(pool.dtype)
    return _ragged_attention(q, pool, None, table, pos, sm_scale,
                             lanes=rank)


def grouped_paged_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, table: jax.Array,
                            pos: jax.Array, kv_heads: int,
                            sm_scale: float,
                            lo: Optional[jax.Array] = None) -> jax.Array:
    """One query row per sequence against pools of GROUPED-QUERY rows:
    q (B, Hq, D), pools (N, bs, G*D) with G = `kv_heads` heads side by
    side, query head h reads key-value head `h // (Hq/G)`. The rows
    form at any head width (`paged_attention_rows` with a group wider
    than one head): the query is laid out block-diagonally over the G
    groups, head h's D numbers at its group's lanes, so its score is
    one contraction with the row as the pool stores it, and its output
    is its group's lanes of `probs @ v_rows`. The block-diagonal query
    costs G times the multiply-adds, not Hq times. `lo` (B,) is the
    lower bound of visibility, for a window layer (`ring_window`).
    Returns (B, Hq, D) float32. Same core, mask, softmax and hygiene
    as the other two reads (`_ragged_attention`)."""
    b, hq, d = q.shape
    of_group = (jnp.arange(hq)[:, None] // (hq // kv_heads)
                == jnp.arange(kv_heads)[None, :])           # (Hq, G)
    qbd = jnp.where(of_group[None, :, :, None], q[:, :, None, :], 0.0) \
        .reshape(b, hq, kv_heads * d).astype(k_pool.dtype)
    return _ragged_attention(qbd, k_pool, v_pool, table, pos, sm_scale,
                             lanes=None, groups=kv_heads, lo=lo)


# ---------------------------------------------------------------- rings

def init_ring_pool(slots: int, ring_blocks: int, block_size: int,
                   width: int, dtype=jnp.float32) -> jax.Array:
    """One RING pool leaf, (1 + slots * ring_blocks, block_size, width),
    zeros: what a layer that attends a window keeps. The same contract
    as `init_row_pool` (blocks are axis 0, block 0 is scratch), and no
    table and no allocator: slot s owns the blocks `1 + s * ring_blocks`
    onwards for good, and the rows of position p live in its ring block
    `(p // block_size) % ring_blocks`, over whatever was there
    `ring_blocks` blocks of positions earlier. With `ring_blocks =
    window / block_size + 1` the ring always holds the window of the
    newest position whole, and a slot never holds more, however long
    its context."""
    return init_row_pool(1 + slots * ring_blocks, block_size, width, dtype)


def ring_window(pos, seated, block_size: int, ring_blocks: int,
                window: int):
    """A ring leaf seen as what `_ragged_attention` reads: for each slot
    (row b of the batch IS slot b) the block table of its window
    (B, ring_blocks), oldest block first, the clock RELATIVE to that
    table's first row, and the lower bound of visibility `lo` in the
    same terms (`window` rows end at the clock). Entries past the
    clock's block, and every entry of a slot that is not `seated`,
    are the scratch block, as in an engine's table. NumPy on the host,
    jax.numpy inside the program: the same arithmetic, so the engine's
    `attended_rows` is what the program read."""
    xp = np if isinstance(pos, np.ndarray) else jnp
    cur = pos // block_size                          # the clock's block
    first = xp.maximum(cur - (ring_blocks - 1), 0)   # oldest block held
    blocks = first[:, None] + xp.arange(ring_blocks)[None, :]
    base = 1 + xp.arange(pos.shape[0]) * ring_blocks
    table = xp.where(seated[:, None] & (blocks <= cur[:, None]),
                     base[:, None] + blocks % ring_blocks, 0)
    rel = pos - first * block_size
    return table.astype(xp.int32), rel, rel - (window - 1)


def ring_write_blocks(pos, seated, block_size: int, ring_blocks: int):
    """Where a decode step's rows go in a ring leaf, (B,): slot b's ring
    block of position `pos[b]`; the scratch block for a slot that is
    not seated."""
    slot = jnp.arange(pos.shape[0])
    return jnp.where(seated, 1 + slot * ring_blocks
                     + (pos // block_size) % ring_blocks, 0)


def ring_prompt_sources(length: int, block_size: int, ring_blocks: int
                        ) -> np.ndarray:
    """Which of a prompt's blocks each of a slot's ring blocks takes at
    prefill (host, NumPy), (ring_blocks,): the block of positions
    `[b * block_size, (b + 1) * block_size)` for the last `ring_blocks`
    blocks b up to the prompt's last token's, at ring block
    `b % ring_blocks`; -1 for a ring block that gets none and is
    zeroed (a short prompt; and so no row of the slot's last occupant
    outlives the prefill)."""
    last = (length - 1) // block_size
    src = np.full(ring_blocks, -1, np.int32)
    held = np.arange(max(0, last - ring_blocks + 1), last + 1)
    src[held % ring_blocks] = held
    return src


def write_prompt_ring(pool: jax.Array, rows: jax.Array, slot, sources
                      ) -> jax.Array:
    """Prefill's write into a ring leaf: ALL of slot `slot`'s ring
    blocks at once, in place, ring block c from the prompt's block
    `sources[c]` of `rows` (S, W), or zeros where `sources[c] < 0`
    (`ring_prompt_sources`)."""
    bs, w = pool.shape[1:]
    ring_blocks = sources.shape[0]
    rows = jnp.pad(rows.astype(pool.dtype), ((0, -rows.shape[0] % bs),
                                             (0, 0)))
    blocks = rows.reshape(-1, bs, w)
    region = jnp.where((sources >= 0)[:, None, None],
                       blocks[jnp.maximum(sources, 0)],
                       jnp.zeros((), pool.dtype))
    return lax.dynamic_update_slice(
        pool, region, (1 + slot * ring_blocks, 0, 0))
