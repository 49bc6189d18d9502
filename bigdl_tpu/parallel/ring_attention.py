"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

No reference counterpart — SURVEY.md §5.7 records the reference's sequence
stack as single-node unrolled BPTT with "no ring attention, no
context/sequence parallel". This module is the TPU-first long-context
plane: the sequence axis of attention is sharded over a mesh axis and the
KV chunks travel the ICI ring, so context length scales linearly with the
number of chips.

Two strategies, both called INSIDE shard_map (the mesh axis must be
bound; see make_ring_attention for a jit-ready wrapper):

* `ring_attention(q, k, v, axis)` — each device keeps its Q chunk and
  streams KV chunks around the ring with `lax.ppermute`, accumulating an
  online (running max / running sum) softmax exactly like the flash
  kernel does across KV blocks — the ring IS the outer loop of flash
  attention, with chunks living on different chips. n-1 hops overlap
  compute with ICI transfers; peak memory is O(S_local² · heads) per
  step. Fully differentiable: the backward of `ppermute` is the reverse
  permute, so jax.grad derives the ring backward automatically.
* `ulysses_attention(q, k, v, axis)` — all-to-all swaps the sharded axis
  from sequence to heads (each device gets the FULL sequence for
  heads/n heads), runs dense/flash attention locally, and swaps back.
  Two all-to-alls per call; requires num_heads % axis_size == 0. The
  local attention is global-sequence, so it rides the Pallas flash
  kernel on TPU (`impl=` passthrough).

Causality across chunks uses global positions: device i's rows cover
[i·S_local, (i+1)·S_local); a KV chunk that originated on device j is
fully visible when j < i, diagonal (locally causal) when j == i, and
fully masked when j > i. The masking is positional, so unequal
chunk-vs-source comparisons compile to one `jnp.where` — no dynamic
control flow inside jit.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


_NEG_INF = -1e30


def _chunk_stats(q, k, v, sm_scale, q_off, k_off, causal):
    """Unnormalized attention of a Q chunk against one KV chunk.

    q: (B, H, Sq, D), k/v: (B, H, Sk, D); q_off/k_off are the chunks'
    global sequence offsets — scalars for contiguous chunks, or (Sq,)/
    (Sk,) position VECTORS for non-contiguous layouts (zigzag).
    Returns (o_unnorm (B,H,Sq,D), m (B,H,Sq), l (B,H,Sq)).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        qpos = (q_off[:, None] if jnp.ndim(q_off) == 1 else
                q_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 0))
        kpos = (k_off[None, :] if jnp.ndim(k_off) == 1 else
                k_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 1))
        s = jnp.where(kpos <= qpos, s, _NEG_INF)
    m = jnp.max(s, axis=-1)                              # (B,H,Sq)
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: m == -inf-ish → p would be exp(0)=1; zero them
    alive = (m > _NEG_INF / 2)[..., None]
    p = jnp.where(alive, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    return o.astype(jnp.float32), m, l


def _online_combine(acc, m_acc, l_acc, o_i, m_i, l_i):
    """Merge one chunk's (o, m, l) into the running accumulator."""
    m_new = jnp.maximum(m_acc, m_i)
    a1 = jnp.exp(m_acc - m_new)[..., None]
    a2 = jnp.exp(m_i - m_new)[..., None]
    acc = acc * a1 + o_i * a2
    l_new = l_acc * jnp.exp(m_acc - m_new) + l_i * jnp.exp(m_i - m_new)
    return acc, m_new, l_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str = "seq",
    causal: bool = False,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Ring attention over mesh axis `axis`. Call inside shard_map.

    q, k, v: (B, H, S_local, D) — the local sequence chunk. Returns the
    local chunk of the attention output, (B, H, S_local, D).
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    n = lax.axis_size(axis)
    my = lax.axis_index(axis)
    s_local = q.shape[-2]
    q_off = my * s_local

    acc = jnp.zeros(q.shape[:-1] + (v.shape[-1],), jnp.float32)
    m_acc = jnp.full(q.shape[:-1], _NEG_INF, jnp.float32)
    l_acc = jnp.zeros(q.shape[:-1], jnp.float32)

    perm = [(j, (j + 1) % n) for j in range(n)]
    kv = (k, v)
    for i in range(n):
        # after i hops the resident KV chunk originated on device my - i
        src = (my - i) % n
        k_i, v_i = kv
        o_i, m_i, l_i = _chunk_stats(q, k_i, v_i, sm_scale, q_off,
                                     src * k_i.shape[-2], causal)
        acc, m_acc, l_acc = _online_combine(acc, m_acc, l_acc, o_i, m_i, l_i)
        if i != n - 1:
            kv = jax.tree_util.tree_map(
                lambda x: lax.ppermute(x, axis, perm), kv)

    safe_l = jnp.where(l_acc == 0.0, 1.0, l_acc)[..., None]
    return (acc / safe_l).astype(q.dtype)


def zigzag_positions(n: int, s_local: int):
    """Global row positions device i holds under the zigzag layout.

    Causal masking makes contiguous ring chunks unbalanced: device 0's
    rows attend 1 chunk, device n-1's attend n — half the ring idles.
    Zigzag gives each device TWO half-chunks, one from the front and
    the mirrored one from the back (device i: half-chunks i and
    2n-1-i), so every device's causal work is equal. Returns a list of
    (s_local,) int arrays, one per device.
    """
    h = s_local // 2
    return [jnp.concatenate([i * h + jnp.arange(h, dtype=jnp.int32),
                             (2 * n - 1 - i) * h
                             + jnp.arange(h, dtype=jnp.int32)])
            for i in range(n)]


def zigzag_order(n: int, s: int) -> jax.Array:
    """Global gather order for the zigzag layout over the full sequence
    (concatenation of every device's zigzag_positions), with the
    divisibility check every entry point needs. THE single source of
    the layout invariant — the model's pos gather, the train-step feed
    permutation, and the attention wrapper all use this module's
    functions, so a layout change stays in one place."""
    if s % (2 * n):
        raise ValueError(
            f"zigzag needs sequence length divisible by 2·{n} "
            f"(two half-chunks per device), got {s}")
    return jnp.concatenate(zigzag_positions(n, s // n))


def zigzag_ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str = "seq",
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Load-balanced CAUSAL ring attention. Call inside shard_map.

    q, k, v: (B, H, S_local, D) in the ZIGZAG layout — device i holds
    global rows [i·h, (i+1)·h) ∪ [(2n−1−i)·h, (2n−i)·h) with
    h = S_local/2 (use `make_ring_attention(mode="zigzag")` for the
    global-array wrapper that applies/undoes the permutation).

    Unlike the contiguous causal ring — where the dense per-hop kernel
    computes every (Sq×Sk) score and throws the masked half away — the
    zigzag hop computes ONLY the visible half-blocks. The case analysis
    for kv arriving from `src` (lo = front half-chunk, hi = mirrored
    back half-chunk; positions lo(my) < lo(src<my) < n·h ≤ hi(any)):

        hi_q · lo_k : fully visible for EVERY (my, src)   — always done
        src < my    : + lo_q · lo_k fully visible
        src > my    : + hi_q · hi_k fully visible
        src == my   : + lo_q·lo_k and hi_q·hi_k, each diagonal

    so every hop after the first costs exactly 2 unmasked half-blocks
    on every device: half the dense ring's flops, perfectly balanced
    (hop 0 is the src==my diagonal case on all devices simultaneously).
    The per-device branch is a lax.switch on traced (src vs my) —
    legal SPMD: devices run independent programs between ppermutes.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    n = lax.axis_size(axis)
    my = lax.axis_index(axis)
    s_local = q.shape[-2]
    if s_local % 2:
        raise ValueError("zigzag needs an even local sequence length")
    h = s_local // 2
    half = jnp.arange(h, dtype=jnp.int32)
    q_lo, q_hi = q[..., :h, :], q[..., h:, :]

    def state0(qh):
        return (jnp.zeros(qh.shape[:-1] + (v.shape[-1],), jnp.float32),
                jnp.full(qh.shape[:-1], _NEG_INF, jnp.float32),
                jnp.zeros(qh.shape[:-1], jnp.float32))

    lo, hi = state0(q_lo), state0(q_hi)
    perm = [(j, (j + 1) % n) for j in range(n)]
    kv = (k, v)
    diag = (half[:, None] >= half[None, :])  # within-half causal mask

    def attn_full(qh, kh, vh):
        return _chunk_stats(qh, kh, vh, sm_scale, 0, 0, causal=False)

    def attn_diag(qh, kh, vh):
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh).astype(jnp.float32) \
            * sm_scale
        s = jnp.where(diag, s, _NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vh.dtype), vh)
        return o.astype(jnp.float32), m, l

    for i in range(n):
        src = (my - i) % n
        k_i, v_i = kv
        k_lo, k_hi = k_i[..., :h, :], k_i[..., h:, :]
        v_lo, v_hi = v_i[..., :h, :], v_i[..., h:, :]

        # hi_q sees src's lo half in full, for every (my, src)
        hi = _online_combine(*hi, *attn_full(q_hi, k_lo, v_lo))

        def case_before(lo, hi):   # src < my: lo_q sees lo_k fully
            return _online_combine(*lo, *attn_full(q_lo, k_lo, v_lo)), hi

        def case_after(lo, hi):    # src > my: hi_q sees hi_k fully
            return lo, _online_combine(*hi, *attn_full(q_hi, k_hi, v_hi))

        def case_self(lo, hi):     # src == my: two diagonal halves
            return (_online_combine(*lo, *attn_diag(q_lo, k_lo, v_lo)),
                    _online_combine(*hi, *attn_diag(q_hi, k_hi, v_hi)))

        idx = jnp.where(src == my, 2, jnp.where(src < my, 0, 1))
        lo, hi = lax.switch(idx, [case_before, case_after, case_self],
                            lo, hi)
        if i != n - 1:
            kv = jax.tree_util.tree_map(
                lambda x: lax.ppermute(x, axis, perm), kv)

    def finish(state, qh):
        acc, m_acc, l_acc = state
        safe_l = jnp.where(l_acc == 0.0, 1.0, l_acc)[..., None]
        return (acc / safe_l).astype(qh.dtype)

    return jnp.concatenate([finish(lo, q_lo), finish(hi, q_hi)], axis=-2)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str = "seq",
    causal: bool = False,
    sm_scale: Optional[float] = None,
    impl: Optional[str] = None,
) -> jax.Array:
    """Ulysses (all-to-all) sequence parallelism. Call inside shard_map.

    q, k, v: (B, H, S_local, D) with H divisible by the axis size.
    all_to_all → (B, H/n, S_global, D) → dense/flash attention (global
    sequence, so the plain `causal` flag is exact) → all_to_all back.
    """
    from bigdl_tpu.ops.flash_attention import flash_attention

    n = lax.axis_size(axis)
    h = q.shape[1]
    if h % n:
        raise ValueError(f"num_heads {h} not divisible by axis size {n}")

    def gather_seq(x):   # (B, H, S_local, D) -> (B, H/n, S_global, D)
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                              tiled=True)

    def scatter_seq(x):  # inverse
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                              tiled=True)

    qg, kg, vg = gather_seq(q), gather_seq(k), gather_seq(v)
    out = flash_attention(qg, kg, vg, causal=causal, sm_scale=sm_scale,
                          impl=impl)
    return scatter_seq(out)


def make_ring_attention(
    mesh: Mesh,
    axis: str = "seq",
    causal: bool = False,
    mode: str = "ring",
    impl: Optional[str] = None,
) -> Callable:
    """jit-ready wrapper: (q, k, v) global arrays sharded on the sequence
    axis → attention output with the same sharding. q,k,v: (B,H,S,D),
    S divisible by the axis size.

    mode: "ring" (contiguous chunks) | "ulysses" (all-to-all) |
    "zigzag" (causal-only load-balanced ring: the wrapper permutes the
    global sequence into the zigzag layout, runs the balanced ring, and
    inverse-permutes the output — callers keeping their data in zigzag
    layout end-to-end should call `zigzag_ring_attention` inside their
    own shard_map instead and skip both permutes)."""
    if mode == "zigzag" and not causal:
        raise ValueError("zigzag balancing only applies to causal "
                         "attention; use mode='ring'")

    def body(q, k, v):
        if mode == "ring":
            return ring_attention(q, k, v, axis=axis, causal=causal)
        if mode == "zigzag":
            return zigzag_ring_attention(q, k, v, axis=axis)
        return ulysses_attention(q, k, v, axis=axis, causal=causal,
                                 impl=impl)

    spec = P(None, None, axis, None)
    smapped = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False)
    fn = jax.jit(smapped)
    if mode != "zigzag":
        return fn

    n = mesh.shape[axis]

    def zig(q, k, v):
        order = zigzag_order(n, q.shape[2])
        inv = jnp.argsort(order)
        out = fn(q[:, :, order], k[:, :, order], v[:, :, order])
        return out[:, :, inv]

    return jax.jit(zig)
