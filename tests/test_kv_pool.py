"""Paged KV cache + radix prefix reuse (ISSUE 8): block-pool
primitives against the contiguous-cache oracle, allocator/ref-count/
COW invariants, LRU eviction determinism, the warm-vs-cold bitwise
pin, and the compile-count guard re-run under the paged cache."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models.transformer import build_lm
from bigdl_tpu.ops.kv_cache import (cached_attention, gather_block_cache,
                                    init_block_pool, init_layer_cache,
                                    paged_attention, update_cache,
                                    write_decode_blocks,
                                    write_prompt_blocks)
from bigdl_tpu.serving import BlockPool, InferenceEngine, Request
from bigdl_tpu.serving.prefix_cache import RadixPrefixCache


def _tiny_lm(max_len=64, layers=2):
    m = build_lm(vocab_size=50, dim=32, num_heads=2, num_layers=layers,
                 max_len=max_len)
    m.build(jax.random.PRNGKey(0))
    return m


# one module-shared model: engines over the same model object share
# jitted executables, so every block_size=4 engine below compiles the
# paged prefill/decode exactly once for this file
_SHARED_LM = None


def _shared_lm():
    global _SHARED_LM
    if _SHARED_LM is None:
        _SHARED_LM = _tiny_lm()
    return _SHARED_LM


class TestPagedPrimitives:
    """ops/kv_cache.py paged ops vs the dense (contiguous) oracle."""

    def test_paged_attention_matches_contiguous_bitwise(self):
        """Identical KV content read through a SHUFFLED block table
        must produce bit-identical attention output to the dense
        cached_attention — the gather is a pure relayout."""
        rng = np.random.RandomState(0)
        B, H, S, D, bs = 2, 2, 32, 8, 4
        nb = S // bs
        k = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
        q = jnp.asarray(rng.randn(B, H, 1, D), jnp.float32)
        pos = jnp.asarray([13, 27], jnp.int32)

        kd, vd = init_layer_cache(B, H, S, D)
        from bigdl_tpu.ops.kv_cache import write_prefill
        kd, vd = write_prefill(kd, vd, k, v)
        dense = np.asarray(cached_attention(q, kd, vd, pos))

        # scatter the same content into a pool behind shuffled tables
        kp, vp = init_block_pool(1 + B * nb, H, bs, D)
        perm = rng.permutation(np.arange(1, 1 + B * nb))
        table = perm.reshape(B, nb).astype(np.int32)
        for b in range(B):
            kp, vp = write_prompt_blocks(
                kp, vp, k[b:b + 1], v[b:b + 1],
                jnp.asarray(table[b]))
        paged = np.asarray(paged_attention(q, kp, vp,
                                           jnp.asarray(table), pos))
        np.testing.assert_array_equal(dense, paged)

    def test_decode_write_matches_dense_update(self):
        """write_decode_blocks lands one row's k/v at exactly the
        (block, offset) the dense update_cache writes at `pos`."""
        rng = np.random.RandomState(1)
        B, H, S, D, bs = 2, 2, 16, 4, 4
        nb = S // bs
        kn = jnp.asarray(rng.randn(B, H, 1, D), jnp.float32)
        vn = jnp.asarray(rng.randn(B, H, 1, D), jnp.float32)
        pos = np.asarray([5, 14], np.int32)

        kd, vd = init_layer_cache(B, H, S, D)
        kd, vd = update_cache(kd, vd, kn, vn, jnp.asarray(pos))

        kp, vp = init_block_pool(1 + B * nb, H, bs, D)
        table = np.arange(1, 1 + B * nb, dtype=np.int32).reshape(B, nb)
        kp, vp = write_decode_blocks(
            kp, vp, kn, vn,
            jnp.asarray(table[np.arange(B), pos // bs]),
            jnp.asarray(pos % bs, np.int32))
        gk = np.asarray(gather_block_cache(kp, jnp.asarray(table), H))
        gv = np.asarray(gather_block_cache(vp, jnp.asarray(table), H))
        np.testing.assert_array_equal(np.asarray(kd)[0, :, 5],
                                      gk[0, :, 5])
        np.testing.assert_array_equal(np.asarray(vd)[1, :, 14],
                                      gv[1, :, 14])

    def test_write_prompt_blocks_pads_partial_bucket(self):
        """An 8-token bucket into 16-token blocks: one block, zero
        pad tail."""
        rng = np.random.RandomState(2)
        H, D, bs = 2, 4, 16
        k = jnp.asarray(rng.randn(1, H, 8, D), jnp.float32)
        kp, vp = init_block_pool(3, H, bs, D)
        kp, _ = write_prompt_blocks(kp, vp, k, k, jnp.asarray([2]))
        got = np.asarray(kp)
        assert (got[1] == 0).all()           # untouched block
        got = np.asarray(gather_block_cache(kp, jnp.asarray([[2]]), H))
        np.testing.assert_array_equal(got[0, :, :8], np.asarray(k)[0])
        assert (got[0, :, 8:] == 0).all()


class TestModelPagedParity:
    """TransformerLM paged prefill/decode vs the full forward and the
    dense incremental path."""

    @pytest.mark.slow
    def test_paged_decode_matches_full_forward(self):
        """Cold paged prefill + paged decode reproduces the full
        forward's next-token distribution at every position (fp32).
        Tier-2: the property rides tier-1 through the paged engine's
        greedy-vs-full-forward oracle (tests/test_serving.py) and the
        bitwise warm/cold pin below."""
        m = _tiny_lm()
        v = m.variables
        toks = np.random.RandomState(3).randint(0, 50, (1, 20)).astype(
            np.int32)
        full, _ = m.apply(v, jnp.asarray(toks))
        bs, nb = 4, 16 // 4
        pools = m.init_block_pool(1 + nb + 8, bs)
        table = np.zeros((1, 64 // bs), np.int32)
        blocks = np.arange(1, 1 + nb, dtype=np.int32)
        table[0, :nb] = blocks
        pools = m.prefill_paged(v, jnp.asarray(toks[:, :12]).reshape(
            1, 12)[:, :12], pools, jnp.asarray(table),
            jnp.asarray(blocks), 0)
        # grow the table for decode past position 16
        extra = np.arange(1 + nb, 1 + nb + 2, dtype=np.int32)
        table[0, nb:nb + 2] = extra
        for t in range(12, 20):
            logits, pools = m.decode_step_paged(
                v, jnp.asarray(toks[:, t]),
                jnp.full((1,), t, jnp.int32), pools,
                jnp.asarray(table))
            np.testing.assert_allclose(
                np.asarray(jax.nn.log_softmax(logits)),
                np.asarray(full[:, t]), atol=1e-5)

    def test_warm_cold_prefill_bitwise_identical(self):
        """THE extent-invariance pin (ops/kv_cache.py bit-identity
        contract): a position's KV computed by a cold bucket-16
        prefill equals — BITWISE — the same position computed by a
        warm bucket-8 suffix prefill over a reused prefix."""
        m = _tiny_lm()
        v = m.variables
        rng = np.random.RandomState(4)
        toks = rng.randint(1, 50, (1, 16)).astype(np.int32)
        bs = 4
        nb_slot = 64 // bs

        def fresh(n):
            return m.init_block_pool(1 + 2 * nb_slot, bs)

        # cold: all 16 tokens in one bucket-16 prefill
        cold_blocks = np.arange(1, 5, dtype=np.int32)
        cold_tab = np.zeros((1, nb_slot), np.int32)
        cold_tab[0, :4] = cold_blocks
        cold = m.prefill_paged(v, jnp.asarray(toks), fresh(0),
                               jnp.asarray(cold_tab),
                               jnp.asarray(cold_blocks), 0)

        # warm: prefix = first 8 tokens (2 blocks) prefilled first,
        # then the suffix [8:16] as a bucket-8 prefill at start=8
        pools = fresh(1)
        pre_blocks = np.arange(1, 3, dtype=np.int32)
        pre_tab = np.zeros((1, nb_slot), np.int32)
        pre_tab[0, :2] = pre_blocks
        pools = m.prefill_paged(v, jnp.asarray(toks[:, :8]), pools,
                                jnp.asarray(pre_tab),
                                jnp.asarray(pre_blocks), 0)
        suf_blocks = np.arange(3, 5, dtype=np.int32)
        warm_tab = np.zeros((1, nb_slot), np.int32)
        warm_tab[0, :2] = pre_blocks
        warm_tab[0, 2:4] = suf_blocks
        warm = m.prefill_paged(v, jnp.asarray(toks[:, 8:]), pools,
                               jnp.asarray(warm_tab),
                               jnp.asarray(suf_blocks),
                               jnp.asarray(8, jnp.int32))
        for lc, lw in zip(cold, warm):
            for leaf in ("k", "v"):
                np.testing.assert_array_equal(
                    np.asarray(lc[leaf])[1:5],
                    np.asarray(lw[leaf])[1:5])


class TestBlockPool:
    def test_alloc_order_deterministic(self):
        p = BlockPool(8, 4)
        assert p.alloc(3) == [1, 2, 3]
        assert p.capacity == 7 and p.free_count == 4
        assert p.alloc(5) is None            # short → no partial take
        assert p.free_count == 4
        p.unref([2])
        assert p.alloc(1) == [2]             # LIFO: freed block reused
        p2 = BlockPool(8, 4)                 # first, deterministically
        assert p2.alloc(3) == [1, 2, 3]      # fresh pool, same order

    def test_ref_unref_cow_invariants(self):
        p = BlockPool(8, 4)
        (a, b) = p.alloc(2)
        p.mark_cached(a)                     # tree inserts while ref'd
        p.ref([a])                           # a second user (shared)
        assert p.refcount(a) == 2 and p.in_tree(a)
        assert p.unref([a]) == []            # still shared
        assert p.unref([a]) == []            # → cached, NOT freed
        assert p.cached_count == 1 and p.free_count == 5
        assert p.unref([b]) == [b]           # plain block → freed
        p.ref([a])                           # cache revival
        assert p.cached_count == 0 and p.refcount(a) == 1
        with pytest.raises(ValueError, match="unreferenced"):
            p.unref([b])

    def test_guards(self):
        with pytest.raises(ValueError, match="scratch"):
            BlockPool(1, 4)
        with pytest.raises(ValueError, match="block_size"):
            BlockPool(8, 1)
        p = BlockPool(4, 4)
        with pytest.raises(ValueError, match="unreferenced"):
            p.mark_cached(1)


class TestRadixPrefixCache:
    def _cached_chain(self, pool, tree, tokens):
        n = (len(tokens)) // pool.block_size
        blocks = pool.alloc(n)
        owned = tree.insert(tokens, blocks)
        for b in owned:
            pool.mark_cached(b)
        pool.unref(blocks)                   # park as cached
        return blocks

    def test_lookup_insert_roundtrip_and_cap(self):
        pool = BlockPool(32, 4)
        tree = RadixPrefixCache(pool)
        toks = list(range(1, 13))            # 12 tokens = 3 blocks
        blocks = self._cached_chain(pool, tree, toks)
        assert tree.lookup(toks, 3) == blocks
        assert tree.lookup(toks, 2) == blocks[:2]     # caller's cap
        assert tree.lookup(toks[:7], 1) == blocks[:1]
        assert tree.lookup([9] + toks, 3) == []       # shifted: miss
        # a diverging suffix shares only the common block-aligned part
        other = toks[:8] + [40, 41, 42, 43]
        assert tree.lookup(other, 3) == blocks[:2]

    def test_lru_eviction_order_deterministic(self):
        pool = BlockPool(32, 4)
        tree = RadixPrefixCache(pool)
        a = self._cached_chain(pool, tree, list(range(1, 9)))
        b = self._cached_chain(pool, tree, [20, 21, 22, 23])
        tree.lookup(list(range(1, 9)), 2)    # touch chain a
        # LRU leaf is b's block; then a's chain leaf-first (deepest
        # node first — interior nodes wait for their subtree)
        assert tree.evict_one() == b[0]
        assert tree.evict_one() == a[1]
        assert tree.evict_one() == a[0]
        assert tree.evict_one() is None
        assert pool.free_count == pool.capacity

    def test_refd_blocks_never_evict(self):
        pool = BlockPool(32, 4)
        tree = RadixPrefixCache(pool)
        a = self._cached_chain(pool, tree, list(range(1, 9)))
        pool.ref([a[0]])                     # an active user
        assert tree.evict_one() == a[1]      # leaf with ref 0
        assert tree.evict_one() is None      # a[0] pinned
        pool.unref([a[0]])
        assert tree.evict_one() == a[0]

    def test_forget_block_leaf_only(self):
        pool = BlockPool(32, 4)
        tree = RadixPrefixCache(pool)
        a = self._cached_chain(pool, tree, list(range(1, 9)))
        assert not tree.forget_block(a[0])   # interior: refused
        assert tree.forget_block(a[1])
        assert tree.forget_block(a[0])       # now a leaf


class TestEnginePaged:
    def test_warm_vs_cold_bit_identity_in_cobatch(self):
        """The tentpole acceptance: a cached-prefix admission decodes
        tokens bit-identical to the cold run of the same request —
        co-batched with a stranger."""
        m = _shared_lm()
        A = dict(prompt=[5, 9, 3, 7, 2, 8, 4, 6, 1, 3, 9, 2, 7],
                 max_new_tokens=5, temperature=0.8, seed=11)
        S = dict(prompt=[30, 31, 32], max_new_tokens=5,
                 temperature=0.9, seed=4)
        eng = InferenceEngine(m, slots=2, prefill_buckets=(8, 16),
                              block_size=4)
        cold = eng.run([Request(**A)])[0]
        assert eng.stats["prefix_hits"] == 0
        warm, stranger = eng.run([Request(**A), Request(**S)])
        assert eng.stats["prefix_hits"] == 1
        assert eng.stats["prefix_tokens_saved"] == 12
        assert warm.tokens == cold.tokens
        alone_s = InferenceEngine(m, slots=2, prefill_buckets=(8, 16),
                                  block_size=4).run([Request(**S)])[0]
        assert stranger.tokens == alone_s.tokens

    def test_compile_count_guard_paged(self):
        """The #buckets+1 contract under the PAGED cache: ragged
        traffic WITH prefix hits and LRU evictions still compiles
        exactly (#buckets used) suffix prefills + 1 decode, and a
        second wave (all shapes + reuse paths warm) compiles
        NOTHING."""
        m = _tiny_lm()                       # fresh: attribute traces
        eng = InferenceEngine(m, slots=2, prefill_buckets=(8, 16),
                              block_size=4, max_len=32,
                              pool_blocks=12)
        rng = np.random.RandomState(0)
        shared = list(rng.randint(1, 50, 9))
        wave = [Request(prompt=shared + [int(x)], max_new_tokens=3,
                        seed=i)
                for i, x in enumerate(rng.randint(1, 50, 3))]
        wave += [Request(prompt=list(rng.randint(1, 50, 4)),
                         max_new_tokens=3, seed=9)]
        eng.run(wave)
        assert eng.stats["prefix_hits"] >= 2          # shared head hit
        assert eng.stats["prefill_traces"] == 2       # buckets 8 + 16
        assert eng.stats["decode_traces"] == 1
        # churn until the pool must evict, then a reuse wave: still 0
        for i in range(4):
            eng.run([Request(prompt=list(rng.randint(1, 50, 9)),
                             max_new_tokens=2, seed=20 + i)])
        eng.run([Request(prompt=shared + [7], max_new_tokens=3,
                         seed=40),
                 Request(prompt=list(rng.randint(1, 50, 12)),
                         max_new_tokens=2, seed=41)])
        assert eng.stats["pool_evictions"] > 0
        assert eng.stats["prefill_traces"] == 2
        assert eng.stats["decode_traces"] == 1

    @pytest.mark.slow
    def test_pool_exhausted_finishes_gracefully(self):
        """A generation that outgrows an exhausted pool finishes
        'pool_exhausted' (partial tokens kept, status done); the
        co-resident request is unaffected. Tier-2: the allocator's
        failure mode is unit-tested (TestBlockPool) and the admission
        requeue path rides tier-1 via the hit-chain-pin test."""
        m = _shared_lm()
        eng = InferenceEngine(m, slots=2, prefill_buckets=(8, 16),
                              block_size=4, max_len=32, pool_blocks=9,
                              prefix_cache=False)
        a, b = eng.run([
            Request(prompt=[1, 2, 3, 4, 5, 6, 7, 8, 9], max_new_tokens=20,
                    seed=1),
            Request(prompt=[9, 8, 7, 6, 5, 4, 3, 2, 1], max_new_tokens=20,
                    seed=2)])
        # 8 usable blocks, both 9-token prompts hold a 16-bucket
        # (4 blocks) each: growth past position 16 finds an empty free
        # list — slot 0 finishes 'pool_exhausted' with its 8 partial
        # tokens (status done), and its freed blocks deterministically
        # let slot 1 run to completion
        assert a.status == "done"
        assert a.finish_reason == "pool_exhausted"
        assert len(a.tokens) == 8
        assert b.status == "done" and b.finish_reason == "max_tokens"
        assert len(b.tokens) == 20
        # the freed blocks serve the next request normally
        c = eng.run([Request(prompt=[2, 4, 6], max_new_tokens=3,
                             seed=3)])[0]
        assert c.finish_reason == "max_tokens"

    def test_hit_chain_pinned_against_admission_eviction(self):
        """Regression: the allocator's LRU eviction during an
        admission must never reclaim the hit chain that same admission
        just matched (it is refcount-0 'cached' until the admission
        refs it — the engine pins it BEFORE allocating). Starved of
        blocks, the admission requeues instead; once the co-resident
        request frees blocks it admits with the prefix intact and
        decodes bit-identical to cold."""
        m = _shared_lm()

        def eng(prefix):
            return InferenceEngine(m, slots=2, prefill_buckets=(16,),
                                   block_size=4, max_len=32,
                                   pool_blocks=9, prefix_cache=prefix)

        P = dict(prompt=[5, 9, 3, 7, 2, 8, 4, 6, 1, 3, 9, 2, 7],
                 max_new_tokens=3, temperature=0.8, seed=11)
        cold = eng(False).run([Request(**P)])[0]
        e = eng(True)
        e.run([Request(**P)])                # caches P's 3-block chain
        # a long-running stranger pins 4 of the 5 free blocks...
        lid = e.submit(Request(prompt=[20, 21, 22, 23, 24, 25, 26, 27,
                                       28],
                               max_new_tokens=6, seed=1))
        e.step()
        # ...so Q (= P resubmitted) matches the cached chain but finds
        # only 1 free block for its 4-block suffix bucket: it must
        # WAIT (requeue), not let eviction eat its own hit chain
        qid = e.submit(Request(**P))
        while e._queue or any(r is not None for r in e._req):
            for res in e.step():
                e.completed[res.id] = res
        q = e.completed[qid]
        assert e.completed[lid].status == "done"
        assert e.stats["prefix_hits"] == 1
        assert e.stats["prefix_tokens_saved"] == 12
        assert q.tokens == cold.tokens

    def test_poisoned_exclusive_chain_fully_forgotten(self):
        """Regression: a poisoned request's EXCLUSIVE inserted chain
        must be forgotten whole (deep-to-shallow — forget_block
        removes leaves only), not just its deepest block: nothing a
        poisoned request wrote may stay addressable in the radix
        tree."""
        from bigdl_tpu.utils import faults

        m = _shared_lm()
        eng = InferenceEngine(m, slots=2, prefill_buckets=(8, 16),
                              block_size=4)
        P = dict(prompt=[5, 9, 3, 7, 2, 8, 4, 6, 1, 3, 9, 2, 7],
                 max_new_tokens=5, temperature=0.8, seed=11)
        faults.set_plan(faults.FaultPlan("serve_nan@1"))
        try:
            got = eng.run([Request(**P)])[0]
        finally:
            faults.set_plan(None)
        assert got.status == "poisoned"
        assert eng.health()["prefix"]["tree_blocks"] == 0
        # a resubmission must prefill COLD — zero reuse of anything
        # the poisoned request wrote
        eng.run([Request(**P)])
        assert eng.stats["prefix_hits"] == 0

    def test_knob_validation(self):
        m = _shared_lm()
        with pytest.raises(ValueError, match="multiple of block_size"):
            InferenceEngine(m, slots=1, max_len=30, block_size=4)
        with pytest.raises(ValueError, match="block_size"):
            InferenceEngine(m, slots=1, block_size=1)
        with pytest.raises(ValueError, match="pool_blocks"):
            InferenceEngine(m, slots=1, block_size=16, pool_blocks=3)

    def test_admit_requeue_budget_bounds_spin(self):
        """Regression (ISSUE 16 satellite): an admission that can
        NEVER succeed (pool pinned by an external holder, nothing in
        flight to free blocks) must not spin the request through the
        queue forever — after `admit_requeue_budget` requeues it
        finishes 'pool_exhausted' (status done, zero tokens) and bumps
        the exhaustion counter; the pool stays serviceable once blocks
        return."""
        m = _shared_lm()
        eng = InferenceEngine(m, slots=1, prefill_buckets=(8,),
                              block_size=4, max_len=16, pool_blocks=5,
                              prefix_cache=False,
                              admit_requeue_budget=3)
        pinned = eng._pool_mgr.alloc(4)      # every usable block held
        r = eng.run([Request(prompt=[1, 2, 3], max_new_tokens=2,
                             seed=0)])[0]
        assert r.status == "done"
        assert r.finish_reason == "pool_exhausted"
        assert r.tokens == []
        assert eng.stats["admit_requeue_exhausted"] == 1
        eng._pool_mgr.unref(pinned)
        ok = eng.run([Request(prompt=[1, 2, 3], max_new_tokens=2,
                              seed=0)])[0]
        assert ok.finish_reason == "max_tokens"

    def test_multi_turn_resubmission_reuses_history(self):
        """The loadgen multi-turn shape: turn 2 resubmits turn 1's
        prompt + output and must hit the cached history prefix, with
        tokens bit-identical to a cold engine's run of the same
        turn-2 prompt."""
        m = _shared_lm()
        eng = InferenceEngine(m, slots=2, prefill_buckets=(8, 16),
                              block_size=4)
        t1 = eng.run([Request(prompt=[3, 1, 4, 1, 5, 9, 2, 6],
                              max_new_tokens=4, temperature=0.7,
                              seed=13)])[0]
        follow = list(t1.prompt) + list(t1.tokens) + [42]
        t2 = eng.run([Request(prompt=follow, max_new_tokens=4,
                              temperature=0.7, seed=14)])[0]
        assert eng.stats["prefix_hits"] == 1
        assert eng.stats["prefix_tokens_saved"] >= 4
        cold = InferenceEngine(m, slots=2, prefill_buckets=(8, 16),
                               block_size=4).run(
            [Request(prompt=follow, max_new_tokens=4, temperature=0.7,
                     seed=14)])[0]
        assert t2.tokens == cold.tokens


class TestSpillTier:
    """Host-RAM block spill tier (ISSUE 16): tree-level spill/park/
    re-admit/graft units, the engine round-trip bitwise pin, and the
    compile-count guard re-pinned with the tier armed."""

    def _cached_chain(self, pool, tree, tokens):
        blocks = pool.alloc(len(tokens) // pool.block_size)
        for b in tree.insert(tokens, blocks):
            pool.mark_cached(b)
        pool.unref(blocks)
        return blocks

    def test_spill_victim_selection_lru_refd_protect(self):
        """spill_victims returns LRU refcount-0 device nodes (stamp,
        then insertion-order tie-break), skips ref'd blocks and the
        protected chain — and unlike eviction has NO leaf-only rule."""
        pool = BlockPool(32, 4)
        tree = RadixPrefixCache(pool, host_blocks=8)
        a = self._cached_chain(pool, tree, list(range(1, 9)))
        b = self._cached_chain(pool, tree, [20, 21, 22, 23])
        tree.lookup(list(range(1, 9)), 2)    # touch chain a
        got = [n.block for n in tree.spill_victims(3)]
        assert got == [b[0], a[0], a[1]]     # b LRU; a root-first
        pool.ref([a[0]])                     # an active user pins it
        assert [n.block for n in tree.spill_victims(3)] == [b[0], a[1]]
        pool.unref([a[0]])
        prot = frozenset(tree.lookup_nodes(list(range(1, 9)), 2))
        assert [n.block for n in tree.spill_victims(3, prot)] == [b[0]]

    def test_park_readmit_roundtrip_and_tier_surfaces(self):
        """park moves a victim's block to the free list and its bytes
        to the host tier; the device-block surface (lookup) stops at
        the parked node while the tier-aware walk still matches;
        readmit hands the bytes back and re-joins the device tier."""
        pool = BlockPool(32, 4)
        tree = RadixPrefixCache(pool, host_blocks=8)
        toks = list(range(1, 9))
        a = self._cached_chain(pool, tree, toks)
        free0 = pool.free_count
        node = tree.spill_victims(1)[0]      # root-most of chain a
        assert node.block == a[0]
        assert tree.park(node, "BYTES") == a[0]
        assert pool.free_count == free0 + 1
        assert (tree.num_blocks, tree.host_in_use) == (1, 1)
        assert tree.lookup(toks, 2) == []    # chain starts on host
        assert len(tree.lookup_nodes(toks, 2)) == 2
        assert tree.peek_blocks(toks, 2) == 2
        nb = pool.alloc(1)[0]
        assert tree.readmit(node, nb) == "BYTES"
        pool.mark_cached(nb)
        pool.unref([nb])
        assert tree.lookup(toks, 2) == [nb, a[1]]
        assert tree.host_in_use == 0

    def test_host_eviction_childless_only_and_graft(self):
        """evict_host_one drops only CHILDLESS host nodes (deepest
        first — interior nodes wait for their subtree); graft_host
        seeds parents-first, lets incumbents win, refuses orphans,
        makes room by host-LRU, and is disabled at host_blocks=0."""
        pool = BlockPool(32, 4)
        tree = RadixPrefixCache(pool, host_blocks=8)
        toks = list(range(1, 9))
        self._cached_chain(pool, tree, toks)
        for node in tree.spill_victims(2):
            tree.park(node, bytes(node.tokens))
        assert tree.host_in_use == 2
        assert tree.evict_host_one()         # deepest (childless)
        assert tree.evict_host_one()         # then its parent
        assert not tree.evict_host_one()
        assert tree.peek_blocks(toks, 2) == 0

        t2 = RadixPrefixCache(BlockPool(8, 4), host_blocks=2)
        assert t2.graft_host(toks[:4], "D0")
        assert t2.graft_host(toks, "D1")
        assert t2.host_in_use == 2
        # orphan: depth-2 entry whose parent chunk was never imported
        assert not t2.graft_host([70, 71, 72, 73, 80, 81, 82, 83],
                                 "ORPHAN")
        assert not t2.graft_host(toks[:4], "X")     # incumbent wins
        assert t2.graft_host([90, 91, 92, 93], "D2")  # evicts LRU
        assert t2.host_in_use == 2
        assert t2.peek_blocks(toks, 2) == 1  # D1 made way for D2
        t3 = RadixPrefixCache(BlockPool(8, 4))      # tier disabled
        assert not t3.graft_host(toks[:4], "D0")

    def test_spill_readmit_round_trip_bit_identity(self):
        """THE tentpole acceptance pin: a chain pushed to the host
        tier by pool pressure and re-admitted on the next hit decodes
        tokens BITWISE identical to the cold run AND to the original
        warm run — spilled blocks are bytes, never recomputation."""
        m = _shared_lm()
        P = dict(prompt=[5, 9, 3, 7, 2, 8, 4, 6, 1, 3, 9, 2, 7],
                 max_new_tokens=3, temperature=0.8, seed=11)
        F = dict(prompt=[30, 31, 32, 33, 34, 35, 36, 37, 38, 39, 40,
                         41, 42],
                 max_new_tokens=3, temperature=0.8, seed=2)
        cold = InferenceEngine(m, slots=1, prefill_buckets=(8, 16),
                               block_size=4, max_len=20, pool_blocks=6,
                               prefix_cache=False).run(
            [Request(**P)])[0]
        # 5 usable blocks: P's 13-token prompt holds 4, so its cached
        # 3-block chain MUST spill to admit F — and F's must spill to
        # re-admit P
        eng = InferenceEngine(m, slots=1, prefill_buckets=(8, 16),
                              block_size=4, max_len=20, pool_blocks=6,
                              spill=True, host_blocks=8)
        first = eng.run([Request(**P)])[0]
        assert first.tokens == cold.tokens
        eng.run([Request(**F)])              # pressure: P's chain spills
        assert eng.stats["kv_spill_blocks"] >= 1
        assert eng.health()["prefix"]["host_in_use"] >= 1
        warm = eng.run([Request(**P)])[0]
        assert eng.stats["kv_readmit_blocks"] >= 1
        assert eng.stats["prefix_hits"] >= 1
        assert warm.tokens == cold.tokens == first.tokens

    def test_compile_guard_with_spill_armed(self):
        """The #buckets+1 contract holds with the tier armed: spill
        waves and host re-admissions compile ZERO new executables — a
        re-admit is a device_put + block-table patch, never a prefill
        of the parked positions."""
        m = _tiny_lm()                       # fresh: attribute traces
        eng = InferenceEngine(m, slots=1, prefill_buckets=(8, 16),
                              block_size=4, max_len=20, pool_blocks=6,
                              spill=True, host_blocks=8)
        P = dict(prompt=[5, 9, 3, 7, 2, 8, 4, 6, 1, 3, 9, 2, 7],
                 max_new_tokens=3, temperature=0.8, seed=11)
        F = dict(prompt=[30, 31, 32, 33, 34, 35, 36, 37, 38, 39, 40,
                         41, 42],
                 max_new_tokens=3, temperature=0.8, seed=2)
        eng.run([Request(**P)])              # bucket 16 + decode
        eng.run([Request(**F)])              # spill wave
        eng.run([Request(**P)])              # re-admit + bucket-8 suffix
        assert eng.stats["prefill_traces"] == 2
        assert eng.stats["decode_traces"] == 1
        eng.run([Request(**F)])              # spill AND re-admit again:
        eng.run([Request(**P)])              # every path now warm
        assert eng.stats["kv_spill_blocks"] > 0
        assert eng.stats["kv_readmit_blocks"] > 0
        assert eng.stats["prefill_traces"] == 2
        assert eng.stats["decode_traces"] == 1

    def test_spill_knob_validation(self):
        m = _shared_lm()
        with pytest.raises(ValueError, match="prefix_cache"):
            InferenceEngine(m, slots=1, block_size=4, max_len=16,
                            spill=True, prefix_cache=False)
        with pytest.raises(ValueError, match="host_blocks"):
            InferenceEngine(m, slots=1, block_size=4, max_len=16,
                            host_blocks=4)
        with pytest.raises(ValueError, match="host_blocks"):
            InferenceEngine(m, slots=1, block_size=4, max_len=16,
                            spill=True, host_blocks=0)
        with pytest.raises(ValueError, match="admit_requeue_budget"):
            InferenceEngine(m, slots=1, block_size=4, max_len=16,
                            admit_requeue_budget=0)


class TestGroupedQueryAndRings:
    """ISSUE 33: grouped-query rows through the decode read's core
    (query head h over key-value head h // (Hq/G)) against the dense
    `cached_attention` with the key-value heads repeated, and a ring
    leaf read through the table of its window with the lower bound of
    visibility."""

    @staticmethod
    def _dense(q, k, v, pos, lo=None):
        """q (B, Hq, D), k/v (B, G, S, D): `cached_attention` with each
        key-value head repeated for its group; with `lo`, over the
        cache's rows from `lo` on alone."""
        rep = q.shape[1] // k.shape[1]
        out = []
        for b in range(q.shape[0]):
            first = 0 if lo is None else max(int(lo[b]), 0)
            out.append(cached_attention(
                q[b:b + 1, :, None, :],
                jnp.repeat(k[b:b + 1, :, first:], rep, axis=1),
                jnp.repeat(v[b:b + 1, :, first:], rep, axis=1),
                pos[b:b + 1] - first)[0, :, 0])
        return np.asarray(jnp.stack(out))

    @pytest.mark.parametrize("hq,g,d", [(32, 4, 128), (4, 2, 8), (6, 6, 4)])
    def test_grouped_rows_equal_cached_attention_with_repeated_heads(
            self, hq, g, d):
        from bigdl_tpu.ops.kv_cache import (grouped_paged_attention,
                                            init_row_pool,
                                            write_prompt_rows)

        rng = np.random.RandomState(hq + d)
        B, S, bs = 3, 48, 4
        nb = S // bs
        k = jnp.asarray(rng.randn(B, g, S, d), jnp.float32)
        v = jnp.asarray(rng.randn(B, g, S, d), jnp.float32)
        q = jnp.asarray(rng.randn(B, hq, d), jnp.float32)
        pos = jnp.asarray([5, 31, 47], jnp.int32)
        table = rng.permutation(np.arange(1, 1 + B * nb)).reshape(B, nb)
        kp = vp = init_row_pool(1 + B * nb, bs, g * d)
        for b in range(B):          # a token's G heads side by side
            kp = write_prompt_rows(
                kp, k[b].transpose(1, 0, 2).reshape(S, g * d),
                jnp.asarray(table[b]))
            vp = write_prompt_rows(
                vp, v[b].transpose(1, 0, 2).reshape(S, g * d),
                jnp.asarray(table[b]))
        got = grouped_paged_attention(q, kp, vp, jnp.asarray(table), pos,
                                      g, d ** -0.5)
        assert got.shape == (B, hq, d) and got.dtype == jnp.float32
        want = self._dense(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())
        # and with a lower bound: the rows from `lo` on alone
        lo = jnp.asarray([-3, 20, 40], jnp.int32)
        got = grouped_paged_attention(q, kp, vp, jnp.asarray(table), pos,
                                      g, d ** -0.5, lo=lo)
        want = self._dense(q, k, v, pos, lo)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())

    @pytest.mark.parametrize("bad", [0.0, np.nan], ids=["clean", "nan"])
    def test_a_ring_reads_the_last_window_of_a_long_history(self, bad):
        """Three slots decode to different lengths, one past three wraps
        of its ring, one not seated; each step's rows go where
        `ring_write_blocks` says. The read through `ring_window` equals
        dense attention over the last `window` positions of the slot's
        whole history; the block of rows that the ring still holds and
        the window has left behind changes nothing, non-finite or
        not."""
        from bigdl_tpu.ops.kv_cache import (grouped_paged_attention,
                                            init_ring_pool, ring_window,
                                            ring_write_blocks,
                                            write_decode_rows)

        rng = np.random.RandomState(7)
        B, g, d, hq, bs, window = 3, 2, 8, 4, 4, 8
        ring = window // bs + 1
        S = 44
        k = rng.randn(B, g, S, d).astype(np.float32)
        v = rng.randn(B, g, S, d).astype(np.float32)
        lengths = np.array([6, 44, 20])
        seated = jnp.asarray([True, True, False])
        kp = vp = init_ring_pool(B, ring, bs, g * d)
        assert kp.shape == (1 + B * ring, bs, g * d)
        for t in range(S):
            pos = jnp.asarray(np.minimum(t, lengths - 1), jnp.int32)
            ids = ring_write_blocks(pos, seated, bs, ring)
            kp = write_decode_rows(
                kp, jnp.asarray(k[np.arange(B), :, np.asarray(pos)]
                                .reshape(B, -1)), ids, pos % bs)
            vp = write_decode_rows(
                vp, jnp.asarray(v[np.arange(B), :, np.asarray(pos)]
                                .reshape(B, -1)), ids, pos % bs)
        pos = jnp.asarray(lengths - 1, jnp.int32)
        # slot 1 is at position 43: its ring holds the blocks of
        # positions 32-43, its window sees 36-43; the block of 32-35 is
        # ring block 8 % 3 of its region
        out = 1 + 1 * ring + 8 % ring
        assert np.asarray(kp[out]).any()
        kp, vp = kp.at[out].set(bad), vp.at[out].set(bad)
        q = jnp.asarray(rng.randn(B, hq, d), jnp.float32)
        table, rel, lo = ring_window(pos, seated, bs, ring, window)
        host = ring_window(np.asarray(pos), np.asarray(seated), bs, ring,
                           window)
        for a, b in zip((table, rel, lo), host):    # the same arithmetic
            np.testing.assert_array_equal(np.asarray(a), b)
        assert not np.asarray(table[2]).any()       # not seated: scratch
        got = np.asarray(grouped_paged_attention(
            q, kp, vp, table, rel, g, d ** -0.5, lo=lo))
        want = self._dense(q, jnp.asarray(k), jnp.asarray(v), pos,
                           np.asarray(pos) - (window - 1))
        np.testing.assert_allclose(got[:2], want[:2], rtol=1e-5,
                                   atol=1e-5 * np.abs(want[:2]).max())
        assert not got[2].any()                     # reads nothing
