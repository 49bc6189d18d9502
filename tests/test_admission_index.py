"""An admission finds its victim, its next request and its expired ones in an
index (ISSUE 46): `RadixPrefixCache`'s heap of evictable leaves and the
engine's `RequestQueue` give the answers that the parent's walks gave. The
walks live on HERE, as oracles: `_scan_victim` is the parent's `evict_one`
loop, `_ScanQueue` the parent's deque with its `_pop_next`, `_expire_queued`,
`steal_queued`, shedding and id check, copied. Work is counted, never timed.
CPU, tiny sizes."""

import copy
import math
from collections import deque

import jax
import numpy as np
import pytest

from bigdl_tpu import obs
from bigdl_tpu.serving import InferenceEngine, Request
from bigdl_tpu.serving.engine import OverloadError
from bigdl_tpu.serving.kv_pool import BlockPool
from bigdl_tpu.serving.prefix_cache import RadixPrefixCache
from bigdl_tpu.serving.request_queue import RequestQueue


# ------------------------------------------------- (a) the eviction's victim
def _scan_victim(tree):
    """The parent's `evict_one` up to its choice: every node of `_by_block`
    in insertion order, the childless refcount-0 one of the lowest stamp,
    the first of equals. Returns the block id or None."""
    best = None
    for node in tree._by_block.values():
        if node.children or tree.pool.refcount(node.block) > 0:
            continue
        if best is None or node.stamp < best.stamp:
            best = node
    return None if best is None else best.block


class _ScanTree(RadixPrefixCache):
    """The tree with the parent's eviction: the twin of the equivalence."""

    def evict_one(self):
        block = _scan_victim(self)
        if block is None:
            return None
        self._detach(self._by_block[block])
        self.pool.release_cached(block)
        return block


def _drive_tree(tree_cls, seed, host_blocks, steps=350, each_step=False):
    """A seeded walk over everything that moves a node's eligibility or
    stamp, the way the engine calls it (ref the hit chain, re-admit its
    host links, allocate under eviction, insert, mark, and later unref).
    Returns the trace of every answer and of the free list."""
    rng = np.random.RandomState(seed)
    bs = 2
    pool = BlockPool(28, bs)
    tree = tree_cls(pool, host_blocks=host_blocks)
    holds, trace = [], []

    def prompt():
        n = bs * rng.randint(1, 6) + rng.randint(0, bs)
        return [int(t) for t in rng.randint(1, 4, n)]

    def evict():
        want = _scan_victim(tree)
        got = tree.evict_one()
        assert tree_cls is _ScanTree or got == want
        return ("evict", got)

    def alloc(n):
        while pool.free_count < n:
            trace.append(evict())
            if trace[-1][1] is None:
                return None
        return pool.alloc(n)

    def admit():
        toks = prompt()
        cap = (len(toks) - 1) // bs
        nodes = tree.lookup_nodes(toks, cap)
        dev = [n.block for n in nodes if n.block is not None]
        pool.ref(dev)
        host = [n for n in nodes if n.block is None]
        new = alloc(len(host))
        if new is None:
            pool.unref(dev)
            return ("admit", None)
        for nd, b in zip(host, new):
            trace.append(("readmit", tree.readmit(nd, b)))
            pool.mark_cached(b)
        hit = [n.block for n in nodes]
        own = alloc(-(-len(toks) // bs) - len(hit))
        if own is None:
            pool.unref(hit)
            return ("admit", None)
        owned = tree.insert(toks, (hit + own)[:cap])
        for b in owned:
            pool.mark_cached(b)
        holds.append(hit + own)
        return ("admit", tuple(hit), tuple(own), tuple(owned))

    def release():
        if not holds:
            return ("release", None)
        return ("release", tuple(pool.unref(
            holds.pop(rng.randint(len(holds))))))

    def spill():
        victims = tree.spill_victims(int(rng.randint(1, 4)))
        out = []
        for v in victims:
            while tree.host_in_use >= host_blocks:
                if not tree.evict_host_one():
                    break
            if tree.host_in_use < host_blocks:
                out.append(tree.park(v, ("bytes", v.tokens)))
        return ("spill", tuple(out))

    def forget():
        if not holds:
            return ("forget", None)
        hold = holds[rng.randint(len(holds))]
        return ("forget", tuple(tree.forget_block(b)
                                for b in reversed(hold)))

    ops = [(admit, 6), (release, 5),
           (lambda: ("peek", tree.peek_blocks(prompt(), 5)), 2),
           (lambda: ("lookup", tuple(tree.lookup(prompt(), 5))), 2),
           (evict, 3), (forget, 1)]
    if host_blocks:
        ops += [(spill, 2),
                (lambda: ("graft", tree.graft_host(
                    prompt()[:bs * int(rng.randint(1, 4))], "grafted")), 2),
                (lambda: ("evict_host", tree.evict_host_one()), 1)]
    weights = np.array([w for _, w in ops], float)
    for _ in range(steps):
        op = ops[rng.choice(len(ops), p=weights / weights.sum())][0]
        trace.append(op())
        trace.append(("free", tuple(pool._free), pool.cached_count,
                      tree.num_blocks, tree.host_in_use))
        if each_step:
            # the victim the index WOULD hand over now, on a copy, beside
            # the one the parent's loop chooses on the tree itself
            # (its two counters shared, not copied: no eviction draws one)
            twin = copy.deepcopy(tree, {id(c): c for c in (tree._clock,
                                                           tree._seq)})
            assert twin.evict_one() == _scan_victim(tree)
    return trace, tree


@pytest.mark.parametrize("seed,host_blocks", [
    (0, 0), (1, 0), (2, 0), (3, 5), (4, 5), (5, 3)])
def test_the_index_names_the_scans_victim_at_every_step(seed, host_blocks):
    trace, tree = _drive_tree(RadixPrefixCache, seed, host_blocks,
                              each_step=True)
    assert sum(1 for t in trace if t[0] == "evict" and t[1]) > 20
    # drained, it hands over every leaf in the scan's order and no more
    while True:
        want = _scan_victim(tree)
        assert tree.evict_one() == want
        if want is None:
            break


@pytest.mark.parametrize("seed,host_blocks", [(10, 0), (11, 4), (12, 0)])
def test_same_victims_same_block_ids_same_free_list(seed, host_blocks):
    """The whole walk twice, once over the parent's scan: every answer and
    the LIFO free list after every step are the same, so the pool's
    contents are."""
    got, _ = _drive_tree(RadixPrefixCache, seed, host_blocks)
    want, _ = _drive_tree(_ScanTree, seed, host_blocks)
    assert got == want


def test_peek_and_a_ref_touch_no_entry_of_the_index():
    pool = BlockPool(16, 2)
    tree = RadixPrefixCache(pool)
    blocks = pool.alloc(3)
    for b in tree.insert([1, 2, 3, 4, 5, 6], blocks):
        pool.mark_cached(b)
    pool.unref(blocks)
    before = (list(tree._lru), tree.visits)
    assert tree.peek_blocks([1, 2, 3, 4, 5, 6], 3) == 3
    pool.ref(blocks[:1])
    pool.unref(blocks[:1])
    assert (tree._lru, tree.visits) == before


def test_the_index_stays_within_twice_the_tier_without_an_eviction():
    """Park and re-admit in a loop (an engine whose spill tier takes all
    the pressure never calls `evict_one`): the dead entries go when the
    heap outgrows twice the device tier."""
    pool = BlockPool(8, 2)
    tree = RadixPrefixCache(pool, host_blocks=4)
    (b,) = pool.alloc(1)
    for owned in tree.insert([1, 2], [b]):
        pool.mark_cached(owned)
    pool.unref([b])
    for _ in range(500):
        (node,) = tree.spill_victims(1)
        tree.park(node, "bytes")
        (nb,) = pool.alloc(1)
        tree.readmit(node, nb)
        pool.mark_cached(nb)
        pool.unref([nb])
    assert len(tree._lru) <= 2 * tree.num_blocks + 65
    assert tree.evict_one() == nb and tree.evict_one() is None


# ---------------------------------------------------- (b) the queue's answers
class _ScanQueue:
    """The parent's queue behind `RequestQueue`'s surface: ONE deque, and
    every answer the loop that `InferenceEngine` ran over it before
    ISSUE 46 (`_pop_next`, `_expire_queued`, `steal_queued`, `_overload`,
    `cancel`, `submit`'s id set), copied."""

    def __init__(self, engine):
        self._eng = engine
        self._dq = deque()
        self.examined = 0

    def __len__(self):
        return len(self._dq)

    def __iter__(self):
        return iter(list(self._dq))

    def holds(self, request_id):
        return request_id in {r.id for r in self._dq}

    def append(self, request, expires_at=math.inf):
        self._dq.append(request)

    def appendleft(self, request, expires_at=math.inf):
        self._dq.appendleft(request)

    def _pop_at(self, i):
        req = self._dq[i]
        del self._dq[i]
        return req

    def pop_next(self):
        best_i, best_p = 0, None
        for i, r in enumerate(self._dq):
            if best_p is None or r.priority > best_p:
                best_i, best_p = i, r.priority
        return self._pop_at(best_i)

    def pop_last(self):
        best_i, best_p = 0, None
        for i, r in enumerate(self._dq):
            if best_p is None or r.priority <= best_p:
                best_i, best_p = i, r.priority
        return self._pop_at(best_i)

    def popleft(self):
        return self._dq.popleft()

    def lowest(self):
        return min(self._dq, key=lambda r: r.priority)

    def remove(self, request_id):
        for r in self._dq:
            if r.id == request_id:
                self._dq.remove(r)
                return r
        raise KeyError(request_id)

    def pop_expired(self, now):
        eng, keep, out = self._eng, deque(), []
        for r in self._dq:
            t0 = eng._meta[r.id]["t"]
            dl = eng._deadline_at(r)
            qw = t0 + r.max_queue_wait_s \
                if r.max_queue_wait_s is not None else math.inf
            if now >= min(dl, qw):
                out.append(r)
            else:
                keep.append(r)
        self._dq = keep
        return out

    def clear(self):
        self._dq.clear()


@pytest.fixture(scope="module")
def lm():
    from bigdl_tpu.models.transformer import build_lm

    m = build_lm(vocab_size=50, dim=32, num_heads=2, num_layers=1,
                 max_len=64)
    m.build(jax.random.PRNGKey(0))
    return m


def _drive_engine(lm, seed, scan, steps=260, **kw):
    """A seeded walk over every site that mutates the queue, on a clock
    the walk owns. Returns the trace: who was refused, shed, cancelled,
    stolen, expired, admitted (in order) and what every request wrote."""
    rng = np.random.RandomState(seed)
    t = {"now": 0.0}
    args = dict(slots=2, prefill_buckets=(8, 16), block_size=4,
                clock=lambda: t["now"])
    args.update(kw)
    eng = InferenceEngine(lm, **args)
    if scan:
        eng._queue = _ScanQueue(eng)
    trace, seen = [], set()
    real = eng._admit_into
    eng._admit_into = lambda slot, req: (
        trace.append(("seat", req.id, slot)), real(slot, req))[1]

    def settle():
        # what reached `completed` since the last look, in its order
        for rid, res in eng.completed.items():
            if rid not in seen:
                seen.add(rid)
                trace.append(("ended", rid, res.status, res.finish_reason,
                              tuple(res.tokens)))

    def submit():
        rid = None if rng.rand() < 0.6 else int(rng.randint(0, 40))
        req = Request(
            prompt=[int(x) for x in rng.randint(1, 50, rng.randint(2, 14))],
            max_new_tokens=int(rng.randint(1, 5)), id=rid,
            priority=int(rng.choice([0, 0, 0, 1, 2])),
            deadline_s=[None, None, 0.5, 2.0, 50.0][rng.randint(5)],
            max_queue_wait_s=[None, None, 0.3, 1.0][rng.randint(4)],
            tenant=["a", "b", None][rng.randint(3)])
        try:
            return ("submit", eng.submit(req))
        except (ValueError, OverloadError) as e:
            return ("refused", rid, type(e).__name__)

    def cancel():
        rid = int(rng.randint(0, 40))
        try:
            return ("cancel", rid, eng.cancel(rid).finish_reason)
        except KeyError:
            return ("cancel", rid, None)

    def steal():
        got = eng.steal_queued(int(rng.randint(1, 4)))
        bounce = rng.rand() < 0.6
        if bounce:
            for req, t0 in got:
                eng._requeue(req, t0)
        return ("steal", tuple(r.id for r, _ in got), bounce)

    def tick():
        t["now"] += float(rng.choice([0.0, 0.1, 0.4]))
        return ("tick", t["now"])

    def step():
        for res in eng.step():
            eng.completed[res.id] = res
        return ("step", tuple(r.id for r in eng._queue))

    ops, p = [submit, cancel, steal, tick, step], [8, 1, 1, 3, 5]
    for _ in range(steps):
        trace.append(ops[rng.choice(5, p=np.array(p) / sum(p))]())
        settle()
        if rng.rand() < 0.1:
            for rid in list(eng.completed)[:3]:     # claimed: ids reusable
                eng.completed.pop(rid)
    return trace, eng


@pytest.mark.parametrize("seed,kw", [
    (0, {}),
    (1, {"max_queue": 5, "overload_policy": "shed-oldest"}),
    (2, {"max_queue": 5, "overload_policy": "shed-lowest-priority"}),
    (3, {"max_queue": 4, "overload_policy": "reject"}),
    # a pool that seats one long prompt at a time: the failed seating's
    # requeue at the front; a quota: the skipped tenant's
    (4, {"max_len": 20, "pool_blocks": 7, "admit_requeue_budget": 3}),
    (5, {"tenant_kv_quotas": {"a": 1}, "max_queue": 8,
         "overload_policy": "shed-lowest-priority"}),
])
def test_the_queue_gives_the_walks_answers(lm, seed, kw):
    """Order of admissions, expired, shed, cancelled, stolen and refused
    ids, and every token, against the parent's walks on the same walk."""
    got, eng = _drive_engine(lm, seed, scan=False, **kw)
    want, _ = _drive_engine(lm, seed, scan=True, **kw)
    assert got == want
    kinds = {t[0] for t in got} | {t[2] for t in got if t[0] == "ended"}
    assert {"seat", "refused", "steal", "expired", "done"} <= kinds
    assert isinstance(eng._queue, RequestQueue)


def _req(rid, priority=0):
    return Request(prompt=[1], id=rid, priority=priority)


def test_request_queue_reads_as_one_line():
    q = RequestQueue()
    for rid, p in [(0, 0), (1, 2), (2, 0), (3, 1), (4, 2)]:
        q.append(_req(rid, p))
    q.appendleft(_req(5, 0))
    q.appendleft(_req(6, 1))
    assert [r.id for r in q] == [6, 5, 0, 1, 2, 3, 4] and len(q) == 7
    assert q.holds(3) and not q.holds(9)
    assert q.lowest().id == 5                # first of the lowest priority
    assert q.pop_last().id == 2              # its youngest
    assert q.pop_next().id == 1              # highest first, FIFO within
    assert q.popleft().id == 6               # the line's front
    assert q.remove(3).id == 3
    assert [r.id for r in q] == [5, 0, 4]
    with pytest.raises(ValueError, match="already queued"):
        q.append(_req(4))
    q.clear()
    assert not q and list(q) == []


def test_request_queue_expiry_examines_what_is_due_and_one_more():
    q = RequestQueue()
    for rid in range(6):
        q.append(_req(rid), 10.0 + rid if rid % 2 else math.inf)
    assert q.pop_expired(9.0) == [] and q.examined == 1
    assert [r.id for r in q.pop_expired(12.0)] == [1]
    assert q.examined == 1 + 2               # the due one, the next one
    q.appendleft(_req(7), 5.0)
    q.append(_req(8), 11.0)
    # in the LINE's order, whatever their times
    assert [r.id for r in q.pop_expired(20.0)] == [7, 3, 5, 8]
    assert [r.id for r in q] == [0, 2, 4] and not q._expiries
    # a removed request's entry dies in the heap: nothing comes out
    q.append(_req(9), 30.0)
    q.remove(9)
    assert q.pop_expired(40.0) == [] and not q._expiries


def test_request_queue_keeps_its_ends_live_and_its_heap_bounded():
    q = RequestQueue()
    for rid in range(5):
        q.append(_req(rid))
    for rid in (1, 2, 3):
        q.remove(rid)                        # dead entries mid-line
    before = q.examined
    assert q.pop_next().id == 0              # the head and three dead
    assert q.examined - before == 4
    assert q.pop_next().id == 4 and not q._lines
    # served requests' expiries leave the heap before their time
    for rid in range(400):
        q.append(_req(rid), 1e9)
        q.pop_next()
    assert len(q._expiries) <= 2 * len(q) + 65


# -------------------------------------------- (c) the work, counted not timed
@pytest.mark.parametrize("deadline_s", [None, 1e6])
def test_fifty_admissions_over_a_deep_queue_and_a_full_tree(lm, deadline_s):
    """A tree of 2,000 cached blocks that fills the pool and a queue of
    2,000: every admission evicts what it allocates and pops one of 2,000.
    The parent examined 2,000 nodes a victim and 4,000 entries an
    admission; an index about one of each."""
    obs_was = obs.set_enabled(True)
    obs.reset_all()
    obs.set_tracer(obs.SpanTracer(enabled=True))
    try:
        eng = InferenceEngine(lm, slots=4, prefill_buckets=(8, 16),
                              block_size=4, max_len=24, pool_blocks=2001)
        rng = np.random.RandomState(7)
        pool, tree = eng._pool_mgr, eng._prefix
        while pool.free_count:
            n = min(int(rng.randint(1, 5)), pool.free_count)
            blocks = pool.alloc(n)
            toks = [int(x) for x in rng.randint(1, 50, 4 * n)]
            for b in tree.insert(toks, blocks):
                pool.mark_cached(b)
            pool.unref(blocks)
        assert tree.num_blocks + pool.free_count == 2000
        assert tree.num_blocks > 1900
        for _ in range(2000):
            eng.submit(Request(
                prompt=[int(x) for x in rng.randint(1, 50,
                                                    rng.randint(5, 14))],
                max_new_tokens=2, deadline_s=deadline_s))
        while eng.stats["prefill_calls"] < 50:
            for res in eng.step():
                eng.completed[res.id] = res
        admits = [e["args"] for e in obs.get_tracer().events("admit")
                  if e["ph"] == "X"]
        admitted = sum(a["admitted"] for a in admits)
        assert admitted == eng.stats["prefill_calls"] >= 50
        assert sum(a["scanned"] for a in admits) / admitted < 4
        assert eng.stats["pool_evictions"] >= 50
        assert eng.stats["pool_eviction_visits"] \
            / eng.stats["pool_evictions"] < 4
        assert eng.queue_depth == 2000 - admitted
    finally:
        obs.reset_all()
        obs.set_enabled(obs_was)
