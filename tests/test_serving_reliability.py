"""Serving-plane reliability layer (ISSUE 4): request lifecycle
(deadlines, queue-wait TTL, cancellation, terminal statuses), admission
control / backpressure, priority scheduling, health snapshot, and the
engine edge cases around slot admission. The fault-injected legs
(poison co-batch, retry, watchdog trip) are drilled bit-deterministically
in scripts/fault_drill.py --plane serving and run as tier-1 via
tests/test_fault_drill.py; this file covers the host-side lifecycle
machinery those drills ride on."""

import jax
import numpy as np
import pytest

from bigdl_tpu.models.transformer import build_lm
from bigdl_tpu.serving import (EngineDegraded, InferenceEngine,
                               OverloadError, Request, bucket_histogram)

# one module-shared model: engines over the same model share jitted
# executables, so this file pays the decode/prefill compile once
_LM = None


def _lm():
    global _LM
    if _LM is None:
        _LM = build_lm(vocab_size=50, dim=32, num_heads=2, num_layers=2,
                       max_len=64)
        _LM.build(jax.random.PRNGKey(0))
    return _LM


def _engine(**kw):
    kw.setdefault("slots", 2)
    kw.setdefault("prefill_buckets", (8,))
    return InferenceEngine(_lm(), **kw)


def _drain(eng, clk=None, dt=1.0):
    """Step until empty, advancing the fake clock between steps."""
    while eng._queue or any(r is not None for r in eng._req):
        for res in eng.step():
            eng.completed[res.id] = res
        if clk is not None:
            clk["t"] += dt


class TestLifecycle:
    def test_deadline_expiry_queued_vs_decoding(self):
        clk = {"t": 0.0}
        eng = _engine(clock=lambda: clk["t"])
        eng.submit(Request(prompt=[1, 2], max_new_tokens=8, seed=1))
        eng.submit(Request(prompt=[3, 4], max_new_tokens=8, seed=2))
        qid = eng.submit(Request(prompt=[5, 6], max_new_tokens=4,
                                 deadline_s=2.0))
        _drain(eng, clk)
        q = eng.completed[qid]
        assert q.status == "expired" and q.tokens == []
        assert q.finish_reason == "expired"
        # while decoding: partial tokens survive the expiry
        clk["t"] = 0.0
        eng2 = _engine(clock=lambda: clk["t"])
        did = eng2.submit(Request(prompt=[1, 2, 3], max_new_tokens=8,
                                  deadline_s=2.0))
        _drain(eng2, clk)
        d = eng2.completed[did]
        assert d.status == "expired" and len(d.tokens) == 3
        assert eng2.stats["deadline_misses"] == 1

    def test_max_queue_wait_expires_queued_only(self):
        """max_queue_wait_s bounds time-in-queue; once decoding it no
        longer applies (unlike deadline_s)."""
        clk = {"t": 0.0}
        eng = _engine(slots=1, clock=lambda: clk["t"])
        eng.submit(Request(prompt=[1, 2], max_new_tokens=6, seed=1))
        wid = eng.submit(Request(prompt=[3, 4], max_new_tokens=2,
                                 max_queue_wait_s=3.0))
        _drain(eng, clk)
        assert eng.completed[wid].status == "expired"
        # admitted fast → the same TTL never fires while decoding
        clk["t"] = 0.0
        eng2 = _engine(slots=1, clock=lambda: clk["t"])
        oid = eng2.submit(Request(prompt=[3, 4], max_new_tokens=6,
                                  max_queue_wait_s=3.0))
        _drain(eng2, clk)
        assert eng2.completed[oid].status == "done"

    def test_cancel_queued_and_inflight(self):
        eng = _engine(slots=1)
        a = eng.submit(Request(prompt=[1, 2], max_new_tokens=6, seed=1))
        b = eng.submit(Request(prompt=[3, 4], max_new_tokens=6, seed=2))
        eng.step()                                # a decoding, b queued
        res_b = eng.cancel(b)
        assert res_b.status == "shed"
        assert res_b.finish_reason == "cancelled" and res_b.tokens == []
        res_a = eng.cancel(a)
        assert res_a.status == "shed" and len(res_a.tokens) == 1
        assert eng.stats["cancelled"] == 2
        with pytest.raises(KeyError):
            eng.cancel(a)
        assert not eng._queue and eng._free_slots() == [0]

    def test_result_statuses_and_run_never_keyerrors(self):
        """run() returns shed/expired results in submission order —
        terminal statuses are results, not exceptions."""
        eng = _engine(max_queue=1, overload_policy="shed-oldest")
        out = eng.run([Request(prompt=[1, 2], max_new_tokens=2, seed=1),
                       Request(prompt=[3, 4], max_new_tokens=2, seed=2),
                       Request(prompt=[5, 6], max_new_tokens=2, seed=3)])
        assert [r.status for r in out] == ["shed", "shed", "done"]


class TestAdmission:
    def test_reject_policy_raises(self):
        eng = _engine(max_queue=1, overload_policy="reject")
        eng.submit(Request(prompt=[1, 2]))
        with pytest.raises(OverloadError, match="queue full"):
            eng.submit(Request(prompt=[3, 4]))
        assert eng.stats["rejected"] == 1
        eng.run()

    def test_priority_admission_order(self):
        """Highest priority leaves the queue first (FIFO within a
        priority), regardless of arrival order."""
        eng = _engine(slots=1)
        lo = eng.submit(Request(prompt=[1, 2], max_new_tokens=2,
                                priority=0))
        hi = eng.submit(Request(prompt=[3, 4], max_new_tokens=2,
                                priority=9))
        mid = eng.submit(Request(prompt=[5, 6], max_new_tokens=2,
                                 priority=5))
        order = []
        while eng._queue or any(r is not None for r in eng._req):
            for res in eng.step():
                order.append(res.id)
        assert order == [hi, mid, lo]

    def test_shed_lowest_priority_victim_selection(self):
        eng = _engine(max_queue=2, overload_policy="shed-lowest-priority")
        low = eng.submit(Request(prompt=[1, 2], priority=1))
        eng.submit(Request(prompt=[3, 4], priority=7))
        eng.submit(Request(prompt=[5, 6], priority=4))   # sheds `low`
        assert eng.completed[low].status == "shed"
        new = eng.submit(Request(prompt=[7, 8], priority=0))
        assert eng.completed[new].status == "shed"       # newcomer lowest
        assert eng.stats["shed"] == 2
        eng.run()

    def test_expired_queue_does_not_count_toward_overload(self):
        """A queue full of already-dead TTLs must not reject fresh
        traffic (submit expires stale entries before the max_queue
        check) — and the dead entries report 'expired', not 'shed'."""
        clk = {"t": 0.0}
        eng = _engine(slots=1, max_queue=2, overload_policy="reject",
                      clock=lambda: clk["t"])
        eng.submit(Request(prompt=[1, 2], max_new_tokens=6, seed=1))
        eng.step()                          # slot busy, queue empty
        s1 = eng.submit(Request(prompt=[3, 4], deadline_s=1.0))
        s2 = eng.submit(Request(prompt=[5, 6], deadline_s=1.0))
        clk["t"] = 5.0                      # both queued TTLs dead
        fresh = eng.submit(Request(prompt=[7, 8], max_new_tokens=2))
        assert eng.completed[s1].status == "expired"
        assert eng.completed[s2].status == "expired"
        assert eng.stats["rejected"] == 0
        _drain(eng)
        assert eng.completed[fresh].status == "done"

    def test_invalid_knobs_rejected(self):
        with pytest.raises(ValueError, match="overload_policy"):
            _engine(overload_policy="drop-everything")
        with pytest.raises(ValueError, match="max_queue"):
            _engine(max_queue=0)
        with pytest.raises(ValueError, match="step_retries"):
            _engine(step_retries=-1)


class TestEdgeCases:
    def test_all_slots_finish_same_step(self):
        eng = _engine()
        eng.submit(Request(prompt=[1, 2], max_new_tokens=3, seed=1))
        eng.submit(Request(prompt=[3, 4], max_new_tokens=3, seed=2))
        finished = []
        for _ in range(3):
            finished = eng.step()
        assert len(finished) == 2            # both evicted on one step
        assert all(r.status == "done" for r in finished)
        assert eng._free_slots() == [0, 1]
        # slots are immediately reusable
        res = eng.run([Request(prompt=[5, 6], max_new_tokens=2)])
        assert res[0].status == "done"

    def test_queue_longer_than_free_slots(self):
        eng = _engine()
        out = eng.run([Request(prompt=[i + 1, i + 2], max_new_tokens=2,
                               seed=i) for i in range(5)])
        assert len(out) == 5
        assert all(r.status == "done" for r in out)
        assert eng.stats["requests_done"] == 5

    def test_run_with_zero_slots_free_at_entry(self):
        eng = _engine()
        eng.submit(Request(prompt=[1, 2], max_new_tokens=4, seed=1))
        eng.submit(Request(prompt=[3, 4], max_new_tokens=4, seed=2))
        eng.step()                            # both slots now occupied
        assert eng._free_slots() == []
        out = eng.run([Request(prompt=[5, 6], max_new_tokens=2, seed=3)])
        assert out[0].status == "done" and len(out[0].tokens) == 2
        assert len(eng.completed) == 2        # the pre-submitted pair


class TestDegradation:
    def test_watchdog_arming_warms_decode_at_init(self):
        """The first decode call traces+compiles (seconds to minutes,
        far past any steady-state budget) — arming the watchdog must
        pre-warm the executable at construction so a healthy engine
        never trips on step 0. Fresh model: the compile is
        attributable."""
        fresh = build_lm(vocab_size=50, dim=16, num_heads=2,
                         num_layers=1, max_len=32)
        fresh.build(jax.random.PRNGKey(1))
        eng = InferenceEngine(fresh, slots=2, prefill_buckets=(8,),
                              step_timeout_s=5.0)
        assert eng.stats["decode_traces"] == 1   # warmed at init
        res = eng.run([Request(prompt=[1, 2], max_new_tokens=3)])
        assert res[0].status == "done"
        assert eng.stats["decode_traces"] == 1   # no step-0 retrace
        assert eng.stats["watchdog_trips"] == 0

    def test_donated_cache_failure_is_not_retried(self):
        """A failure after the dispatch consumed (donated) the cache
        must degrade immediately with the real cause — re-dispatching
        deleted buffers would burn the retry budget on misleading
        buffer errors."""
        from bigdl_tpu.utils import faults

        eng = _engine(step_retries=3, retry_backoff_s=0.0)
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=6, seed=1))
        eng.step()                           # healthy step first
        for leaf in jax.tree_util.tree_leaves(eng.pool):
            leaf.delete()                    # model the donated pool
        faults.set_plan(faults.FaultPlan("serve_err@1"))
        try:
            out = eng.step()
        finally:
            faults.set_plan(None)
        assert eng.degraded is not None
        assert "not retryable" in eng.degraded
        assert eng.stats["retries"] == 0     # budget untouched
        assert [r.status for r in out] == ["failed"]


class TestHealth:
    def test_snapshot_shape_and_latency(self):
        eng = _engine(max_queue=4)
        eng.submit(Request(prompt=[1, 2], max_new_tokens=3, seed=1))
        eng.submit(Request(prompt=[3, 4], max_new_tokens=3, seed=2))
        eng.submit(Request(prompt=[5, 6], max_new_tokens=3, seed=3))
        eng.step()
        h = eng.health()
        assert h["state"] == "ok" and h["degraded_reason"] is None
        assert h["slots_active"] == 2 and h["queue_depth"] == 1
        assert h["queue_buckets"] == {8: 1}
        assert h["decode_p50_ms"] > 0 and h["decode_p95_ms"] > 0
        for key in ("deadline_misses", "shed", "rejected", "poisoned",
                    "retries", "watchdog_trips", "failed", "cancelled"):
            assert h[key] == 0
        eng.run()
        assert eng.health()["requests_done"] == 3

    def test_bucket_histogram(self):
        assert bucket_histogram([3, 9, 17, 2], (8, 16, 32)) == \
            {8: 2, 16: 1, 32: 1}
        assert bucket_histogram([], (8, 16)) == {8: 0, 16: 0}
        with pytest.raises(ValueError, match="exceeds"):
            bucket_histogram([33], (8, 16, 32))

