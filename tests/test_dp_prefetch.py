"""DistriOptimizer runs its input side one batch ahead (ISSUE 47): the
host batch of step n + 1 leaves the iterator at the top of iteration n
and is placed on the mesh right after step n's call, before anything
fetches step n's results. Pinned here on the virtual CPU mesh: the order
of the spans, the numbers (against a plain loop that places each batch
at the top), where a held loader failure surfaces, what a recovery does
with the batch placed ahead, and the two counters."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from bigdl_tpu import nn, obs
from bigdl_tpu.dataset import DataSet, Sample
from bigdl_tpu.optim import SGD, Optimizer, Trigger
from bigdl_tpu.optim.optimizer import _batch_iterator
from bigdl_tpu.parallel import FlatParamSpec, make_mesh
from bigdl_tpu.parallel.data_parallel import (
    make_dp_accum_steps, make_dp_train_step,
)
from bigdl_tpu.parallel.mesh import host_to_global, place_global
from bigdl_tpu.utils import faults

BATCH, DIM, STEPS = 16, 6, 6


@pytest.fixture(autouse=True)
def _fresh_obs():
    prev = obs.set_enabled(True)
    obs.reset_all()
    faults.set_plan(faults.FaultPlan(""))
    yield
    faults.set_plan(None)
    obs.reset_all()
    obs.set_enabled(prev)


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh({"data": 8})


def _dataset():
    rng = np.random.RandomState(11)
    return DataSet.array(
        [Sample(rng.rand(DIM).astype(np.float32), int(rng.randint(0, 4)))
         for _ in range(64)], seed=7)


def _model():
    return nn.Sequential(nn.Linear(DIM, 16), nn.ReLU(), nn.Linear(16, 4),
                         nn.LogSoftMax()).build(jax.random.PRNGKey(3))


def _method():
    return SGD(learningrate=0.1, momentum=0.9, dampening=0.0)


def _optimizer(mesh, steps=STEPS, accum=1, ckpt=None, ckpt_iter=None):
    opt = (Optimizer(_model(), _dataset(), nn.ClassNLLCriterion(),
                     batch_size=BATCH, seed=5)
           .set_optim_method(_method())
           .set_mesh(mesh)
           .set_end_when(Trigger.max_iteration(steps)))
    if accum > 1:
        opt.set_gradient_accumulation(accum)
    if ckpt is not None:
        opt.set_checkpoint(str(ckpt), Trigger.several_iteration(ckpt_iter))
    return opt


def _flat(model):
    return np.concatenate([np.ravel(np.asarray(a))
                           for _, a in model.parameters()])


def _step_events():
    return obs.get_event_log().events("train_step")


def _counter(name):
    series = obs.get_registry().snapshot()["metrics"][name]["series"]
    return series[0]["value"] if series else 0


def _spans(name):
    return [(e["ts"], e["ts"] + e["dur"])
            for e in obs.get_tracer().events()
            if e.get("ph") == "X" and e["name"] == name]


def test_next_batch_is_placed_before_the_step_is_fenced(mesh8):
    obs.set_tracer(obs.SpanTracer(enabled=True))
    _optimizer(mesh8).optimize()
    place, fence = _spans("h2d_place"), _spans("fence")
    dispatch = _spans("dispatch")
    # batch 0 before its own call, then one ahead in every step's shadow
    # (the last of them is the batch the end trigger leaves over)
    assert len(fence) == len(dispatch) == STEPS
    assert len(place) == STEPS + 1
    for n in range(STEPS):
        assert place[n + 1][0] < fence[n][0], n
    for a, b in place:
        assert any(d0 <= a and b <= d1 for d0, d1 in dispatch)
    # the first dispatch holds two placements, every other exactly one
    inside = [sum(d0 <= a and b <= d1 for a, b in place)
              for d0, d1 in dispatch]
    assert inside == [2] + [1] * (STEPS - 1)
    # data_fetch stays a span of its own, outside dispatch
    fetch = _spans("data_fetch")
    assert len(fetch) == STEPS + 1
    for a, b in fetch:
        assert not any(d0 < b and a < d1 for d0, d1 in dispatch)


def _plain_loop(mesh, accum):
    """The loop as it was: each step's batch fetched and placed at the
    top of its own iteration, then the call, then the loss fetched."""
    model, method = _model(), _method()
    crit = nn.ClassNLLCriterion()
    n = mesh.shape["data"]
    spec = FlatParamSpec(model.variables["params"], n)
    flat_w = place_global(mesh, P(), spec.flatten(model.variables["params"]))
    mod_state = place_global(mesh, P(), model.variables["state"])
    slots = place_global(mesh, P("data"), method.init_slots(
        jnp.zeros((spec.padded,), jnp.float32)))
    if accum == 1:
        step_fn = make_dp_train_step(model, crit, method, mesh, spec)
    else:
        micro_fn, apply_fn = make_dp_accum_steps(model, crit, method, mesh,
                                                 spec)
        g_acc = place_global(mesh, P("data"),
                             jnp.zeros((spec.padded,), jnp.float32))
    rng = jax.random.PRNGKey(5)
    batches = _batch_iterator(_dataset(), True, BATCH)
    state = {"epoch": 1, "neval": 0, "nupdates": 0}
    losses, micro_n = [], 0
    for neval in range(STEPS):
        state["neval"] = neval
        mb = next(batches)
        x = host_to_global(mesh, P("data", None), np.asarray(mb.input))
        y = host_to_global(mesh, P("data"), np.asarray(mb.target))
        step_rng = jax.random.fold_in(rng, neval)
        if accum == 1:
            lr = method.current_rate(state)
            flat_w, slots, mod_state, loss = step_fn(
                flat_w, slots, mod_state, x, y,
                jnp.asarray(lr, jnp.float32), jnp.asarray(neval, jnp.int32),
                step_rng)
        else:
            lr = method.current_rate({**state, "neval": state["nupdates"]})
            g_acc, mod_state, loss = micro_fn(flat_w, g_acc, mod_state, x,
                                              y, step_rng)
            micro_n += 1
            if micro_n == accum:
                flat_w, slots, g_acc = apply_fn(
                    flat_w, slots, g_acc, jnp.asarray(lr, jnp.float32),
                    jnp.asarray(state["nupdates"], jnp.int32),
                    jnp.asarray(accum, jnp.float32))
                micro_n = 0
                state["nupdates"] += 1
        losses.append(float(loss))
    return losses, np.asarray(flat_w)[:spec.total]


@pytest.mark.parametrize("accum", [1, 2])
def test_losses_are_the_plain_loops_bit_for_bit(mesh8, accum):
    want, want_w = _plain_loop(mesh8, accum)
    model = _optimizer(mesh8, accum=accum).optimize()
    got = [e["loss"] for e in _step_events()]
    assert got == want
    np.testing.assert_array_equal(_flat(model), want_w)


def test_a_held_loader_failure_surfaces_at_the_step_that_consumes_it(
        mesh8, tmp_path):
    """`data@3` fires when batch 3 leaves the iterator, which is now at
    the top of the step before; the retry, the reload and the steps the
    log shows are those of a loop that fetched it at step 3."""
    clean = _flat(_optimizer(mesh8).optimize())
    obs.reset_all()

    faults.set_plan(faults.FaultPlan("data@3"))
    got = _flat(_optimizer(mesh8, ckpt=tmp_path / "a", ckpt_iter=3)
                .optimize())
    np.testing.assert_array_equal(got, clean)
    log = obs.get_event_log()
    # neval 0..2 ran and were accounted (steps 1..3) BEFORE the failure
    # surfaced; the reload of checkpoint-3 replays nothing
    assert [e["step"] for e in _step_events()] == [1, 2, 3, 4, 5, 6]
    assert len(log.events("checkpoint_load")) == 1
    kinds = [e["kind"] for e in log.events()
             if e["kind"] in ("fault_injected", "train_step",
                              "checkpoint_load")]
    # the loader failed while step 3 (neval 2) was being set up ...
    assert kinds.index("fault_injected") == 2
    # ... and was raised only after that step had been accounted
    assert kinds.index("checkpoint_load") == 4


def test_a_held_loader_failure_draws_on_the_retry_budget_of_its_step(
        mesh8, tmp_path):
    faults.set_plan(faults.FaultPlan("data@3x4"))
    with pytest.raises(faults.FaultInjected, match="data@3"):
        _optimizer(mesh8, ckpt=tmp_path / "b", ckpt_iter=3).optimize()
    # three retries, each a reload; the fourth failure of the same step
    # is raised, and no step past 3 was ever accounted
    assert len(obs.get_event_log().events("checkpoint_load")) == 3
    assert [e["step"] for e in _step_events()] == [1, 2, 3]
    assert len(obs.get_event_log().events("fault_injected",
                                          fault="data", step=3)) == 4


def test_without_a_checkpoint_the_held_failure_is_raised_at_its_step(mesh8):
    faults.set_plan(faults.FaultPlan("data@3"))
    with pytest.raises(faults.FaultInjected, match="data@3"):
        _optimizer(mesh8).optimize()
    assert [e["step"] for e in _step_events()] == [1, 2, 3]


@pytest.mark.parametrize("accum", [1, 2])
def test_a_recovery_drops_the_batch_placed_ahead(mesh8, tmp_path, accum):
    clean = _flat(_optimizer(mesh8, accum=accum).optimize())
    obs.reset_all()

    faults.set_plan(faults.FaultPlan("step@4"))
    got = _flat(_optimizer(mesh8, accum=accum, ckpt=tmp_path / "c",
                           ckpt_iter=2).optimize())
    np.testing.assert_array_equal(got, clean)
    # steps 1..4, the failure at neval 4, checkpoint-4 reloaded, 5 and 6
    assert [e["step"] for e in _step_events()] == [1, 2, 3, 4, 5, 6]
    # batch 4 was on the mesh when step 4 failed: dropped with the old
    # iterator, placed again from the new one; and the one at the end
    assert _counter("training_batches_prefetch_dropped_total") == 2
    assert _counter("training_batches_prefetched_total") == STEPS - 2


def test_a_poisoned_batch_is_placed_again(mesh8):
    from bigdl_tpu.utils.anomaly import AnomalyGuard

    faults.set_plan(faults.FaultPlan("nan@2"))
    opt = _optimizer(mesh8)
    opt.set_anomaly_guard(AnomalyGuard(policy="skip_step"))
    opt.optimize()
    applied = [e["update_applied"] for e in _step_events()]
    assert applied == [True, True, False, True, True, True]
    assert _counter("training_batches_prefetch_dropped_total") == 2
    assert _counter("training_batches_prefetched_total") == STEPS - 2


def test_counters_of_a_clean_run_and_nothing_placed_outlives_it(mesh8):
    def placed_batches():
        gc.collect()
        return {id(a) for a in jax.live_arrays()
                if a.shape in ((BATCH, DIM), (BATCH,))}

    before = placed_batches()
    model = _optimizer(mesh8).optimize()
    assert _counter("training_batches_prefetched_total") == STEPS - 1
    assert _counter("training_batches_prefetch_dropped_total") == 1
    assert _counter("training_steps_total") == STEPS
    assert placed_batches() <= before
    assert model.variables is not None
