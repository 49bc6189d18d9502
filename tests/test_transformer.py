"""TransformerLM tests: shapes, causality, convergence smoke, and
sequence-parallel apply on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from bigdl_tpu.models.transformer import TransformerConfig, TransformerLM, build_lm
from bigdl_tpu.parallel import make_mesh



def test_forward_shape():
    m = build_lm(vocab_size=50, dim=32, num_heads=2, num_layers=2,
                 max_len=64)
    variables = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 50)
    out, _ = m.apply(variables, toks)
    assert out.shape == (2, 16, 50)
    # log-probs sum to one
    np.testing.assert_allclose(np.asarray(jnp.exp(out).sum(-1)), 1.0,
                               atol=1e-5)


def test_causality():
    m = build_lm(vocab_size=50, dim=32, num_heads=2, num_layers=2,
                 max_len=64)
    variables = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, 50)
    out1, _ = m.apply(variables, toks)
    toks2 = toks.at[:, 8:].set(0)
    out2, _ = m.apply(variables, toks2)
    np.testing.assert_allclose(np.asarray(out1[:, :8]),
                               np.asarray(out2[:, :8]), atol=1e-5)


def test_converges_on_repetition():
    # learn to predict a repeating token pattern
    m = build_lm(vocab_size=8, dim=32, num_heads=2, num_layers=2,
                 max_len=32)
    variables = m.init(jax.random.PRNGKey(0))
    pattern = jnp.asarray([[1, 2, 3, 4] * 8], jnp.int32)
    x, y = pattern[:, :-1], pattern[:, 1:]

    params = variables["params"]

    @jax.jit
    def step(params):
        def loss_fn(p):
            out, _ = m.apply({"params": p, "state": {}}, x)
            return -jnp.mean(jnp.take_along_axis(out, y[..., None],
                                                 axis=-1))
        loss, g = jax.value_and_grad(loss_fn)(params)
        return jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, g), loss

    for _ in range(60):
        params, loss = step(params)
    assert float(loss) < 0.1, float(loss)


def test_sequence_parallel_matches_single_device():
    mesh = make_mesh({"seq": 8})
    cfg = TransformerConfig(vocab_size=40, max_len=64, dim=32, num_heads=2,
                            num_layers=2)
    m_single = TransformerLM(cfg, name="lm")
    m_sp = TransformerLM(cfg, sp_axis="seq", name="lm")
    variables = m_single.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 40)

    ref, _ = m_single.apply(variables, toks)

    def body(params, toks):
        out, _ = m_sp.apply({"params": params, "state": {}}, toks)
        return out

    fn = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, "seq")),
        out_specs=P(None, "seq", None),
        check_vma=False,
    ))
    out = fn(variables["params"],
             jax.device_put(toks, NamedSharding(mesh, P(None, "seq"))))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("policy", ["full", "dots", "attn_saved"])
def test_remat_matches_no_remat(policy):
    """jax.checkpoint must not change values or grads, only memory —
    for EVERY policy, including attn_saved (FFN-half-only checkpoint,
    the bench.py LM default)."""
    import numpy as np

    from bigdl_tpu.models.transformer import (
        TransformerConfig, TransformerLM)

    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 50, (2, 16)), jnp.int32)
    base = dict(vocab_size=50, max_len=16, dim=32, num_heads=4,
                num_layers=2)
    m1 = TransformerLM(TransformerConfig(**base, remat=False))
    m2 = TransformerLM(TransformerConfig(**base, remat=True,
                                         remat_policy=policy))
    v = m1.init(jax.random.PRNGKey(0))

    def loss(model, p):
        out, _ = model.apply({"params": p, "state": {}}, toks)
        return jnp.mean(out ** 2)

    l1, g1 = jax.value_and_grad(lambda p: loss(m1, p))(v["params"])
    l2, g2 = jax.value_and_grad(lambda p: loss(m2, p))(v["params"])
    assert abs(float(l1) - float(l2)) < 1e-6
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


class TestSwitchMoELM:
    """TransformerConfig.moe_experts: Switch/GShard-FFN transformer."""

    def _cfg(self, top_k=1):
        return TransformerConfig(vocab_size=64, max_len=32, dim=32,
                                 num_heads=4, num_layers=2, dropout=0.0,
                                 moe_experts=4, moe_top_k=top_k)

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_forward_loss_and_grads(self, top_k):
        model = TransformerLM(self._cfg(top_k), name="lm")
        v = model.init(jax.random.PRNGKey(0))
        assert v["params"]["blocks"]["w1"].shape == (2, 4, 32, 128)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
        tgts = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 64)
        logp, _ = model.apply(v, toks)
        assert logp.shape == (2, 16, 64)
        # loss includes the positive aux term
        loss = model.loss(v, toks, tgts, chunk=16)
        h, aux = model.apply_hidden(v, toks, with_aux=True)
        assert float(aux) > 0.0
        g = jax.grad(lambda p: model.loss(
            {"params": p, "state": {}}, toks, tgts, chunk=16))(v["params"])
        leaves = jax.tree_util.tree_leaves(g)
        assert all(np.isfinite(np.asarray(x)).all() for x in leaves)
        # router must receive gradient (through routing AND aux)
        assert float(jnp.abs(g["blocks"]["router"]).sum()) > 0

    def test_trains_through_optimizer(self):
        from bigdl_tpu import nn as bnn
        from bigdl_tpu.dataset import DataSet
        from bigdl_tpu.dataset.text import synthetic_next_token
        from bigdl_tpu.optim import Adam, Optimizer, Trigger

        model = TransformerLM(self._cfg(), name="lm")
        model.build(jax.random.PRNGKey(0))
        data = synthetic_next_token(64, 64, 16)
        opt = (Optimizer(model, DataSet.array(data),
                         bnn.ChunkedSoftmaxCE(), batch_size=16)
               .set_optim_method(Adam(3e-3))
               .set_end_when(Trigger.max_iteration(20)))
        opt.log_every = 100
        trained = opt.optimize()
        # loss finite and decreased vs iteration 1 is covered by the
        # convergence harness elsewhere; here: end-to-end runs + params
        # moved
        p0 = model.init(jax.random.PRNGKey(0))["params"]
        moved = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max()),
            trained.variables["params"], p0)
        assert max(jax.tree_util.tree_leaves(moved)) > 0

    def test_moe_rejects_tp(self):
        with pytest.raises(NotImplementedError, match="tensor"):
            TransformerLM(self._cfg(), tp_axis="model", name="lm")


def test_moe_lm_expert_choice_routing():
    """moe_routing='expert_choice' wires through the LM: forward runs,
    aux is exactly 0 (balanced by construction), grads flow."""
    cfg = TransformerConfig(vocab_size=64, max_len=32, dim=32,
                            num_heads=4, num_layers=2, dropout=0.0,
                            moe_experts=4, moe_routing="expert_choice")
    m = TransformerLM(cfg)
    v = m.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (2, 16)), jnp.int32)
    h, aux = m.apply_hidden({"params": v["params"], "state": {}}, toks,
                            with_aux=True)
    assert h.shape == (2, 16, 32)
    assert float(aux) == 0.0

    def loss(p):
        out, _ = m.apply({"params": p, "state": {}}, toks)
        return jnp.mean(out ** 2)

    g = jax.grad(loss)(v["params"])
    gn = sum(float(jnp.abs(l).sum())
             for l in jax.tree_util.tree_leaves(g))
    assert np.isfinite(gn) and gn > 0
