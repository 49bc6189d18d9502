"""`parallel/moe.DroplessMoE` under a routing the MODEL decided (ISSUE 38):
expert ids and weights handed in, and rows that go to NO expert. That the
programs of the models which do not use it are unchanged is
tests/test_served_programs_text.py's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.parallel.moe import DroplessMoE, expert_load_report, gated_ffn


@pytest.fixture(scope="module")
def layer():
    """16 experts of 24 -> 40 -> 24, no shared expert, and 64 rows."""
    moe = DroplessMoE(24, 40, 16, 1)
    p = moe.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 24))
    return moe, p, x


def _dense(p, x, idx, w, experts):
    """The sum over ALL experts, each weighted by the routing (zero where a
    row did not choose it): what a routing means, with no sorting."""
    y = jnp.zeros_like(x)
    for e in range(experts):
        column = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        y = y + column[:, None] * gated_ffn(
            x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    return y


@pytest.mark.parametrize("top_k", [1, 2])
def test_rows_over_sixteen_experts_agree_with_the_dense_sum(layer, top_k):
    """64 rows over 16 experts under softmax weights the model chose."""
    _, p, x = layer
    moe = DroplessMoE(24, 40, 16, top_k)
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(2), (64, 17)), -1)
    w, idx = jax.lax.top_k(probs, top_k)
    with jax.default_matmul_precision("highest"):
        y, counts = moe.forward(p, x, routing=(idx.astype(jnp.int32), w))
        want = _dense(p, x, idx, w, 16)
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5
    assert counts.shape == (17,) and int(counts.sum()) == 64 * top_k
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(idx).reshape(-1), minlength=17))
    assert int(counts[16]) > 0          # some rows chose no expert


def test_a_row_sent_to_no_expert_adds_nothing_and_is_in_no_group(layer):
    moe, p, x = layer
    idx = (jnp.arange(64, dtype=jnp.int32) % 4)[:, None]
    w = jnp.full((64, 1), 0.7)
    nowhere = (jnp.arange(64) % 3 == 0)[:, None]
    with jax.default_matmul_precision("highest"):
        y_all, c_all = moe.forward(p, x, routing=(idx, w))
        y, c = moe.forward(p, x, routing=(jnp.where(nowhere, 16, idx), w))
    assert not np.asarray(y)[np.asarray(nowhere)[:, 0]].any()
    np.testing.assert_array_equal(
        np.asarray(y)[~np.asarray(nowhere)[:, 0]],
        np.asarray(y_all)[~np.asarray(nowhere)[:, 0]])
    assert int(c[16]) == int(nowhere.sum()) == 22 and int(c_all[16]) == 0
    assert int(c[:16].sum()) == 64 - 22 and not c[4:16].any()
    # every row to no expert: no group has a row, nothing comes out
    y0, c0 = moe.forward(p, x, routing=(jnp.full((64, 1), 16, jnp.int32), w))
    assert not np.asarray(y0).any() and int(c0[16]) == 64
    # and the grouped matmuls get the sixteen experts' sizes, not seventeen
    jaxpr = jax.make_jaxpr(
        lambda p, x, i, w: moe.forward(p, x, routing=(i, w)))(p, x, idx, w)
    grouped = [e for e in jaxpr.eqns if "ragged_dot" in e.primitive.name]
    assert len(grouped) == 3
    assert all(e.invars[2].aval.shape == (16,) for e in grouped)


def test_its_own_routing_handed_back_is_its_own_forward():
    """The sigmoid router's choice given as `routing`: the same result as
    letting `forward` route, with a last column of zeros in the counts."""
    moe = DroplessMoE(24, 40, 8, 2, shared_hidden=40, scale=2.5)
    p = moe.init_params(jax.random.PRNGKey(3))
    p["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (8,))
    x = jax.random.normal(jax.random.PRNGKey(5), (33, 24))
    y, counts = moe.forward(p, x)
    y2, counts2 = moe.forward(p, x, routing=moe.route(p, x))
    np.testing.assert_array_equal(y, y2)
    np.testing.assert_array_equal(counts, counts2[:8])
    assert counts.shape == (8,) and int(counts2[8]) == 0


def test_the_report_of_a_step_with_a_skip_column():
    aux = np.array([[3, 0, 1, 0, 2], [0, 0, 0, 4, 2]])
    args = expert_load_report(aux, skip_column=True)
    assert args == {"experts_touched": [2, 1],
                    "expert_load_max_over_mean": [3.0, 4.0],
                    "moe_assignments": 8,
                    "skipped_rows": [2, 2], "routed_rows": 12}
    plain = expert_load_report(aux)
    assert set(plain) == {"experts_touched", "expert_load_max_over_mean",
                          "moe_assignments"}
    assert plain["moe_assignments"] == 12
    assert plain["experts_touched"] == [3, 2]
