"""The plain float32 references against the program at toy sizes on the
CPU (float32 both sides, so the tolerance is rounding of another summation
order: 1e-4 on log-probabilities of order 1-10, 2e-3 relative on gradient
leaves), and the references' optimizers and operation counts."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.counts import gpt2 as gpt2_counts
from benchmarks.counts import resnet as resnet_counts
from benchmarks.families import gpt2 as gpt2_family
from benchmarks.families import resnet as resnet_family
from benchmarks.reference import gpt2 as gpt2_ref
from benchmarks.reference import optim as ref_optim
from benchmarks.reference import resnet as resnet_ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GPT2 = {"vocab_size": 211, "n_positions": 32, "n_embd": 32, "n_layer": 2,
        "n_head": 4, "n_inner": 128}
RESNET = {"depth": 18, "image_size": 64, "num_classes": 7}


def _close_leaves(got, want, rtol):
    """Each leaf against its own largest entry, or a thousandth of the
    largest anywhere: GPT-2's key-bias gradient is mathematically zero and
    holds only rounding."""
    assert set(got) == set(want)
    floor = 1e-3 * max(float(jnp.max(jnp.abs(v))) for v in want.values())
    for k in want:
        scale = max(float(jnp.max(jnp.abs(want[k]))), floor) + 1e-12
        err = float(jnp.max(jnp.abs(got[k] - want[k]))) / scale
        assert err < rtol, (k, err)


def test_gpt2_reference_equals_the_program():
    variables = gpt2_family.make_variables(3, GPT2)
    model = gpt2_family.program_model(GPT2, {})
    ref_params = gpt2_family.reference_params(3, GPT2)
    _close_leaves(gpt2_family.to_reference(variables["params"]), ref_params,
                  1e-7)
    toks = gpt2_family.make_tokens(3, GPT2, {"batch": 3, "seq_len": 24}, 1)[0]
    x, y = toks[:, :-1], toks[:, 1:]
    with jax.default_matmul_precision("highest"):
        want = jax.nn.log_softmax(gpt2_ref.logits(ref_params, x, GPT2))
        got, _ = model.apply(variables, x)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-4
        loss_p, grads_p = jax.value_and_grad(
            lambda p: model.loss({"params": p, "state": {}}, x, y))(
                variables["params"])
        loss_r, grads_r = gpt2_ref.loss_and_grad_rows(ref_params, x, y, GPT2)
    assert float(abs(loss_p - loss_r)) < 1e-5
    _close_leaves(gpt2_family.to_reference(grads_p), grads_r, 2e-3)


def test_gpt2_lower_precisions_differ_from_the_reference():
    p = gpt2_family.reference_params(4, GPT2)
    x = gpt2_family.make_tokens(4, GPT2, {"batch": 2, "seq_len": 16}, 1)[0][
        :, :-1]
    exact = gpt2_ref.logits(p, x, GPT2)
    for precision in ("bf16", "fp8"):
        err = float(jnp.max(jnp.abs(
            gpt2_ref.logits(p, x, GPT2, precision) - exact)))
        assert 1e-4 < err < 0.5, (precision, err)


def test_resnet_reference_equals_the_program():
    from bigdl_tpu import nn
    from bigdl_tpu.models import resnet

    # 64x64 images leave a 2x2 map before the program's fixed 7x7 average
    # pool, so the comparison stops at the features both sides pool from:
    # here it runs at 224 on a single image pair instead
    cfg = dict(RESNET, image_size=224)
    model = resnet.build_imagenet(cfg["depth"], cfg["num_classes"])
    lay = resnet_family.Layout(model, cfg)
    ref_params = jax.jit(lambda s: resnet_ref.init(s, cfg))(
        resnet_family.u32(5))
    variables = {"params": lay.to_program(ref_params),
                 "state": lay.fresh_state()}
    x, y = resnet_family._batch(resnet_family.u32(5), cfg, {"batch": 8}, 0)
    with jax.default_matmul_precision("highest"):
        want = resnet_ref.log_probs(ref_params, x, cfg)
        got, _ = model.apply(variables, x, training=True)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-4
        crit = nn.ClassNLLCriterion()
        grads_p = jax.grad(lambda p: crit(model.apply(
            {"params": p, "state": variables["state"]}, x,
            training=True)[0], y))(variables["params"])
        _, grads_r = resnet_ref.loss_and_grad_rows(ref_params, x, y, cfg,
                                                   rows_per_block=8)
    # 18 BatchNorms over eight images: the program's one-pass variance
    # (E[x^2] - E[x]^2) and the reference's two-pass one round differently,
    # and the backward pass through the normalisations multiplies it
    _close_leaves(lay.to_reference(grads_p), grads_r, 5e-2)
    assert lay.to_reference(lay.to_program(ref_params)).keys() == \
        ref_params.keys()


def test_reference_optimizers_equal_the_programs():
    from bigdl_tpu.optim import SGD, Adam

    key = jax.random.PRNGKey(0)
    p = {"a": jax.random.normal(key, (5, 3)), "b": jnp.ones((4,))}
    g = jax.tree_util.tree_map(lambda v: 0.3 * v + 0.1, p)
    adam, state, slots = Adam(3e-4), ref_optim.adam_init(p), None
    slots = adam.init_slots(p)
    sgd = SGD(0.1, momentum=0.9, dampening=0.0, weightdecay=1e-4)
    vel, sstate = sgd.init_slots(p), ref_optim.sgd_init(p)
    pa = ps = ra = rs = p
    for step in range(3):
        pa, slots = adam.update(g, pa, slots, 3e-4, step)
        ra, state = ref_optim.adam_step(ra, g, state, step, 3e-4)
        ps, vel = sgd.update(g, ps, vel, 0.1, step)
        rs, sstate = ref_optim.sgd_step(rs, g, sstate, step, 0.1)
    for got, want in ((pa, ra), (ps, rs), (slots["m"], state["m"]),
                      (vel["velocity"], sstate["velocity"])):
        _close_leaves(got, want, 1e-6)


def test_counts_match_the_published_sizes():
    with open(os.path.join(REPO, "benchmarks/configs/gpt2-medium.json")) as f:
        medium = json.load(f)
    shapes = jax.eval_shape(lambda: gpt2_ref.init(0, medium))
    n_params = sum(int(np.prod(s.shape)) for s in shapes.values())
    assert 354e6 < n_params < 356e6            # "gpt2-medium: 355M"
    traffic = {"seq_len": 1024, "batch": 8}
    per_record = gpt2_counts.train_flops_per_record(medium, traffic)
    # 6 x matmul parameters x tokens, plus causal attention
    matmul = n_params - medium["n_positions"] * medium["n_embd"] - sum(
        int(np.prod(s.shape)) for k, s in shapes.items() if s.ndim <= 2
        and k not in ("wte", "wpe"))
    assert per_record == pytest.approx(
        6 * matmul * 1024 + 3 * gpt2_counts.attention_flops_per_seq_fwd(
            medium, 1024), rel=1e-3)
    assert gpt2_counts.attention_kernel_flops_per_step(medium, traffic) == \
        pytest.approx(8 * 3 * 24 * 4 * 1024 * (1024 * 1025 / 2))
    with open(os.path.join(
            REPO, "benchmarks/configs/resnet50-imagenet.json")) as f:
        r50 = json.load(f)
    # He et al. Table 1: 3.8e9 multiply-adds with the stride on the first
    # 1x1; on the 3x3 (this program, torchvision) it is 4.1e9
    assert 2 * 3.8e9 < resnet_counts.forward_flops_per_image(r50) < 2 * 4.2e9
    r50_params = sum(int(np.prod(s)) for _, s, _ in resnet_ref.layout(r50))
    assert 25.5e6 < r50_params < 25.7e6        # "25.6M parameters"
