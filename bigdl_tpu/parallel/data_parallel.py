"""Data-parallel training plane — ZeRO-1 over the ICI mesh.

Reference parity: parameters/AllReduceParameter.scala — THE distributed
core of the reference (SURVEY.md §5.8). The reference keeps all weights
in ONE flat vector (Module.getParameters), splits it into partitionNum
slices, and per iteration does:

    putGradients            → scatter my gradient, sliced, FP16 on the wire
    aggregateGradientPartition → fetch + sum my slice     (= reduce-scatter)
    optimMethod.optimize on my slice                      (= sharded ZeRO-1 step)
    sendWeightPartition / getWeights                      (= all-gather)

TPU-first redesign: the SAME shape executed as XLA collectives inside one
jitted, shard_mapped step — no blocks, no netty, no host:

    grads  = jax.grad(loss)(unflatten(flat_w))      per-device local batch
    g_my   = psum_scatter(flatten(grads), 'data')   reduce-scatter over ICI
    w_my   = my slice of flat_w
    w_my'  = optim.update(g_my, w_my, slots_my)     slots live sharded (ZeRO-1)
    flat_w'= all_gather(w_my', 'data')              all-gather over ICI

The reference's FP16CompressedTensor wire compression maps to bf16
gradient communication (`grad_dtype='bfloat16'`): contributions cross the
wire as bf16 via all_to_all and are summed locally in f32 — the exact
compress-on-wire / f32-accumulate split of the reference's
putGradients/aggregateGradientPartition, at half the wire cost and with
accumulation error independent of the axis size.

ZeRO-2 (`zero=2`; ISSUE 9, arXiv 2004.13336 cross-replica weight-update
sharding): the master fp32 flat weight vector ALSO lives sharded on the
data axis — each device persists only its (shard_size,) slice between
steps, and the step opens with one all_gather to rebuild the full
vector for the forward/backward. The collective volume per step is
identical to ZeRO-1 (one all-gather either way: ZeRO-1 gathers the
updated shards at the END of step k, ZeRO-2 gathers the same bytes at
the START of step k+1), but per-device weight residency drops from
`padded` to `padded / n` floats. Because `all_gather` of the disjoint
slices reconstructs the exact concatenation, the ZeRO-2 step is
BIT-IDENTICAL to the ZeRO-1 step in fp32 (tests/test_zero2.py pins
this; the zero2 dryrun leg in __graft_entry__.py asserts it on the
8-device virtual mesh).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.nn.module import Criterion, Module
from bigdl_tpu.utils.anomaly import health_ok, select_update as _select_update

# the flatten/pad/slice algebra lives in the param-layout spine
# (ISSUE 18) — re-exported here because this module IS its historical
# home and every training consumer imports it from parallel/
from bigdl_tpu.parallel.param_layout import FlatParamSpec  # noqa: F401


def _make_scattered_grads(model, criterion, spec, axis, grad_dtype,
                          precision):
    """Per-device closure: local fwd/bwd on the batch shard, then
    reduce-scatter of the flat gradient — the putGradients/
    aggregateGradientPartition half of the reference's iteration.
    Returns (g_my (shard_size,) f32 mean-over-global-batch, new_state,
    local loss)."""
    n = spec.num_shards

    from bigdl_tpu.ops.losses import build_train_loss

    loss_call = build_train_loss(model, criterion, precision)

    def scattered_grads(flat_w, mod_state, bx, by, rng):
        params = spec.unflatten(flat_w)
        my_index = lax.axis_index(axis)
        local_rng = jax.random.fold_in(rng, my_index)

        (loss, new_state), grads = jax.value_and_grad(
            lambda p: loss_call(p, mod_state, bx, by, local_rng),
            has_aux=True)(params)

        flat_g = spec.flatten(grads)
        if grad_dtype is not None:
            # The reference's FP16 wire compression with f32 accumulation
            # (FP16CompressedTensor.compress on the wire, decompress + f32
            # sum in aggregateGradientPartition): send each device's
            # contribution to each slice as bf16 via all_to_all, then sum
            # the received contributions locally in f32 — bf16 wire cost,
            # f32 accumulation numerics at any axis size.
            g_chunks = flat_g.reshape(n, spec.shard_size).astype(grad_dtype)
            recv = lax.all_to_all(g_chunks, axis, split_axis=0,
                                  concat_axis=0, tiled=True)
            g_my = jnp.sum(recv.reshape(n, spec.shard_size)
                           .astype(jnp.float32), axis=0) / n
        else:
            # exact path: fused f32 reduce-scatter
            g_my = lax.psum_scatter(flat_g, axis, scatter_dimension=0,
                                    tiled=True) / n
        return g_my, new_state, loss

    return scattered_grads


def _clip_shard(g_my, clip_const, clip_norm, axis):
    if clip_const is not None:
        g_my = jnp.clip(g_my, clip_const[0], clip_const[1])
    if clip_norm is not None:
        # global grad norm needs the full (pre-scatter) vector; compute
        # from the scattered shards with a psum — mathematically equal
        sq = lax.psum(jnp.sum(g_my * g_my), axis)
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(jnp.sqrt(sq), 1e-12))
        g_my = g_my * scale
    return g_my


NON_REDUCIBLE_STATE_KEYS = frozenset({"num_batches", "step", "counter"})


def _reduce_state(new_state, axis, non_reducible: bool = False):
    """BN running stats etc. diverge per shard of the batch; average them
    so replicated state stays replicated (documented divergence: the
    reference keeps per-replica stats — SURVEY.md §7 hard parts).

    NOT every float leaf is averaged. Two opt-outs, per the contract on
    nn.Module.init_state: a dict key starting with '_' exempts its whole
    subtree (the explicit convention); a key in NON_REDUCIBLE_STATE_KEYS
    exempts ONLY a direct leaf under that key — it does not propagate to
    subtrees, so a future module whose batch-dependent stats happen to
    live under a generic name like 'step' cannot silently diverge. All
    shards advance exempt leaves identically under SPMD, so "keep local"
    is "keep replicated"."""
    if isinstance(new_state, dict):
        out = {}
        for k, v in new_state.items():
            named_leaf = (isinstance(k, str) and k in NON_REDUCIBLE_STATE_KEYS
                          and not isinstance(v, (dict, list, tuple)))
            nr = non_reducible or named_leaf or (
                isinstance(k, str) and k.startswith("_"))
            out[k] = _reduce_state(v, axis, nr)
        return out
    if isinstance(new_state, (list, tuple)):
        return type(new_state)(_reduce_state(v, axis, non_reducible)
                               for v in new_state)
    if non_reducible:
        return new_state
    if jnp.issubdtype(jnp.asarray(new_state).dtype, jnp.floating):
        return lax.pmean(new_state, axis)
    return new_state


def make_dp_train_step(
    model: Module,
    criterion: Criterion,
    method,
    mesh: Mesh,
    spec: FlatParamSpec,
    axis: str = "data",
    grad_dtype: Optional[str] = "bfloat16",
    clip_const: Optional[Tuple[float, float]] = None,
    clip_norm: Optional[float] = None,
    precision=None,
    health: bool = False,
    zero: int = 1,
) -> Callable:
    """Build the jitted SPMD train step.

    Signature: (flat_w, slots, mod_state, bx, by, lr, stepno, rng)
             -> (flat_w', slots', mod_state', mean_loss)

    With `health=True` (anomaly guard armed on the Optimizer) the step
    takes a trailing `max_gnorm` scalar and returns two extra scalars
    `(ok, gnorm)`: the pre-clip global gradient norm and the
    utils/anomaly health predicate over (mean loss, norm, threshold).
    When `ok` is false the update is discarded ON DEVICE — the returned
    flat_w/slots/mod_state are the bit-identical inputs — so an
    anomalous step can never write to the weights regardless of host
    policy. Costs two scalar collectives; `health=False` builds exactly
    the historical step.

    Shardings: slots sharded on `axis`; mod_state replicated; batch
    sharded on `axis`. `zero=1` keeps flat_w replicated (historical
    ZeRO-1 step); `zero=2` shards flat_w on `axis` too — the step then
    opens with an all_gather of the weight shards and returns the
    updated SHARDED vector (see the module docstring: same collective
    volume, 1/n weight residency, bit-identical fp32 results).
    `precision` is a utils.precision.Policy for bf16-compute mixed
    precision (master weights stay fp32 in flat_w).
    """
    if zero not in (1, 2):
        raise ValueError(f"zero must be 1 or 2, got {zero!r}")
    other_axes = [a for a in mesh.axis_names if a != axis]
    scattered_grads = _make_scattered_grads(model, criterion, spec, axis,
                                            grad_dtype, precision)

    def body(flat_w, slots, mod_state, bx, by, lr, stepno, rng,
             max_gnorm=None):
        if zero == 2:
            # flat_w arrives as this device's (shard_size,) slice;
            # all_gather of the disjoint slices rebuilds the exact full
            # vector the ZeRO-1 step would have held replicated
            w_my = flat_w
            flat_w = lax.all_gather(w_my, axis, axis=0, tiled=True)
        g_my, new_state, loss = scattered_grads(flat_w, mod_state, bx, by,
                                                rng)
        mean_loss = lax.pmean(loss, axis)
        new_state = _reduce_state(new_state, axis)
        if other_axes:
            mean_loss = lax.pmean(mean_loss, tuple(other_axes))
        if health:
            # pre-clip global norm of the mean gradient: the shards are
            # disjoint slices of the flat vector, so one scalar psum
            gnorm = jnp.sqrt(lax.psum(jnp.sum(g_my * g_my), axis))
            ok = health_ok(mean_loss, gnorm, max_gnorm)
        g_my = _clip_shard(g_my, clip_const, clip_norm, axis)

        if zero == 1:
            w_my = spec.shard_slice(flat_w, lax.axis_index(axis))
        new_w_my, new_slots = method.update(g_my, w_my, slots, lr, stepno)
        if zero == 2:
            new_flat_w, prev_w = new_w_my, w_my  # stays sharded
        else:
            new_flat_w = lax.all_gather(new_w_my, axis, axis=0, tiled=True)
            prev_w = flat_w

        if health:
            new_flat_w = _select_update(ok, new_flat_w, prev_w)
            new_slots = _select_update(ok, new_slots, slots)
            new_state = _select_update(ok, new_state, mod_state)
            return new_flat_w, new_slots, new_state, mean_loss, ok, gnorm
        return new_flat_w, new_slots, new_state, mean_loss

    batch_spec = P(axis)
    w_spec = P(axis) if zero == 2 else P()
    in_specs = (w_spec, P(axis), P(), batch_spec, batch_spec, P(), P(), P())
    out_specs = (w_spec, P(axis), P(), P())
    if health:
        in_specs += (P(),)
        out_specs += (P(), P())
    smapped = shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(smapped, donate_argnums=(0, 1))


def make_dp_accum_steps(
    model: Module,
    criterion: Criterion,
    method,
    mesh: Mesh,
    spec: FlatParamSpec,
    axis: str = "data",
    grad_dtype: Optional[str] = "bfloat16",
    clip_const: Optional[Tuple[float, float]] = None,
    clip_norm: Optional[float] = None,
    precision=None,
    health: bool = False,
    zero: int = 1,
) -> Tuple[Callable, Callable]:
    """Gradient accumulation on the mesh: the accumulator lives SHARDED
    (shard_size,) per device — micro-steps reduce-scatter then add, so
    accumulation costs one extra f32 vector per shard, never a full
    gradient replica (cheap exactly as VERDICT r1 #3 prescribes:
    accumulate the scattered shard, after psum_scatter, before the
    optimizer step).

    Returns (micro_fn, apply_fn):
      micro_fn: (flat_w, g_acc, mod_state, bx, by, rng)
              -> (g_acc', mod_state', mean_loss)
      apply_fn: (flat_w, slots, g_acc, lr, stepno, n_micro)
              -> (flat_w', slots', zeroed g_acc)
    Clipping applies to the averaged accumulated gradient at update time
    (same semantics as the local path's clip_and_update).

    With `health=True` micro_fn takes a trailing `max_gnorm` and returns
    extra `(ok, gnorm)` scalars; an anomalous micro-gradient is NOT
    added to the accumulator (and module state keeps its inputs), so
    the guard screens each micro-batch before it can poison the cycle —
    the host skips its micro_n increment, extending the cycle by one
    batch. apply_fn is unchanged: it only ever sees screened gradients.

    `zero=2`: flat_w is sharded on `axis` in BOTH functions — micro_fn
    all_gathers the weight shards for the forward/backward (the
    ZeRO-2 residency/volume trade, see make_dp_train_step), apply_fn
    updates the local shard directly and returns it sharded.
    """
    if zero not in (1, 2):
        raise ValueError(f"zero must be 1 or 2, got {zero!r}")
    other_axes = [a for a in mesh.axis_names if a != axis]
    scattered_grads = _make_scattered_grads(model, criterion, spec, axis,
                                            grad_dtype, precision)

    def micro_body(flat_w, g_acc, mod_state, bx, by, rng, max_gnorm=None):
        if zero == 2:
            flat_w = lax.all_gather(flat_w, axis, axis=0, tiled=True)
        g_my, new_state, loss = scattered_grads(flat_w, mod_state, bx, by,
                                                rng)
        mean_loss = lax.pmean(loss, axis)
        new_state = _reduce_state(new_state, axis)
        if other_axes:
            mean_loss = lax.pmean(mean_loss, tuple(other_axes))
        if health:
            gnorm = jnp.sqrt(lax.psum(jnp.sum(g_my * g_my), axis))
            ok = health_ok(mean_loss, gnorm, max_gnorm)
            # where-select the SUM, not the addend: adding 0.0 would
            # flip -0.0 accumulator elements to +0.0 and break the
            # bit-identical-discard contract
            new_acc = jnp.where(ok, g_acc + g_my, g_acc)
            new_state = _select_update(ok, new_state, mod_state)
            return new_acc, new_state, mean_loss, ok, gnorm
        return g_acc + g_my, new_state, mean_loss

    def apply_body(flat_w, slots, g_acc, lr, stepno, n_micro):
        g_my = _clip_shard(g_acc / n_micro, clip_const, clip_norm, axis)
        if zero == 2:
            w_my = flat_w
        else:
            w_my = spec.shard_slice(flat_w, lax.axis_index(axis))
        new_w_my, new_slots = method.update(g_my, w_my, slots, lr, stepno)
        if zero == 2:
            new_flat_w = new_w_my
        else:
            new_flat_w = lax.all_gather(new_w_my, axis, axis=0, tiled=True)
        return new_flat_w, new_slots, jnp.zeros_like(g_acc)

    batch_spec = P(axis)
    w_spec = P(axis) if zero == 2 else P()
    micro_in = (w_spec, P(axis), P(), batch_spec, batch_spec, P())
    micro_out = (P(axis), P(), P())
    if health:
        micro_in += (P(),)
        micro_out += (P(), P())
    micro_fn = jax.jit(shard_map(
        micro_body, mesh=mesh,
        in_specs=micro_in,
        out_specs=micro_out,
        check_vma=False,
    ), donate_argnums=(1,))
    apply_fn = jax.jit(shard_map(
        apply_body, mesh=mesh,
        in_specs=(w_spec, P(axis), P(axis), P(), P(), P()),
        out_specs=(w_spec, P(axis), P(axis)),
        check_vma=False,
    ), donate_argnums=(0, 1, 2))
    return micro_fn, apply_fn


def make_dp_eval_step(model: Module, methods, mesh: Mesh, axis: str = "data"):
    """SPMD eval step: forward on the local batch shard, psum the
    (sum, count) stats — the reference's Evaluator mapPartitions+reduce
    (optim/Evaluator.scala) as one collective.

    Signature: (params, mod_state, bx, by, row_mask) -> [(sum, count), ...]
    row_mask is a per-row 0/1 float vector (masks padded tail rows).
    """

    def body(params, mod_state, bx, by, row_mask):
        out, _ = model.apply({"params": params, "state": mod_state}, bx,
                             training=False)
        stats = []
        for m in methods:
            s, c = m.stats(out, by, row_mask)
            stats.append((lax.psum(s, axis), lax.psum(c, axis)))
        return stats

    smapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(smapped)
