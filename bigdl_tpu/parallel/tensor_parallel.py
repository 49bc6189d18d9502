"""Tensor parallelism for the transformer stack — Megatron-style sharding
expressed as shard_map + XLA collectives over the ICI mesh.

No reference counterpart: the reference's only strategy is data
parallelism (SURVEY.md §2.3 "Parallelism strategies present"); TP is one
of this framework's additive mesh axes. The split is the classic one:

    wq/wk/wv/w1 column-sharded  (each device owns heads/tp heads,
                                 ffn/tp hidden units — no comm needed)
    wo/w2       row-sharded      (partial sums → one psum per matmul)
    ln/embed/pos/bo/b2 replicated

`TransformerLM._block` already runs this split unchanged inside
shard_map (it infers its local head count from the weight shard and
psums after the row-parallel matmuls); this module supplies the
PartitionSpecs for the stacked parameter pytree and a full jitted
training step that composes TP with data parallelism and ring-attention
sequence parallelism on one mesh.

Gradient collectives: after per-device jax.grad, every leaf is averaged
over the data (and sequence) axes. Across TP no per-leaf correction is
needed — the model's `tp_identity` (Megatron's conjugate "f": identity
forward, psum backward) sums partial activation cotangents before they
reach TP-replicated params, so their grads emerge full and identical on
every shard, while TP-sharded leaves' grads are exact locally.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.models.transformer import TransformerLM


# stacked-block leaves: which dim (after the layer axis) carries the shard
_COL = {"wq", "wk", "wv", "w1"}          # shard last dim
_ROW = {"wo", "w2"}                      # shard middle (input) dim
_COL_BIAS = {"bq", "bk", "bv", "b1"}     # shard last dim


def transformer_tp_specs(tp_axis: str = "model",
                         tie_embeddings: bool = True) -> Dict[str, Any]:
    """PartitionSpec pytree for TransformerLM params (stacked blocks)."""
    blocks = {}
    for k in ("ln1_g", "ln1_b", "ln2_g", "ln2_b", "bo", "b2"):
        blocks[k] = P()
    for k in _COL:
        blocks[k] = P(None, None, tp_axis)
    for k in _ROW:
        blocks[k] = P(None, tp_axis, None)
    for k in _COL_BIAS:
        blocks[k] = P(None, tp_axis)
    specs = {
        "embed": P(), "pos": P(), "lnf_g": P(), "lnf_b": P(),
        "blocks": blocks,
    }
    if not tie_embeddings:
        specs["head"] = P()  # replicated: the loss needs the full vocab
    return specs


def make_transformer_train_step(
    model: TransformerLM,
    method,
    mesh: Mesh,
    dp_axis: Optional[str] = "data",
    tp_axis: Optional[str] = "model",
    sp_axis: Optional[str] = None,
) -> Callable:
    """Build the jitted SPMD LM training step over a dp×tp(×sp) mesh.

    Signature: (params, slots, tokens, targets, lr, stepno, rng)
             -> (params', slots', mean_loss)

    tokens/targets: (B, S) int32, batch sharded on dp, sequence sharded
    on sp. The model must have been constructed with matching
    tp_axis/sp_axis. Use `transformer_tp_specs()` + `shard_params` to
    place params/slots.
    """
    if (model.tp_axis or None) != (tp_axis or None):
        raise ValueError(
            f"model.tp_axis={model.tp_axis!r} != step tp_axis={tp_axis!r}")
    if model.cfg.moe_experts:
        raise NotImplementedError(
            "make_transformer_train_step over a MoE-FFN TransformerLM "
            "(the expert-stacked param specs and the aux loss are not "
            "plumbed; train MoE via the Optimizer path, or shard "
            "experts with parallel/moe.py directly)")
    if (model.sp_axis or None) != (sp_axis or None):
        raise ValueError(
            f"model.sp_axis={model.sp_axis!r} != step sp_axis={sp_axis!r}")

    tie = model.cfg.tie_embeddings
    specs = transformer_tp_specs(tp_axis, tie) if tp_axis else \
        jax.tree_util.tree_map(lambda _: P(),
                               transformer_tp_specs("x", tie),
                               is_leaf=lambda x: isinstance(x, P))
    batch_axes = tuple(a for a in (dp_axis,) if a)
    seq_axes = tuple(a for a in (sp_axis,) if a)
    reduce_axes = batch_axes + seq_axes

    def body(params, slots, tokens, targets, lr, stepno, rng):
        if reduce_axes:
            # unique id per (data, seq) shard — mixed-radix over the axes;
            # NOT folded over tp (tp shards must share the dropout mask)
            shard_id, stride = 0, 1
            for a in reduce_axes:
                shard_id = shard_id + lax.axis_index(a) * stride
                stride *= mesh.shape[a]
            rng = jax.random.fold_in(rng, shard_id)

        def loss_fn(p):
            logp, _ = model.apply({"params": p, "state": {}}, tokens,
                                  training=True, rng=rng)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
            return jnp.mean(nll)

        loss, grads = jax.value_and_grad(loss_fn)(params)

        # batch/sequence shards each saw part of the data → average.
        # No per-leaf TP correction is needed: the model's tp_identity
        # (Megatron "f") already makes replicated-leaf grads full and
        # identical per shard, and TP-sharded leaves' grads are exact.
        if reduce_axes:
            grads = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, reduce_axes), grads)
            loss = lax.pmean(loss, reduce_axes)

        new_params, new_slots = method.update(grads, params, slots, lr,
                                              stepno)
        return new_params, new_slots, loss

    tok_spec = P(dp_axis, sp_axis)
    slot_specs = slot_specs_for(method, specs)

    smapped = shard_map(
        body, mesh=mesh,
        in_specs=(specs, slot_specs, tok_spec, tok_spec, P(), P(), P()),
        out_specs=(specs, slot_specs, P()),
        check_vma=False,
    )
    step = jax.jit(smapped, donate_argnums=(0, 1))
    if sp_axis is None or getattr(model, "sp_mode", "ring") != "zigzag":
        return step

    # zigzag SP: permute tokens/targets into the balanced layout before
    # the shard_map (the LM loss is a mean over positions, so the
    # consistent permutation leaves it — and every gradient — exactly
    # equal to the contiguous-layout step)
    from bigdl_tpu.parallel.ring_attention import zigzag_order

    n_sp = mesh.shape[sp_axis]
    orders = {}  # seq len → device-resident permutation (stable shapes)

    def _order(s):
        if s not in orders:
            orders[s] = jnp.asarray(zigzag_order(n_sp, s))
        return orders[s]

    def zig_step(params, slots, tokens, targets, lr, stepno, rng):
        order = _order(tokens.shape[1])
        return step(params, slots, tokens[:, order], targets[:, order],
                    lr, stepno, rng)

    return jax.jit(zig_step, donate_argnums=(0, 1))


def slot_specs_for(method, specs):
    """Optimizer slots are {slot_name: params-like tree} (see
    OptimMethod.init_slots); each slot leaf shards like its param."""
    probe = method.init_slots({"x": jnp.zeros((1,), jnp.float32)})
    return {k: specs for k in probe}


def shard_params(mesh: Mesh, specs, tree):
    """device_put a pytree according to a matching PartitionSpec pytree."""
    return jax.tree_util.tree_map(
        lambda s, x: jax.device_put(x, NamedSharding(mesh, s)),
        specs, tree, is_leaf=lambda x: isinstance(x, P))
