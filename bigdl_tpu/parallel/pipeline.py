"""Pipeline parallelism — GPipe microbatch schedule over a mesh axis.

No reference counterpart (SURVEY.md §2.3: the reference has data
parallelism only); this is the `pipe` mesh axis. The TransformerLM's
stacked-layer parameters make stages trivial: stage i owns the
contiguous layer slice blocks[i·L/n : (i+1)·L/n] — i.e. every stacked
block leaf is sharded on its LAYER axis with P('pipe', ...). Activations
hop stage→stage over the ICI ring with `lax.ppermute`.

Schedule: classic GPipe. M microbatches flow through n stages in
M + n - 1 ticks; stage s processes microbatch t - s at tick t. The
backward schedule is derived by jax.grad reversing the forward
(ppermute transposes to the inverse permutation), so warmup/drain
bubbles match GPipe's 2(n-1) ticks.

Losses exist only on the last stage; they cross to every stage through
the same psum-forward/identity-backward operator the tensor-parallel
plane uses (models/transformer.py#tp_reduce). Replicated leaves
(embed/pos/final LN) are USED on different stages (lookup on stage 0,
head on stage n-1), so their per-stage grads are partial and get psum'd
over the pipe axis; layer-sharded leaves' grads are exact locally.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu.models.transformer import TransformerLM, tp_reduce



def pipeline_specs(pipe_axis: str = "pipe", tie_embeddings: bool = True):
    """PartitionSpecs: stacked block leaves sharded on the layer axis."""
    def blk(ndim):
        return P(pipe_axis, *([None] * (ndim - 1)))

    blocks = {
        "ln1_g": blk(2), "ln1_b": blk(2), "ln2_g": blk(2), "ln2_b": blk(2),
        "wq": blk(3), "wk": blk(3), "wv": blk(3), "wo": blk(3),
        "bq": blk(2), "bk": blk(2), "bv": blk(2), "bo": blk(2),
        "w1": blk(3), "b1": blk(2), "w2": blk(3), "b2": blk(2),
    }
    specs = {"embed": P(), "pos": P(), "lnf_g": P(), "lnf_b": P(),
             "blocks": blocks}
    if not tie_embeddings:
        specs["head"] = P()
    return specs


def pipeline_bubble_fraction(n_stages: int, microbatches: int) -> float:
    """GPipe idle fraction: each stage is idle for (n−1) of the
    m+n−1 ticks (warmup + drain). Raising `microbatches` amortizes it;
    report this when choosing a schedule."""
    return (n_stages - 1) / (microbatches + n_stages - 1)


def _injection_schedule(n: int, m: int, v: int):
    """Interleaved-schedule injection ticks: microbatch j enters virtual
    stage 0 (device 0, chunk 0) at tick inject[j]. An in-flight
    microbatch occupies device (t−t0) mod n at tick t for v·n ticks, so
    two microbatches collide iff their injection ticks share a residue
    mod n while both in flight; greedy first-free-tick is optimal here.
    v=1 degenerates to GPipe (inject = 0..m−1)."""
    inject, last = [], {}
    t = 0
    for _ in range(m):
        while True:
            r = t % n
            if r not in last or last[r] + v * n <= t:
                break
            t += 1
        inject.append(t)
        last[t % n] = t
        t += 1
    return inject


def interleaved_bubble_fraction(n_stages: int, microbatches: int,
                                virtual_stages: int) -> float:
    """Idle fraction of the interleaved (Megatron-style virtual-stage)
    schedule: total_ticks ticks of length T/v versus m·T of useful work
    per device. Strictly below GPipe's for v>1 at equal microbatches
    (e.g. 4 stages × 8 microbatches: 0.273 → 0.158 at v=2)."""
    inject = _injection_schedule(n_stages, microbatches, virtual_stages)
    total_ticks = inject[-1] + virtual_stages * n_stages
    return 1.0 - microbatches * virtual_stages / total_ticks


def to_virtual_layout(tree, n_stages: int, virtual_stages: int,
                      inverse: bool = False):
    """Permute a params-shaped tree's stacked "blocks" leaves from
    standard layer order into the interleaved schedule's virtual-stage
    order (or back, inverse=True).

    Virtual stage c·n+d (chunk c of device d) must own global layers
    [(c·n+d)·Lc, (c·n+d+1)·Lc); under the P('pipe') row sharding device
    d holds rows [d·L/n, (d+1)·L/n), so new row d·(L/n)+c·Lc+l maps to
    old row (c·n+d)·Lc+l. Optimizer-slot dicts (params-shaped trees one
    level down) are handled by recursing until a "blocks" key appears.
    Apply ONCE at setup; checkpoints should store standard layout (run
    inverse=True before saving)."""
    import numpy as np

    if not isinstance(tree, dict) or not tree:
        return tree
    if "blocks" not in tree:
        return {k: to_virtual_layout(v, n_stages, virtual_stages,
                                     inverse) for k, v in tree.items()}
    blocks = tree["blocks"]
    any_leaf = jax.tree_util.tree_leaves(blocks)[0]
    L = any_leaf.shape[0]
    n, v = n_stages, virtual_stages
    if L % (n * v):
        raise ValueError(
            f"{L} stacked layers not divisible by {n} stages x {v} "
            "virtual stages — refusing to build a garbage permutation")
    lc = L // (n * v)
    perm = np.empty(L, np.int64)
    for d in range(n):
        for c in range(v):
            for l in range(lc):
                perm[d * (L // n) + c * lc + l] = (c * n + d) * lc + l
    if inverse:
        perm = np.argsort(perm)
    out = dict(tree)
    out["blocks"] = jax.tree_util.tree_map(
        lambda a: jnp.take(a, jnp.asarray(perm), axis=0), blocks)
    return out


def make_pipeline_train_step(
    model: TransformerLM,
    method,
    mesh: Mesh,
    pipe_axis: str = "pipe",
    dp_axis: Optional[str] = None,
    microbatches: int = 4,
    virtual_stages: int = 1,
) -> Callable:
    """Jitted pipeline training step for TransformerLM over pipe(×data).

    Signature: (params, slots, tokens, targets, lr, stepno, rng)
             -> (params', slots', mean_loss)

    tokens/targets: (B, S) with B divisible by microbatches (× dp size).
    The model must have tp_axis=None/sp_axis=None (pipe composes with dp
    here; TP/SP composition inside a stage is a further extension).

    virtual_stages=1 is classic GPipe. virtual_stages=v>1 is the
    interleaved (Megatron-style) schedule: each device owns v
    round-robin layer chunks, every tick runs ONE chunk (L/(n·v)
    layers), and a microbatch circles the ring v times — warmup/drain
    shrinks from (n−1) full-stage ticks to (n−1) chunk ticks, cutting
    the bubble fraction by ~v at equal microbatches (the backward
    mirrors the forward via jax.grad, so the whole step benefits).
    Params/slots must be pre-permuted with `to_virtual_layout` (and
    inverse-permuted before checkpointing in standard layout).
    """
    if model.tp_axis is not None or model.sp_axis is not None:
        raise ValueError("pipeline stage model must not set tp/sp axes")
    if model.cfg.moe_experts:
        raise NotImplementedError(
            "pipeline over a MoE-FFN TransformerLM (the MoE aux loss "
            "and expert-stacked specs are not plumbed through GPipe)")
    n = mesh.shape[pipe_axis]
    v = virtual_stages
    if model.cfg.num_layers % (n * v):
        raise ValueError(
            f"num_layers {model.cfg.num_layers} not divisible by "
            f"{n} pipeline stages x {v} virtual stages")
    m_micro = microbatches
    cfg = model.cfg
    layers_per_chunk = cfg.num_layers // (n * v)
    inject = _injection_schedule(n, m_micro, v)
    total_ticks = inject[-1] + v * n
    # static per-tick tables: which chunk each device runs (idle → 0,
    # its result simply never reaches a loss), which microbatch is
    # injected at device 0 / finished at device n-1 this tick
    import numpy as np
    chunk_tbl = np.zeros((total_ticks, n), np.int32)
    for j, t0 in enumerate(inject):
        for dt in range(v * n):
            chunk_tbl[t0 + dt, dt % n] = dt // n
    inject_at = {t0: j for j, t0 in enumerate(inject)}
    finish_at = {t0 + v * n - 1: j for j, t0 in enumerate(inject)}

    def body(params, slots, tokens, targets, lr, stepno, rng):
        idx = lax.axis_index(pipe_axis)
        b, s = tokens.shape
        mb = b // m_micro
        toks_mb = tokens.reshape(m_micro, mb, s)
        tgts_mb = targets.reshape(m_micro, mb, s)

        def loss_fn(p):
            def embed(tk):
                return p["embed"][tk] + p["pos"][:s]

            def stage(x, chunk):
                # local blocks rows = this device's v chunks in order
                bp = jax.tree_util.tree_map(
                    lambda a: lax.dynamic_slice_in_dim(
                        a, chunk * layers_per_chunk, layers_per_chunk, 0),
                    p["blocks"]) if v > 1 else p["blocks"]

                def blk(x, bpar):
                    y, _aux = model._block(x, bpar, jax.random.PRNGKey(0),
                                           False)
                    return y, None
                x, _ = lax.scan(blk, x, bp)
                return x

            def head_loss(x, tg):
                x = model._ln(x, p["lnf_g"], p["lnf_b"])
                head = p["embed"].T if cfg.tie_embeddings else p["head"]
                logp = jax.nn.log_softmax(x @ head, axis=-1)
                return jnp.mean(
                    -jnp.take_along_axis(logp, tg[..., None], -1))

            perm = [(j, (j + 1) % n) for j in range(n)]
            h = jnp.zeros((mb, s, cfg.dim), jnp.float32)
            total = jnp.zeros((), jnp.float32)
            for t in range(total_ticks):
                x_in = h
                if t in inject_at:  # static: device 0's slot is free
                    x_in = jnp.where(idx == 0,
                                     embed(toks_mb[inject_at[t]]), h)
                chunk = jnp.asarray(chunk_tbl[t])[idx]
                y = stage(x_in, chunk)
                if t in finish_at:  # static: mb leaves chunk v-1 at n-1
                    total = total + jnp.where(
                        idx == n - 1,
                        head_loss(y, tgts_mb[finish_at[t]]), 0.0)
                if t != total_ticks - 1:
                    h = lax.ppermute(y, pipe_axis, perm)
            # share the last stage's loss with every stage (identity bwd)
            return tp_reduce(total, pipe_axis) / m_micro

        loss, grads = jax.value_and_grad(loss_fn)(params)

        # replicated leaves are used on different stages → sum partials
        specs = pipeline_specs(pipe_axis, cfg.tie_embeddings)
        grads = jax.tree_util.tree_map(
            lambda sp, g: g if any(a is not None for a in sp)
            else lax.psum(g, pipe_axis),
            specs, grads, is_leaf=lambda x: isinstance(x, P))
        if dp_axis:
            grads = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, dp_axis), grads)
            loss = lax.pmean(loss, dp_axis)

        new_params, new_slots = method.update(grads, params, slots, lr,
                                              stepno)
        return new_params, new_slots, loss

    specs = pipeline_specs(pipe_axis, cfg.tie_embeddings)
    from bigdl_tpu.parallel.tensor_parallel import slot_specs_for

    slot_specs = slot_specs_for(method, specs)
    tok_spec = P(dp_axis, None) if dp_axis else P()
    smapped = shard_map(
        body, mesh=mesh,
        in_specs=(specs, slot_specs, tok_spec, tok_spec, P(), P(), P()),
        out_specs=(specs, slot_specs, P()),
        check_vma=False,
    )
    step = jax.jit(smapped, donate_argnums=(0, 1))
    bubble = interleaved_bubble_fraction(n, m_micro, v)
    step.bubble_fraction = bubble
    import logging

    logging.getLogger("bigdl_tpu.parallel").info(
        "pipeline schedule: %d stages x %d microbatches x %d virtual, "
        "bubble fraction %.3f%s", n, m_micro, v, bubble,
        "" if v > 1 else " (GPipe)")
    return step
