"""Tensor-parallel sharded serving — mesh-sharded decode over the
paged KV cache (ISSUE 10 tentpole).

The paper's scale-out story is data-parallel workers over a shared
parameter layout (arXiv 1804.05839); BigDL 2.0's Cluster Serving adds
worker elasticity one level up (arXiv 2204.01715). This module
supplies the missing MODEL-parallel axis under that same fleet plane:
one engine's weights and KV pool are sharded over a NamedSharding
mesh, behind the unchanged `InferenceEngine` surface
(`InferenceEngine(model, tp_mesh=mesh)`), so the router/autoscaler
layer from PR 7 and the paged prefix cache from PR 8 host sharded
engines without knowing it.

The split (per stacked serving layer, Megatron-shaped but bit-exact):

    wq/wk/wv, bq/bk/bv   column-sharded by HEAD (each shard owns
                         H/tp heads end to end)
    KV block pools       sharded by head — (N, bs, (H/tp)*D) per
                         shard (a row holds the heads side by side, so
                         a split of the last axis is a split by whole
                         heads), 1/tp cache residency; the block
                         TABLE stays host-side int32, REPLICATED and
                         identical on every shard, so every host-side
                         invariant (allocator, radix prefix tree,
                         copy-on-write caps) carries over verbatim
    w1/b1                column-sharded (ffn hidden split)
    wo/w2 + everything   replicated; their gemms run over the FULL
    else                 contraction extent on every shard

**Bit-identity construction.** The acceptance bar is tokens BITWISE
identical to the unsharded engine, which rules out Megatron's
row-parallel psum: psumming PARTIAL matmul sums changes the fp32
accumulation order. Instead the collective placed where that psum
would sit is `tp_shard_gather` (models/transformer.py) — one
all_gather per layer half that concatenates DISJOINT activation
shards back into the exact unsharded array, the same discipline that
makes zero2 bitwise == zero1 (all_gather of disjoint weight shards)
and warm prefix decode bitwise == cold (full-extent reductions,
ops/kv_cache.py). What stays sharded is everything whose unsharded
counterpart it reproduces exactly on this construction: per-head
attention (a pure batch split over heads), the head-column qkv gemms
and the ffn-up gemm (column splits keep each output element's
contraction extent intact — verified bitwise on the CPU backend and
pinned by tests/test_tp_serving.py + the tp_serve dryrun leg). The
price is that the wo/w2/logits-head gemms are computed replicated —
the deliberate trade for a serving plane whose failover, prefix-cache
and resharding invariants can be asserted bit-for-bit across layouts.

**Compile contract.** The wrapper is memoized per (model, mesh, axis)
— `tp_serving_model()` — and rides through the engine's shared jitted
steps as the static `model` argument, so a sharded engine compiles
exactly (#prefill buckets used) + 1 executables and every further
engine over the same (model, mesh, axis) compiles NOTHING
(tests/test_tp_serving.py pins both).

**Resharding.** `serving_params` leaves are GLOBAL jax arrays (the
mesh only places them), so a checkpointed layout moves between tp
degrees by re-placement: `gather_serving_params` fetches the host
(checkpoint) form, `shard_serving_params` places it on any other
mesh — round-trip pinned by tests/test_tp_serving.py.

All tp knobs are CONSTRUCTOR arguments (mesh, axis), never env —
graftlint trace-env-read applies to this module like the rest of the
serving plane.

**Observability (ISSUE 11).** The wrapper's `tp` attribute is the
layout label the whole journey/SLO plane keys on: the engine stamps
it on every request_submit / handoff_import / request_terminal event,
so obs/journey.py reconstructs cross-LAYOUT hops (a tp=2 engine
failing over to an unsharded survivor shows tp 2 → 1 on the journey),
and scripts/obs_report.py splits SLO digests per layout. Handoff
packages stay layout-free (GLOBAL arrays) — the journey's layout
labels come from the SEATING engine, never the package.
"""

from __future__ import annotations

import weakref
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu.models.transformer import TransformerLM
# the column-shard table, spec derivation and gather form live in the
# param-layout spine (ISSUE 18); this module keeps the serving-plane
# names and adds the mesh PLACEMENT the spine stays agnostic of
from bigdl_tpu.parallel.param_layout import (gather_tree,
                                             tp_serving_block_specs,
                                             tp_serving_specs)
from bigdl_tpu.parallel.tensor_parallel import shard_params
from bigdl_tpu.serving.protocol import ServedModel


def gather_serving_params(params):
    """Host (checkpoint) form of a possibly-sharded serving-layout
    tree: every leaf fetched as a GLOBAL numpy array. The inverse of
    `shard_serving_params` — placement round-trips bitwise across tp
    degrees because the mesh only places values, never changes them.
    (= the spine's `gather_tree`; this name is the serving-plane
    surface the hot-swap/resharding docs point at.)"""
    return gather_tree(params)


def shard_serving_params(mesh: Mesh, params, axis: str = "model"):
    """Place a serving-layout tree (host or device) on `mesh` under
    the tp serving specs — the resharding half of the checkpoint
    round-trip (a tp=2 checkpoint loads onto a tp=4 mesh, or back to
    an unsharded host tree, with every leaf bit-identical)."""
    return shard_params(mesh, tp_serving_specs(params, axis), params)


def why_not(model) -> Optional[str]:
    """Why this module does not wrap `model`, or None where it does
    (`ServedModel.serving_refusals` asks): it splits `TransformerLM`'s
    K and V pools by head and shards its block leaves, and knows no
    other pool and no other layout."""
    if isinstance(model, (TransformerLM, TPServingLM)):
        return None
    return ("serving/tp.py splits TransformerLM's K and V pools by "
            "head and shards its block leaves; it knows no other "
            "model's")


class TPServingLM(ServedModel):
    """Drop-in sharded serving backend: a `ServedModel`
    (serving/protocol.py) whose paged trio, `serving_params` and
    `place_pools` are the wrapped model's on the mesh, so
    `InferenceEngine` serves through it unchanged — the engine's
    jitted steps take it as their static `model` argument and trace
    shard_map'd bodies instead of single-mesh ones.

    Divisibility: `num_heads % tp == 0` (head-parallel attention) and
    `(dim * mlp_ratio) % tp == 0` (ffn column split). MoE and
    non-causal configs are refused exactly like the unsharded paged
    path."""

    def __init__(self, model: TransformerLM, mesh: Mesh,
                 axis: str = "model"):
        if axis not in mesh.shape:
            raise ValueError(f"mesh has no axis {axis!r} "
                             f"(axes: {dict(mesh.shape)})")
        cfg = model.cfg
        tp = int(mesh.shape[axis])
        if cfg.moe_experts:
            raise NotImplementedError(
                "tensor-parallel serving over a MoE FFN (shard experts "
                "with parallel/moe.py instead)")
        if cfg.num_heads % tp:
            raise ValueError(
                f"num_heads {cfg.num_heads} not divisible by tp degree "
                f"{tp} (head-parallel attention shards whole heads)")
        if (cfg.dim * cfg.mlp_ratio) % tp:
            raise ValueError(
                f"ffn hidden {cfg.dim * cfg.mlp_ratio} not divisible "
                f"by tp degree {tp}")
        self.model = model
        self.mesh = mesh
        self.axis = axis
        self.tp = tp
        self.cfg = cfg
        # the tp-aware twin: same config, tp_axis armed — its paged
        # trio runs the gather construction when traced inside
        # shard_map below (models/transformer.py)
        self._tp_model = TransformerLM(
            cfg, tp_axis=axis, name=f"{model.name}_tp{tp}")
        self._block_specs = tp_serving_block_specs(axis)
        # the last axis is H*D, heads contiguous: tp | H makes this a
        # split by whole heads
        self._pool_specs = tuple(
            {"k": P(None, None, axis), "v": P(None, None, axis)}
            for _ in range(cfg.num_layers))

    @property
    def variables(self):
        """The wrapped model's variables (the engine's default)."""
        return self.model.variables

    def _param_specs(self, params) -> Dict[str, Any]:
        return tp_serving_specs(params, self.axis)

    # ------------------------------------------------------ placement
    def serving_params(self, variables):
        """Repack into the per-layer serving layout, then shard:
        head-column leaves split over the mesh, the rest replicated.
        Leaves stay GLOBAL arrays — resharding to another tp degree is
        re-placement, not reshaping."""
        sp = self.model.serving_params(variables)
        return shard_params(self.mesh, self._param_specs(sp), sp)

    def init_block_pool(self, num_blocks: int, block_size: int,
                        dtype=jnp.float32, slots: int = 1):
        """The per-layer paged pools, head-sharded on the mesh: each
        shard holds (num_blocks, block_size, (H/tp)*D) per layer —
        1/tp KV residency, the serving memory win. Block ids/tables
        are untouched host integers, identical across shards. `slots`
        is not used (every entry is a "table")."""
        pools = self.model.init_block_pool(num_blocks, block_size,
                                           dtype)
        return self.place_pools(pools)

    def place_pools(self, pools):
        """(Re-)commit pool leaves to their head-axis sharding — used
        at creation and after host-side pool surgery (scrubs, handoff
        imports) whose eager scatter may have dropped the placement."""
        return shard_params(self.mesh, self._pool_specs, pools)

    # ------------------------------------------------------ paged trio
    def prefill_paged(self, variables, tokens, pools, table, block_ids,
                      start):
        """Sharded suffix prefill: each shard writes its own heads'
        k/v into its pool shard through the SAME replicated block
        table. Traced inside the engine's shared jitted prefill step
        (this wrapper is the static model argument)."""
        p = variables["params"] if "params" in variables else variables

        def body(p, pools, tokens, table, block_ids, start):
            return self._tp_model.prefill_paged(
                {"params": p}, tokens, pools, table, block_ids, start)

        fn = shard_map(
            body, mesh=self.mesh,
            in_specs=(self._param_specs(p), self._pool_specs, P(), P(),
                      P(), P()),
            out_specs=self._pool_specs, check_vma=False)
        return fn(p, pools, tokens, table, block_ids,
                  jnp.asarray(start, jnp.int32))

    def decode_attn_form(self) -> str:
        """The form is chosen from the LOCAL shape: a shard attends
        its own heads (models/transformer.decode_attn_form)."""
        return self.model.decode_attn_form(tp=self.tp)

    def decode_step_paged(self, variables, tokens, pos, pools, table):
        """Sharded decode step: per-head attention against the local
        pool shard, activation gathers keeping every contraction
        full-extent, logits replicated and bitwise == tp=1 — the
        engine samples from them exactly as it would unsharded."""
        p = variables["params"] if "params" in variables else variables

        def body(p, pools, tokens, pos, table):
            return self._tp_model.decode_step_paged(
                {"params": p}, tokens, pos, pools, table)

        fn = shard_map(
            body, mesh=self.mesh,
            in_specs=(self._param_specs(p), self._pool_specs, P(), P(),
                      P()),
            out_specs=(P(), self._pool_specs), check_vma=False)
        return fn(p, pools, tokens, pos, table)


# memoized wrappers: engines built over the same (model, mesh, axis)
# must share ONE wrapper object — the engine's jitted steps are
# static-arg'd on the model, so sharing the wrapper is what makes the
# #buckets+1 compile contract hold fleet-wide for sharded pools too.
# WEAK values: the wrapper lives exactly as long as something serves
# through it (every engine holds its model, = the wrapper) — a
# long-lived process that churns through fresh models doesn't pin
# each one (and its params) forever just because it served sharded
_WRAPPERS: "weakref.WeakValueDictionary[Tuple[int, Mesh, str], TPServingLM]" \
    = weakref.WeakValueDictionary()


def tp_serving_model(model: TransformerLM, mesh: Mesh,
                     axis: str = "model") -> TPServingLM:
    """The memoized constructor `InferenceEngine(tp_mesh=...)` goes
    through: one TPServingLM per (model, mesh, axis), so pool growth
    over one model object keeps compiling nothing (while any engine
    over the triple is alive — a fully-released layout is rebuilt,
    and recompiled, on next use)."""
    if isinstance(model, TPServingLM):
        # a fleet factory reusing an existing sharded engine's .model
        # together with tp_mesh=: same layout passes through (sharing
        # its executables); re-wrapping onto a DIFFERENT layout is a
        # config error, not a silent double-shard
        if model.mesh == mesh and model.axis == axis:
            return model
        raise ValueError(
            f"model is already tp-wrapped for (mesh={model.mesh}, "
            f"axis={model.axis!r}); to serve its weights on another "
            "layout, pass the underlying model (wrapper.model)")
    key = (id(model), mesh, axis)
    got = _WRAPPERS.get(key)
    if got is None or got.model is not model:   # id() reuse guard
        got = TPServingLM(model, mesh, axis)
        _WRAPPERS[key] = got
    return got
