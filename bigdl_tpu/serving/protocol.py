"""What a served model owes the engine: `ServedModel`, the one seam
between `serving/` and `models/`.

No reference counterpart (the reference serves no language model with
a cache). `InferenceEngine` and `SpeculativeEngine` call a model by the
names below and by no other, and never through `getattr`: a model that
derives from `ServedModel` answers every one, with the default where
it has nothing of its own to say, and a model that does not derive
from it is refused at construction. `TransformerLM`, `LatentMoELM`,
`WindowMoELM`, `CCAMoELM`, `HybridSSMLM`, `LoopLM` and
`serving/tp.TPServingLM` implement it. This module imports nothing of
the package at import time (`models/` imports it; it asks
`serving/quant.py` and `serving/tp.py` only inside
`serving_refusals`).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# what a pool entry of a kind other than "table" keeps, and where: one
# sentence a kind, the reason of every refusal below
_KEPT_OUTSIDE_BLOCKS = {
    "ring": "a sliding layer's rows live in its slot's ring "
            "(cache_kinds), which keeps the last window only",
    "state": "a layer's per-slot state (cache_kinds) has no position "
             "axis: what it knows of the context is one row a slot, "
             "which no block carries",
}
# the options that share, move or roll back TABLE blocks, and why each
# cannot carry what `{kept}` says: asked of every model by its kinds
_NEEDS_BLOCKS_ONLY = {
    "speculative": "a rejected draft suffix has already overwritten "
                   "what the slot kept, and rollback cannot bring it "
                   "back: {kept}",
    "prefix_cache": "{kept}: a hit would need what the shared prefix "
                    "left there, a snapshot per tree node",
    "spill": "it parks prefix-cache blocks on the host, and {kept}",
    "role": "a handoff package carries table blocks, and {kept}",
}


class ServedModel:
    """The protocol, name by name. "The engine" is `InferenceEngine`;
    unless it says otherwise a name is asked ONCE, at the engine's
    construction, and the answer is static for the model object (it is
    a static argument of the jitted steps).

    **Attributes.**
    `cfg.max_len` (the default cache length), `cfg.vocab_size` (the
    sampler's path, every round; `SpeculativeEngine` compares draft's
    and target's). `variables`: the weights an engine built with
    `variables=None` serves (`nn.Module` has it). `tp = 1`: shards one
    engine's weights and pool are split over (a label of every series
    and `health()["tp"]`; `TPServingLM` sets it). `tp_axis = None`: a
    mesh axis armed for TRAINING tensor parallelism; the engine refuses
    a model with one unless it is given `tp_mesh=`.

    **Refusals.** `check_serving_options(weight_dtype, tp, speculative,
    prefix_cache, spill, role)`: the engine calls it first of all, with
    its own options (`tp` = a `tp_mesh` was given), `SpeculativeEngine`
    with `speculative=True` for draft and target; raises
    `NotImplementedError("<Class> does not serve with <what>: <why>")`
    for the first option asked that `serving_refusals()` has a reason
    against. Implemented HERE and nowhere else: the reasons are facts
    about a cache kind, about `serving/quant.py` and `serving/tp.py`,
    or data on the class (`unserved`). `kept_outside_blocks()` is the
    kinds' part of it, which `import_handoff` asks too.

    **The cache.** `cache_kinds()` -> a tuple as long as
    `init_block_pool`'s, the KIND of each entry: "table" (rows in
    blocks the slot's table names; the engine allocates, grows, shares
    and releases them), "ring" (rows in a region of the leaf the slot
    owns for good, `ops/kv_cache.init_ring_pool`), "state" (one row a
    slot, no position axis: the prefill sets it, a decode step rewrites
    it, the engine zeroes a poisoned request's). Default: every entry
    "table". `ring_blocks(block_size)` -> blocks of one slot's ring, 0
    exactly where no entry is a "ring". `slot_state_bytes(cache_dtype)`
    -> bytes ONE seated slot keeps in the "state" entries (the engine
    passes its cache's dtype; a caller that passes none gets the
    float32 answer), 0 exactly where no entry is a "state".
    `init_block_pool(num_blocks, block_size, dtype, slots=1)` -> the
    pools, a tuple of dicts of leaves: a "table" leaf `(num_blocks,
    block_size, ...)` with block 0 scratch (blocks are axis 0 for
    every holder of a pool; `import_handoff` alone reads the block
    size off axis 1, and a model whose leaves put something else there,
    `LoopLM`'s passes, names `role` in `unserved`), a "ring" leaf `(1 + slots *
    ring_blocks, block_size, ...)`, a "state" leaf `(slots, ...)`; the
    engine always passes `slots=`, a table-only model ignores it.
    `place_pools(pools)` -> the pools, committed again to the placement
    the model's programs expect, after the engine's eager scatters
    (scrub, handoff import, spill re-admission); default: as they are.

    **The weights.** `serving_params(variables)` -> the tree the jitted
    steps take as `params`; at construction and at every
    `swap_params`. Default: `variables["params"]`.

    **The programs** (traced inside the engine's jitted steps).
    `prefill_paged({"params": params}, tokens (1, bucket), pools,
    table (1, max_blocks), block_ids, start)` -> pools: one request's
    suffix at positions [start, start + bucket). `block_ids` is int32
    (n,), the fresh table blocks; for a model with a ring
    `{"table": ids, "ring": {"slot", "sources"}}`, with a state
    `{"table": ids, "state": {"slot", "keep"}}`.
    `decode_step_paged({"params": params}, tokens (B,), pos (B,),
    pools, table (B, max_blocks))` -> `(logits (B, V) float32, pools)`
    or `(logits, pools, aux)`: `aux` is a small pytree the model
    defines, fetched only while the tracer records and handed back to
    `decode_aux_report`.

    **What the spans say** (host code; asked only while a span is
    recorded, but for `prefill_span_args`, which the engine also reads
    once a bucket at construction for its `scan_chunks` counter, and
    `expert_matmul_form`, which `health()` reads). `decode_attn_form()`
    -> "rows" or "heads", the `attn_form` label (default "rows": the
    rows are attended as they are stored; `TransformerLM` chooses by
    shape). `expert_matmul_form(params, tokens)` -> "stream" /
    "ragged_dot", the `expert_matmul` label of a program of `tokens`
    rows, or None (no experts, no key). `prefill_span_args(bucket)` ->
    arguments of the `prefill` span (`moe_assignments`, `scan_chunks`).
    `decode_aux_report(aux)` -> arguments of the `decode_step` span
    from the step's fetched `aux` (`experts_touched`,
    `expert_load_max_over_mean`, `moe_assignments`, `skipped_rows`,
    `routed_rows`). `decode_read_report(pos, table, block_size)` ->
    arguments of the `decode_step` span from the host's clocks and
    table (`window_rows`, `full_rows`, `attended_rows`); the engine
    adds `state_bytes` for a model with a state; a model whose layers
    run several times adds `ut_steps`, `cache_entries` and
    `weight_bytes_streamed` (models/loop_lm.py). Defaults: `{}`. The
    experts' three are `parallel/moe.ExpertsReport`'s.
    `health_report()` -> keys the model adds to the engine's
    `health()` (asked at every `health()`; `ut_steps`,
    `cache_entries`). Default: `{}`."""

    tp = 1
    tp_axis = None
    # option of `check_serving_options` -> why THIS model does not
    # serve with it, where neither a cache kind nor quant.py / tp.py
    # is the reason
    unserved: Dict[str, str] = {}

    # ------------------------------------------------------- refusals

    def kept_outside_blocks(self) -> Optional[str]:
        """Why a table block does not carry all a slot keeps: the
        sentence of the first kind of `cache_kinds()` that is no
        "table"; None for a table-only model."""
        for kind in self.cache_kinds():
            if kind != "table":
                return _KEPT_OUTSIDE_BLOCKS[kind]
        return None

    def serving_refusals(self) -> Dict[str, str]:
        """option of `check_serving_options` -> why this model does
        not serve with it; an option that is served has no key."""
        from bigdl_tpu.serving import quant, tp

        kept = self.kept_outside_blocks()
        table = {} if kept is None else {
            option: why.format(kept=kept)
            for option, why in _NEEDS_BLOCKS_ONLY.items()}
        for option, why in (("weight_dtype", quant.why_not(self)),
                            ("tp", tp.why_not(self))):
            if why is not None:
                table[option] = why
        return {**table, **self.unserved}

    def check_serving_options(self, weight_dtype="fp32", tp=False,
                              speculative=False, prefix_cache=False,
                              spill=False, role="both"):
        asked = (("weight_dtype", weight_dtype != "fp32",
                  f"weight_dtype={weight_dtype!r}"),
                 ("tp", tp, "tp_mesh"),
                 ("speculative", speculative, "SpeculativeEngine"),
                 ("prefix_cache", prefix_cache, "prefix_cache=True"),
                 ("spill", spill, "spill=True"),
                 ("role", role != "both", f"role={role!r}"))
        refusals = self.serving_refusals()
        for option, is_asked, what in asked:
            if is_asked and option in refusals:
                raise NotImplementedError(
                    f"{type(self).__name__} does not serve with {what}: "
                    f"{refusals[option]}")

    # ------------------------------------------------------ the cache

    def cache_kinds(self) -> Tuple[str, ...]:
        entries = jax.eval_shape(
            lambda: self.init_block_pool(2, 2, jnp.float32, slots=1))
        return ("table",) * len(entries)

    def ring_blocks(self, block_size: int) -> int:
        return 0

    def slot_state_bytes(self, cache_dtype=None) -> int:
        return 0

    def init_block_pool(self, num_blocks: int, block_size: int,
                        dtype=jnp.float32, slots: int = 1):
        raise NotImplementedError(
            f"{type(self).__name__}.init_block_pool")

    def place_pools(self, pools):
        return pools

    # ---------------------------------------- weights and programs

    def serving_params(self, variables):
        return variables["params"]

    def prefill_paged(self, variables, tokens, pools, table, block_ids,
                      start):
        raise NotImplementedError(
            f"{type(self).__name__}.prefill_paged")

    def decode_step_paged(self, variables, tokens, pos, pools, table):
        raise NotImplementedError(
            f"{type(self).__name__}.decode_step_paged")

    # ------------------------------------------- what the spans say

    def decode_attn_form(self) -> str:
        return "rows"

    def expert_matmul_form(self, params, tokens: int) -> Optional[str]:
        return None

    def prefill_span_args(self, bucket: int) -> dict:
        return {}

    def decode_aux_report(self, aux) -> dict:
        return {}

    def decode_read_report(self, pos, table, block_size: int) -> dict:
        return {}

    def health_report(self) -> dict:
        return {}
