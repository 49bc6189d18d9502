"""Continuous-batching inference engine — the TPU-native analog of
BigDL 2.0's low-latency Cluster Serving (arXiv 2204.01715), built on
the KV-cache incremental decode path (models/transformer.py
prefill/decode_step, ops/kv_cache.py).

Design
------
* **Paged KV pool + block tables (ISSUE 8).** The engine owns one
  PAGED KV cache: per-layer `(num_blocks, block_size, H*D)` pools
  (ops/kv_cache.py) plus a host `(slots, max_blocks)` int32 block
  TABLE — a slot is a row of pool indices, not a contiguous buffer.
  A request occupies one slot from prefill to finish; eviction and
  admission are block-table surgery plus ref-count updates
  (serving/kv_pool.py) BETWEEN decode steps — never a cache copy, and
  never a jitted-shape change (the table rides into the decode step as
  a (B, max_blocks) operand). Block 0 is reserved scratch: inactive
  rows point at it and write their garbage there.
* **Radix prefix reuse.** Admission looks the prompt up in a
  content-hashed radix tree over block-aligned token chunks
  (serving/prefix_cache.py): the longest cached prefix's blocks are
  ref-counted into the slot's table (copy-on-write — shared blocks
  are read-only; every write position is in an exclusive block) and
  only the SUFFIX is prefilled, at most `(len(prompt)-1)//block_size`
  blocks reused so the re-decoded last prompt token never touches a
  shared block. Freshly prefilled full prompt blocks are inserted
  into the tree immediately, so a burst of shared-prompt requests
  amortizes its prefill after the first admission; refcount-0 blocks
  stay cached and are LRU-evicted only under pool pressure. The
  load-bearing bar: cached-prefix decode is BIT-IDENTICAL to cold
  decode — in co-batch, across eviction/reuse cycles, and through
  fleet failover (ops/kv_cache.py explains the full-table-extent
  construction; tests/test_kv_pool.py and the serve_prefix drill pin
  it).
* **One decode executable, ever.** The decode step is a single jitted
  function over all B slots; per-slot position, current token, PRNG
  stream, sampling knobs (temperature/top-k/top-p) and the poison
  operand are (B,) operands, and inactive slots simply compute garbage
  rows that the host ignores (rows are independent: LN/matmul/attention
  are per-row). Ragged traffic therefore triggers exactly
  (#prefill buckets used) + 1 compilations — the compile-count guard
  test pins this (tests/test_serving.py).
* **Prefill buckets.** The SUFFIX (whole prompt on a miss) pads right
  to the nearest bucket (serving/bucketing.py); causal attention makes
  real positions independent of the pad, and the pad's garbage lands
  beyond the row clock in the request's exclusive blocks — masked on
  read, overwritten in place by decode. Prefill for ONE request
  compiles per bucket (cold and warm share the executable — the
  prefix length is an operand) and scatters its k/v straight into the
  fresh pool blocks — admissions don't depend on how many requests
  arrive together.
* **First token via re-decode.** Prefill only fills the cache (its
  head projection is dead code XLA eliminates). The slot then enters
  the decode loop with current-token = last prompt token and clock =
  len-1: the first decode step rewrites that position's k/v and
  samples the first new token — every generated token comes from the
  same executable, and no separate sample-from-prefill path exists to
  compile or to drift. The rewrite is why prefix reuse caps at the
  blocks STRICTLY before this position: it always lands in an
  exclusive block, never a shared one.
* **Per-request determinism.** Sampling keys are
  fold_in(PRNGKey(request.seed), #generated) — a request's output is
  bit-independent of its slot, its co-batch, and arrival order (the
  batcher-equivalence property the tests assert).

Reliability layer (the BigDL contract — arXiv 1804.05839: jobs survive
task failures and stragglers instead of crashing — carried into the
serving plane; every behavior below is deterministically fault-drilled
via utils/faults serving kinds and scripts/fault_drill.py --plane
serving):

* **Request lifecycle.** Every request ends in exactly one terminal
  status — ``done`` / ``shed`` / ``expired`` / ``poisoned`` /
  ``failed`` (`GenerationResult.status`). Per-request deadlines
  (`Request.deadline_s`, a TTL from submission enforced both queued and
  decoding), max queue wait (`Request.max_queue_wait_s`), host-side
  cancellation (`cancel()`), and bounded retry-with-backoff for
  transient decode-step failures (`step_retries`/`retry_backoff_s`).
  Deadlines are measured against an injectable `clock` so the expiry
  drills are bit-deterministic.
* **Admission control & backpressure.** `max_queue` bounds the queue;
  on overload the `overload_policy` decides: ``reject`` (submit raises
  OverloadError), ``shed-oldest`` (evict the longest-queued request
  with status ``shed``), or ``shed-lowest-priority`` (evict the
  lowest-`Request.priority` queued request — or the new request itself
  if it is lowest). Admission into free slots is highest-priority
  first, FIFO within a priority. None of it walks the queue
  (serving/request_queue.py, ISSUE 46): the next request, the expired
  ones, the victim to shed and the ids in flight come out of an index,
  at a cost that does not go by the queue's depth.
* **Poison isolation.** The decode step returns a (B,) finite-logits
  health operand (utils/anomaly.rows_finite — one jit-side reduction,
  fetched alongside the token, no extra host sync). A NaN/inf row
  evicts ONLY that request with status ``poisoned``; co-batched rows
  are untouched (rows are independent) and their outputs stay
  bit-identical to running alone. The poisoned slot's cache rows are
  scrubbed to zero before reuse, and ops/kv_cache.cached_attention
  nan-scrubs masked value rows, so a genuinely non-finite request can
  never leak NaN into the slot's next occupant.
* **Step watchdog.** `step_timeout_s` arms a wall-clock budget over
  decode dispatch+fetch (the work runs on a daemon thread; a device
  call that blocks instead of erroring becomes a StepTimeout instead
  of a wedged host). A trip degrades the engine:
  in-flight AND queued requests fail with status ``failed``, the
  engine quiesces (submit raises EngineDegraded), and `health()`
  surfaces the snapshot: slot occupancy, queue depth/buckets, p50/p95
  decode latency, deadline misses, sheds, retries, watchdog trips.

The engine serves any `ServedModel` (serving/protocol.py: every name
it asks of a model, with its arguments, its result and its default,
is written there and nowhere else) and refuses what is not one. Of a
pool's leaves it knows the leading axis and the entry's cache kind,
and nothing past them.

Tensor-parallel sharding (ISSUE 10): `tp_mesh=` swaps the model for
the memoized `serving/tp.py` wrapper — weights and the per-layer KV
pool shard over the mesh (pool BY HEAD, so the block table
and every host-side invariant here stay byte-identical), the jitted
steps trace shard_map'd bodies, and the emitted tokens are BITWISE
identical to the unsharded engine (the tp_shard_gather construction).
Everything in this file is layout-blind: slots, tables, the radix
tree, overload/poison/watchdog handling never ask how many shards
serve them — which is exactly what lets a tp=2 engine fail over to an
unsharded survivor with bit-identical rerouted tokens (the
fleet_tp_failover drill).

Disaggregated prefill (ISSUE 10 stretch): `role="prefill"` turns an
engine into a prefill tier — step() admits and prefills as usual, but
then EXPORTS each filled slot's KV block contents as a host-side
HandoffPackage instead of decoding (take_handoffs() drains them) —
and `import_handoff()` on a serving engine seats a package directly
into a slot + fresh pool blocks, skipping prefill entirely. The block
contents are bitwise what the importer's own prefill would have
written (the same full-extent-reduction discipline that makes warm ==
cold), so a handed-off request decodes bit-identically to a
single-engine run — across sharding layouts, since prefill bits are
tp-invariant. The router (serving/router.py handoff path) moves the
packages so long prompts never stall a decode engine's token streams.
"""

from __future__ import annotations

import functools
import itertools
import logging
import math
import threading
import time
import warnings
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import obs
from bigdl_tpu.ops.kv_cache import (READ_SHARE_NAMES, decode_read,
                                    ring_prompt_sources)
from bigdl_tpu.serving.bucketing import (bucket_for, bucket_histogram,
                                         default_buckets, pad_tokens)
from bigdl_tpu.serving.kv_pool import BlockPool
from bigdl_tpu.serving.prefix_cache import RadixPrefixCache
from bigdl_tpu.serving.protocol import ServedModel
from bigdl_tpu.serving.request_queue import RequestQueue
from bigdl_tpu.serving.sampler import (SAMPLER_PATHS, sample_logits,
                                       step_path)
from bigdl_tpu.utils import faults
from bigdl_tpu.utils.anomaly import rows_finite

logger = logging.getLogger("bigdl_tpu.serving")

# terminal request statuses (GenerationResult.status)
STATUSES = ("done", "shed", "expired", "poisoned", "failed")

OVERLOAD_POLICIES = ("reject", "shed-oldest", "shed-lowest-priority")

# which stats counter each terminal status bumps
_STATUS_COUNTER = {"done": "requests_done", "shed": "shed",
                   "expired": "deadline_misses", "poisoned": "poisoned",
                   "failed": "failed"}
# reverse view: stats key → terminal status (registry label)
_COUNTER_STATUS = {v: k for k, v in _STATUS_COUNTER.items()}

# per-process engine index — the registry label distinguishing
# co-resident engines' series (deterministic within a process run, so
# drill snapshots stay bit-reproducible)
_ENGINE_IDS = itertools.count()

# process-wide trace tallies for the SHARED jitted steps below; an
# engine snapshots them at creation and reports its own deltas
_TRACES = {"prefill": 0, "decode": 0}

# the category of the leaves that split `admit` (obs/spans.py: the
# "serving" tree is a closed set of names that the benchmark's readers
# sum by name; these are seen only by who asks for every category)
_ADMIT_PARTS = "serving.admit"


class OverloadError(RuntimeError):
    """submit() under overload_policy='reject' with a full queue."""


class StepTimeout(RuntimeError):
    """Decode dispatch+fetch exceeded the watchdog budget (the hung
    device model: the call blocks indefinitely instead of erroring)."""


def _watchdog_call(fn, timeout_s: Optional[float]):
    """Run a dispatch+fetch closure under an optional wall-clock budget
    on a daemon thread — the watchdog pattern shared by the engine's
    decode step and the SpeculativeEngine's draft/verify dispatches
    (ISSUE 15). `timeout_s=None` runs inline. Raises StepTimeout when
    the budget passes with the thread still alive (the hung-device
    model: the call blocks instead of erroring); other exceptions
    propagate unchanged. The daemon thread suffices because
    steady-state PJRT dispatch/fetch releases the GIL while it waits.
    Backend INIT is outside this guard: it happens once, in the one
    process that owns the chip, before any engine exists."""
    if timeout_s is None:
        return fn()
    box: Dict[str, object] = {}

    def boxed():
        try:
            box["r"] = fn()
        except BaseException as e:      # noqa: BLE001
            box["e"] = e

    th = threading.Thread(target=boxed, daemon=True,
                          name="bigdl-serving-step")
    th.start()
    th.join(timeout_s)
    if th.is_alive():
        raise StepTimeout(
            f"decode dispatch+fetch exceeded {timeout_s} s watchdog "
            "budget")
    if "e" in box:
        raise box["e"]                  # type: ignore[misc]
    return box["r"]                     # type: ignore[misc]


class EngineDegraded(RuntimeError):
    """The engine quiesced after a watchdog trip or exhausted step
    retries; build a fresh engine (executables are shared, so the
    replacement pays no recompile)."""


class EngineDraining(RuntimeError):
    """submit() on an engine in drain mode (stop-admission): already
    accepted work runs to completion, new work must go elsewhere —
    the EngineRouter (serving/router.py) and the autoscaler's
    scale-down path rely on exactly this contract."""


def _first_leaf(pools):
    """The first leaf of a pool-shaped tree (the engine's pools, a
    handoff package's or a migrated entry's blocks), by position:
    what its leaves are called is the model's business."""
    return jax.tree_util.tree_leaves(pools)[0]


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _prefill_step(model, params, pools, tokens, start, block_ids,
                  table_row):
    """Prefill ONE request's suffix (1, bucket) into the paged pools:
    k/v scatter into the fresh `block_ids`, attention gathered through
    the slot's full `table_row` (cached prefix blocks included) from
    position `start` — a traced operand, so cold (start=0) and warm
    prefills share ONE executable per bucket. `model` is a static
    argument, so every engine over the same model object shares it
    too."""
    _TRACES["prefill"] += 1               # runs at trace time only
    return model.prefill_paged({"params": params}, tokens, pools,
                               table_row, block_ids, start)


@functools.partial(jax.jit, static_argnums=(0, 12), donate_argnums=(2,))
def _decode_step(model, params, pools, tok, pos, seed, nout, temp,
                 topk, topp, poison, table, attn_impl="xla"):
    """One decode step over all slots + per-row sampling + per-row
    finite-logits health. Shared across engines of the same model
    (static arg) — ONE executable ever. `table` (B, max_blocks) int32
    is each slot's block-table row (an operand: block surgery never
    retraces). `poison` (B,) bool is the serve_nan injection operand:
    a True row's logits are forced to NaN INSIDE the jitted step, so
    the drill exercises the same health reduction and eviction path a
    genuinely non-finite request would — and, being a (B,) operand,
    arming it never retraces. `attn_impl` is a constant nothing reads:
    the benchmark's AOT tests (tests/bench/test_aot.py:134,
    test_aot_mla_moe.py:170) pass the literal as a thirteenth
    argument, and only a `benchmark` PR may edit them (ROADMAP A0);
    no caller in the program passes it. `aux` is a small pytree
    the MODEL defines, a third result of its `decode_step_paged`
    (models/latent_moe.py: the tokens each expert got); a model that
    returns two has none, and its program is what it was. The engine
    fetches it only while the tracer records."""
    _TRACES["decode"] += 1                # runs at trace time only
    logits, pools, *aux = model.decode_step_paged(
        {"params": params}, tok, pos, pools, table)
    logits = jnp.where(poison[:, None], jnp.float32(jnp.nan), logits)
    finite = rows_finite(logits)
    keys = jax.vmap(lambda s, t: jax.random.fold_in(
        jax.random.PRNGKey(s), t))(seed, nout)
    nxt = sample_logits(logits, keys, temp, topk, topp)
    return nxt, finite, pools, tuple(aux)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _clear_slot_state(kinds, pools, slot):
    """Zero one slot's row of every "state" entry of the pools, in
    place, in one program for all the layers: nothing a poisoned
    request wrote outlives its eviction."""
    return tuple(
        jax.tree_util.tree_map(
            lambda leaf: leaf.at[slot].set(jnp.zeros((), leaf.dtype)),
            layer) if kind == "state" else layer
        for kind, layer in zip(kinds, pools))


@dataclass
class Request:
    """One generation request. temperature <= 0 → greedy; top_k <= 0 /
    top_p >= 1 → that filter off. `stop_ids`: generation ends when one
    is sampled (the stop token is not emitted).

    Reliability knobs (all host-side — none changes a jitted shape):
    `priority` — higher admits first and survives
    shed-lowest-priority overload; `deadline_s` — TTL in clock seconds
    from submission, enforced while queued AND while decoding (expiry
    → status 'expired', partial tokens kept); `max_queue_wait_s` —
    tighter bound on time spent queued only.

    Journey tracing (ISSUE 11, host-side only): `trace_id` is stamped
    at first admission (router or engine — deterministic, derived from
    the admitting component's obs label + the request id, never a
    clock or RNG) and `hop` counts engine-to-engine moves (failover
    resubmission, rebalance, disaggregated-prefill import). Every
    lifecycle event carries both, and obs/journey.py reconstructs the
    cross-engine timeline from them.

    Multi-tenancy (ISSUE 19, host-side only): `tenant` names the
    consumer the request bills against — the router's
    TenancyController gates admission by its token bucket and WFQ
    weight, the engine's `tenant_kv_quotas` bounds its exclusive KV
    blocks, and every lifecycle event carries the name. `model_tag`
    selects the engine GROUP that may serve the request (None →
    'default'); dispatch, failover and rebalance never cross groups."""
    prompt: Sequence[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    stop_ids: Sequence[int] = ()
    seed: int = 0
    id: Optional[int] = None
    priority: int = 0
    deadline_s: Optional[float] = None
    max_queue_wait_s: Optional[float] = None
    trace_id: Optional[str] = None
    hop: int = 0
    tenant: Optional[str] = None
    model_tag: Optional[str] = None


@dataclass
class HandoffPackage:
    """One prefilled request, detached from its prefill engine
    (disaggregated prefill, ISSUE 10): the original Request, the
    host-side KV block contents its prefill wrote (per-layer
    {'k','v'} arrays of shape (nb, block_size, H*D) — GLOBAL arrays,
    so the package moves between sharding layouts), and the original
    submit stamp (the importer re-stamps its meta with it, so
    TTFT/latency tell the whole truth across the handoff)."""
    request: Request
    kv: Tuple[Dict[str, object], ...]
    submit_t: float
    source: str


@dataclass
class GenerationResult:
    """`status` is the terminal lifecycle state (one of STATUSES):
    'done' (finish_reason: "stop_id" | "max_tokens" | "cache_full"),
    'shed' (overload victim or cancelled — finish_reason "shed" /
    "cancelled"), 'expired' (deadline or queue-wait TTL), 'poisoned'
    (non-finite logits row), 'failed' (engine degraded mid-request).
    Non-done results keep whatever tokens were generated before the
    terminal event.

    `latency_s` is submit→terminal and `ttft_s` submit→first-token,
    both on the ENGINE clock (injectable — deterministic in drills;
    None when unknown, e.g. ttft before any token). The same numbers
    ride on the request_terminal event, so scripts/obs_report.py can
    compute SLO percentiles from the JSONL alone."""
    id: int
    prompt: List[int]
    tokens: List[int]
    finish_reason: str
    status: str = "done"
    ttft_s: Optional[float] = None
    latency_s: Optional[float] = None


class InferenceEngine:
    """Continuous-batching engine over a fixed number of cache slots.

    >>> eng = InferenceEngine(model, slots=4)
    >>> eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=16))
    >>> results = eng.run()          # drain queue + slots

    `stats` self-reports the zero-recompile contract:
    prefill_traces == #distinct buckets used, decode_traces == 1.
    `health()` is the operational snapshot (state, occupancy, queue,
    latency percentiles, reliability counters).

    Reliability knobs: `max_queue` + `overload_policy` (admission
    control), `step_timeout_s` (watchdog over dispatch+fetch),
    `step_retries`/`retry_backoff_s` (transient step failures),
    `clock` (monotonic-seconds source for deadlines — injectable so
    expiry drills are bit-deterministic).

    Paged-cache knobs (constructor args, never env — graftlint
    trace-env-read): `block_size` (tokens per KV block; cache length
    must divide by it; >= 2), `pool_blocks` (total pool blocks incl.
    the reserved scratch block 0; default slots * cache_len //
    block_size + 1 — dense-capacity parity), `prefix_cache` (False
    disables radix reuse — every admission prefills cold; the bench's
    cold-baseline column).

    Host-RAM spill tier (ISSUE 16; constructor args, never env):
    `spill=True` turns pool-pressure eviction of refcount-0 prefix
    blocks into a SPILL to pinned host numpy arrays (the
    HandoffPackage per-layer {'k','v'} layout) — bytes, never
    recomputation, so warm==cold bit-identity extends across a
    spill/re-admit round trip; `host_blocks` caps the host tier
    (default: the device pool's capacity), whose own LRU evicts to
    oblivion. Re-admission on a prefix hit is a host→device placement
    plus block-table patch — zero new executables. `admit_requeue_
    budget` bounds how many times a failed admission may requeue
    before the request finishes 'pool_exhausted' (the admission-spin
    bugfix — a pool that never frees must not spin a request through
    the queue forever).

    Sharding knobs (ISSUE 10; constructor args, never env):
    `tp_mesh` + `tp_axis` — serve through the serving/tp.py wrapper:
    weights and KV pool shard over the mesh (pool on the head axis),
    tokens stay BITWISE identical to the unsharded engine.
    `role='prefill'` turns the engine into a disaggregated-prefill
    tier (step() exports HandoffPackages instead of decoding);
    'decode' is a topology label serving exactly like 'both'."""

    def __init__(self, model, variables=None, slots: int = 4,
                 max_len: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 cache_dtype=jnp.float32,
                 block_size: int = 16,
                 pool_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 spill: bool = False,
                 host_blocks: Optional[int] = None,
                 admit_requeue_budget: int = 64,
                 max_queue: Optional[int] = None,
                 overload_policy: str = "reject",
                 step_timeout_s: Optional[float] = None,
                 step_retries: int = 0,
                 retry_backoff_s: float = 0.05,
                 clock: Callable[[], float] = time.monotonic,
                 obs_label: Optional[str] = None,
                 tp_mesh=None, tp_axis: str = "model",
                 role: str = "both",
                 weight_dtype: str = "fp32",
                 model_tag: Optional[str] = None,
                 tenant_kv_quotas: Optional[Dict[str, int]] = None):
        if not isinstance(model, ServedModel):
            raise TypeError(
                f"{type(model).__name__} is not a ServedModel: what the "
                "engine asks of a model is serving/protocol.py's, and a "
                "model derives from it")
        # what the model does not serve under is refused here, before
        # anything is built
        model.check_serving_options(
            weight_dtype=weight_dtype, tp=tp_mesh is not None,
            prefix_cache=bool(prefix_cache), spill=bool(spill), role=role)
        if tp_mesh is not None:
            # memoized: engines over the same (model, mesh, axis)
            # share one wrapper and therefore every jitted executable
            # (serving/tp.py) — a sharded model passed directly as
            # `model` (e.g. by a fleet factory) works identically
            from bigdl_tpu.serving.tp import tp_serving_model

            model = tp_serving_model(model, tp_mesh, tp_axis)
        elif model.tp_axis is not None:
            # a training-TP model's paged trio would trace
            # tp_shard_gather's all_gather with no mesh bound to the
            # axis — a cryptic deep-trace failure; refuse here with
            # the fix in hand
            raise ValueError(
                f"model has tp_axis={model.tp_axis!r} armed (training "
                "tensor parallelism): serve it sharded via "
                "InferenceEngine(tp_mesh=...), which wraps it through "
                "serving/tp.py — or build a plain TransformerLM for "
                "unsharded serving")
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"role {role!r}: expected 'both', "
                             "'prefill' or 'decode'")
        if role == "prefill" and (step_timeout_s is not None
                                  or step_retries):
            # the watchdog/retry machinery wraps the DECODE dispatch,
            # which a prefill tier never runs — accepting the knobs
            # would promise a guard that cannot trip
            raise ValueError(
                "step_timeout_s/step_retries on a prefill-role "
                "engine: the watchdog and retry budget guard the "
                "decode dispatch, which role='prefill' never runs")
        # 'decode' is a fleet-topology label — it serves exactly like
        # 'both' (handoff imports AND direct admissions); 'prefill'
        # changes step() into the export path
        self.role = role
        # what the decode program does with the cache at this model's
        # (local) widths: "rows" / "heads" (ops/kv_cache
        # .paged_attention_form, chosen from the shape). Static per
        # compiled program, so a label and not a rate
        self.attn_form = model.decode_attn_form()
        # weight layout (ISSUE 17; constructor arg, never env):
        # "fp32" is THE bit-identity reference layout every bitwise
        # pin runs on; "int8" repacks the serving gemm weights via
        # serving/quant.py under a tolerance contract
        # (tests/test_quant_serving.py) — the router keeps failover
        # within one layout_family for exactly that reason
        if weight_dtype not in ("fp32", "int8"):
            raise ValueError(f"weight_dtype {weight_dtype!r}: "
                             "expected 'fp32' or 'int8'")
        if weight_dtype != "fp32" and tp_mesh is not None:
            raise ValueError(
                "weight_dtype='int8' under tp_mesh: the sharded path "
                "pins BITWISE tp==unsharded tokens, which a lossy "
                "weight layout cannot honor — quantize unsharded "
                "engines only")
        self.weight_dtype = weight_dtype
        # engine-group membership (ISSUE 19; constructor arg, never
        # env): the router scopes dispatch/failover/rebalance/affinity
        # to engines sharing one tag (None → the 'default' group).
        # Mutable on purpose — EngineRouter.move_engine regroups a
        # same-model engine compile-free by rewriting it.
        self.model_tag = model_tag
        # per-tenant KV quotas (ISSUE 19; constructor arg, never env):
        # tenant name → max EXCLUSIVELY-owned pool blocks summed over
        # this engine's active slots. Admission SKIPS (never blocks
        # behind) a quota-exceeded request — it stays queued and other
        # tenants keep admitting past it.
        if tenant_kv_quotas:
            for t, qn in tenant_kv_quotas.items():
                if qn < 1:
                    raise ValueError(
                        f"tenant_kv_quotas[{t!r}] must be >= 1")
        self.tenant_kv_quotas = dict(tenant_kv_quotas or {})
        self._quota_noted: set = set()
        self.model = model
        # tp degree for telemetry/provenance (1 = unsharded)
        self.tp = int(model.tp)
        self.variables = variables if variables is not None \
            else model.variables
        # one-time repack into the per-layer serving layout (stacked
        # weights pay a full-stack slice copy per decoded token);
        # swap_params re-runs the identical build for weight hot-swap
        self._params = self._build_params(self.variables)
        # stored weight bytes for the bench rows' bytes/token
        # provenance (QuantWeight leaves count q AND scale)
        self._weight_bytes = int(sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(self._params)))
        self.slots = slots
        self.cache_len = max_len if max_len is not None \
            else model.cfg.max_len
        self.cache_dtype = cache_dtype
        if block_size < 2:
            raise ValueError("block_size must be >= 2 (a 1-token "
                             "suffix prefill would break the paged "
                             "bit-identity contract, ops/kv_cache.py)")
        if self.cache_len % block_size:
            raise ValueError(f"cache length {self.cache_len} must be "
                             f"a multiple of block_size {block_size}")
        self.block_size = block_size
        self.blocks_per_slot = self.cache_len // block_size
        if pool_blocks is None:
            # capacity parity with the old dense cache: every slot can
            # hold a full-length sequence with zero sharing (+1 for
            # the reserved scratch block 0); sharing then turns spare
            # blocks into cached prefixes instead of requiring them
            pool_blocks = slots * self.blocks_per_slot + 1
        if pool_blocks < self.blocks_per_slot + 1:
            raise ValueError(
                f"pool_blocks {pool_blocks} cannot hold even one "
                f"full-length sequence ({self.blocks_per_slot} blocks "
                "+ scratch)")
        self.pool_blocks = pool_blocks
        self.prefix_cache_enabled = bool(prefix_cache)
        # host-RAM spill tier (ISSUE 16): constructor args, never env
        if spill and not prefix_cache:
            raise ValueError("spill=True without prefix_cache: the "
                             "spill tier parks radix-tree blocks — "
                             "there is nothing to spill with the tree "
                             "disabled")
        if host_blocks is not None and not spill:
            raise ValueError("host_blocks without spill=True")
        if host_blocks is not None and host_blocks < 1:
            raise ValueError("host_blocks must be >= 1 (or None for "
                             "device-pool-capacity parity)")
        self.spill_enabled = bool(spill)
        self.host_blocks = 0 if not spill else int(
            host_blocks if host_blocks is not None else pool_blocks)
        if admit_requeue_budget < 1:
            raise ValueError("admit_requeue_budget must be >= 1")
        self.admit_requeue_budget = admit_requeue_budget
        self._admit_fails: Dict[int, int] = {}
        # the KIND of each entry of the model's pool tuple ("table",
        # "ring", "state": serving/protocol.py, "the cache"). Blocks of
        # a "table" entry are allocated, grown and released below; a
        # slot's ring (`_ring_blocks` blocks) and its state row
        # (`_slot_state_bytes`) are its own for good: nothing here
        # allocates them, `_release_slot` scrubs a poisoned request's
        # state. What indexes every leaf by a TABLE block id (spill,
        # handoff, migration, the prefix tree) the model has refused
        # for them above
        self._cache_kinds = tuple(model.cache_kinds())
        self._ring_blocks = model.ring_blocks(block_size)
        self._slot_state_bytes = model.slot_state_bytes(cache_dtype)
        self.pool = model.init_block_pool(pool_blocks, block_size,
                                          cache_dtype, slots=slots)
        self._pool_mgr = BlockPool(pool_blocks, block_size)
        self._prefix = RadixPrefixCache(self._pool_mgr,
                                        host_blocks=self.host_blocks)
        # KV bytes one token occupies across all layers (the
        # bytes-saved counter's unit), from the pool leaves themselves
        # — model-agnostic
        self._kv_bytes_per_token = int(sum(
            leaf.nbytes // leaf.shape[0]
            for kind, layer in zip(self._cache_kinds, self.pool)
            if kind != "state"      # a slot's row, not a token's
            for leaf in jax.tree_util.tree_leaves(layer))
            // block_size)
        self.buckets = tuple(sorted(
            prefill_buckets if prefill_buckets is not None
            else default_buckets(self.cache_len)))
        if max(self.buckets) > self.cache_len:
            raise ValueError(f"bucket {max(self.buckets)} exceeds cache "
                             f"length {self.cache_len}")
        if overload_policy not in OVERLOAD_POLICIES:
            raise ValueError(f"overload_policy {overload_policy!r}: "
                             f"expected one of {OVERLOAD_POLICIES}")
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None)")
        if step_retries < 0:
            raise ValueError("step_retries must be >= 0")
        self.max_queue = max_queue
        self.overload_policy = overload_policy
        self.step_timeout_s = step_timeout_s
        self.step_retries = step_retries
        self.retry_backoff_s = retry_backoff_s
        self._clock = clock
        # in-round spans run on the tracer's clock unless a drill
        # injected one here: one timeline, one clock (obs/spans.py)
        self._span_clock = None if clock is time.monotonic else clock
        # a traced round's admitted / emitted request ids (step())
        self._round_log: Optional[Dict[str, list]] = None
        self._stats: Dict[str, int] = {
            "prefill_calls": 0, "decode_steps": 0, "requests_done": 0,
            "shed": 0, "rejected": 0, "deadline_misses": 0,
            "poisoned": 0, "failed": 0, "retries": 0,
            "watchdog_trips": 0, "cancelled": 0,
            "prefix_hits": 0, "prefix_blocks_reused": 0,
            "prefix_tokens_saved": 0, "prefix_bytes_saved": 0,
            "pool_evictions": 0, "pool_eviction_visits": 0,
            "kv_spill_blocks": 0, "kv_readmit_blocks": 0,
            "kv_host_evictions": 0, "admit_requeue_exhausted": 0,
            "handoffs_out": 0, "handoffs_in": 0,
            "weight_swaps": 0,
        }
        # ---- telemetry plane (ISSUE 5): every _stats increment also
        # mirrors into the process-wide registry under this engine's
        # label; decode-step latency feeds a FIXED-BUCKET histogram
        # (bounded memory for a long-lived engine — replaces the old
        # per-engine recent-latency deque) and health() percentiles
        # are estimated from its buckets. Children are resolved once
        # here (per the ACTIVE registry — install custom telemetry
        # before building engines); the per-step cost is an int add +
        # a bisect. `obs_label`: a replacement engine (the documented
        # degrade-and-rebuild path) should pass its predecessor's
        # health()["metrics"]["engine"] label to CONTINUE that series
        # instead of growing the registry with one label set per
        # rebuild.
        self._obs_name = obs_label or f"engine{next(_ENGINE_IDS)}"
        # ISSUE 10: every engine series carries its tensor-parallel
        # shard count as a label ("1" unsharded), so fleet dashboards
        # can split traffic by layout without new metric families
        self._obs_tp = str(self.tp)
        reg = obs.get_registry()
        self._m_requests = reg.counter(
            "serving_requests_total",
            "requests reaching a terminal status",
            labelnames=("engine", "status", "tp"))
        op_help = {
            "prefill_calls": "prefill dispatches",
            "decode_steps": "batched decode steps",
            "retries": "decode-step retries",
            "watchdog_trips": "step-watchdog trips",
            "rejected": "submissions rejected under overload",
            "cancelled": "host-side cancellations",
            "prefix_hits": "admissions that reused a cached prefix",
            "prefix_blocks_reused": "KV blocks reused from the "
                                    "prefix cache",
            "prefix_tokens_saved": "prompt tokens whose prefill was "
                                   "skipped by a prefix hit",
            "prefix_bytes_saved": "KV bytes not recomputed thanks to "
                                  "prefix hits",
            "pool_evictions": "LRU prefix blocks evicted under pool "
                              "pressure",
            "pool_eviction_visits": "entries of the prefix tree's LRU "
                                    "index examined while choosing "
                                    "eviction victims (about one an "
                                    "eviction)",
            "kv_spill_blocks": "refcount-0 KV blocks spilled to the "
                               "host-RAM tier",
            "kv_readmit_blocks": "host-tier KV blocks re-admitted to "
                                 "device on a prefix hit",
            "kv_host_evictions": "host-tier KV blocks evicted to "
                                 "oblivion under host pressure",
            "admit_requeue_exhausted": "admissions abandoned after "
                                       "exhausting the requeue budget",
            "handoffs_out": "prefilled requests exported for "
                            "disaggregated decode",
            "handoffs_in": "prefilled requests imported from a "
                           "prefill tier",
            "weight_swaps": "weight hot-swaps re-placed into the live "
                            "serving layout (ISSUE 18)",
        }
        self._m_ops = {
            key: reg.counter(f"serving_{key}_total", help_,
                             labelnames=("engine", "tp")
                             ).labels(engine=self._obs_name,
                                      tp=self._obs_tp)
            for key, help_ in op_help.items()}
        # decode steps by what the program's sampler ran for the seated
        # rows (serving/sampler.py: the costliest row's class), counted
        # on the host from the step's own operands
        self._sampler_steps = dict.fromkeys(SAMPLER_PATHS, 0)
        self._m_sampler = {
            path: reg.counter(
                "serving_sampler_steps_total",
                "decode steps by the sampler path their rows took",
                labelnames=("engine", "path")
                ).labels(engine=self._obs_name, path=path)
            for path in SAMPLER_PATHS}
        # decode steps by the share of the table their read was compiled
        # for (ops/kv_cache.decode_read: the program's own roundings),
        # counted on the host from the step's clocks and table
        self._read_steps = dict.fromkeys(READ_SHARE_NAMES, 0)
        self._m_read = {
            read: reg.counter(
                "serving_decode_read_steps_total",
                "decode steps by the share of the block table their "
                "read was compiled for",
                labelnames=("engine", "read")
                ).labels(engine=self._obs_name, read=read)
            for read in READ_SHARE_NAMES}
        self._m_lat = reg.histogram(
            "serving_decode_step_seconds",
            "decode dispatch+fetch wall seconds",
            labelnames=("engine", "tp")).labels(
                engine=self._obs_name, tp=self._obs_tp)
        self._m_pool_gauge = reg.gauge(
            "serving_kv_pool_blocks_in_use",
            "KV pool blocks held by live requests or cached prefixes",
            labelnames=("engine", "tp")).labels(
                engine=self._obs_name, tp=self._obs_tp)
        # ISSUE 17: occupancy in BYTES — in-use blocks x the pool's
        # actual per-block footprint, so a bf16/int8 cache_dtype
        # engine's residency reads half/quarter the fp32 engine's at
        # equal block counts
        self._m_pool_bytes_gauge = reg.gauge(
            "serving_kv_pool_bytes",
            "KV pool bytes held by live requests or cached prefixes "
            "(block count x cache-dtype block footprint)",
            labelnames=("engine", "tp")).labels(
                engine=self._obs_name, tp=self._obs_tp)
        # per-tier occupancy (ISSUE 16): device = in-use pool blocks
        # (live + cached), host = parked spill-tier blocks
        self._m_tier_gauges = {
            tier: reg.gauge(
                "serving_kv_tier_blocks_in_use",
                "KV blocks resident per tier (device pool in-use vs "
                "host-RAM spill tier)",
                labelnames=("engine", "tier", "tp")
                ).labels(engine=self._obs_name, tier=tier,
                         tp=self._obs_tp)
            for tier in ("device", "host")}
        # rows of ONE layer of each cache kind that the seated slots
        # hold: "full" in table blocks (they grow with the context),
        # "window" in rings (never more than slots x ring rows)
        self._m_rows_gauges = {
            kind: reg.gauge(
                "serving_kv_rows_held",
                "cache rows one layer of a kind holds for the seated "
                "slots (full: table blocks; window: ring blocks)",
                labelnames=("engine", "kind")
                ).labels(engine=self._obs_name, kind=kind)
            for kind in ("window", "full")}
        self._m_state_gauge = reg.gauge(
            "serving_slot_state_bytes",
            "bytes the seated slots keep in the model's per-slot state "
            "entries (cache kind 'state': no position axis)",
            labelnames=("engine",)).labels(engine=self._obs_name)
        # chunks the model's scan runs over a prompt, for each bucket (a
        # model whose `prefill_span_args` names them: models/
        # hybrid_ssm.py; no other has any): counted once an admission,
        # whether or not the tracer records
        self._scan_chunks = {
            b: model.prefill_span_args(b).get("scan_chunks", 0)
            for b in self.buckets}
        self._m_scan_chunks = reg.counter(
            "serving_prefill_scan_chunks_total",
            "chunks of the model's state-space scan that admitted "
            "prompts were prefilled in, a layer",
            labelnames=("engine",)).labels(engine=self._obs_name)
        self._m_tp_gauge = reg.gauge(
            "serving_tp_shards",
            "tensor-parallel shard count serving this engine",
            labelnames=("engine",)).labels(engine=self._obs_name)
        if obs.enabled():
            self._m_tp_gauge.set(self.tp)
        self._trace0 = dict(_TRACES)
        # finished results not yet handed back by a run(requests=...)
        # call — retrievable here (results are never silently dropped)
        self.completed: Dict[int, GenerationResult] = {}
        self._queue = RequestQueue()
        self._ids = itertools.count()
        self._req: List[Optional[Request]] = [None] * slots
        self._gen: List[List[int]] = [[] for _ in range(slots)]
        # block table: row per slot, entry 0 = unassigned (scratch) —
        # the decode step's (B, max_blocks) operand
        self._table = np.zeros((slots, self.blocks_per_slot), np.int32)
        # per-slot (hit_blocks, own_blocks): shared prefix refs vs
        # exclusively owned blocks, for release at eviction
        self._slot_blocks: List[List[List[int]]] = [
            [[], []] for _ in range(slots)]
        self._pos = np.zeros(slots, np.int32)
        self._tok = np.zeros(slots, np.int32)
        self._nout = np.zeros(slots, np.int32)   # sampling-stream clock
        self._seed = np.zeros(slots, np.int32)
        self._temp = np.zeros(slots, np.float32)
        self._topk = np.zeros(slots, np.int32)
        self._topp = np.ones(slots, np.float32)
        self._meta: Dict[int, Dict[str, float]] = {}  # id → submit time
        self._degraded: Optional[str] = None
        self._draining = False
        # prefill-role export queue, drained by take_handoffs()
        self._handoffs: List[HandoffPackage] = []
        self._aux = None    # a recorded step's fetched model aux
        if step_timeout_s is not None:
            # arming the watchdog opts into a warmup decode at
            # construction: the FIRST decode call traces+compiles
            # (seconds to minutes), which would trip any sane
            # steady-state budget and permanently degrade a healthy
            # engine. The warmup runs unguarded — a compile is not a
            # hang, and no steady-state budget should price it. Inactive
            # slots compute garbage the host ignores, and every slot
            # is prefilled (position 0 rewritten) before it decodes.
            self._dispatch_and_fetch(np.zeros(slots, bool), 0.0,
                                     watchdog=False)

    def _build_params(self, variables):
        """The serving weight layout for `variables`, through the
        param-layout spine (ISSUE 18): per-layer unstack
        (`model.serving_params` → parallel/param_layout.unstack_blocks;
        the tp wrapper's variant additionally mesh-places via
        shard_serving_params), then the int8 block-leaf repack when
        quantized (serving/quant.py). The constructor and
        `swap_params` run the IDENTICAL build — one spine, no drift."""
        params = self.model.serving_params(variables)
        if self.weight_dtype == "int8":
            from bigdl_tpu.serving.quant import quantize_serving_params

            params = quantize_serving_params(params)
        return params

    def swap_params(self, variables) -> None:
        """Hot-swap model weights (ISSUE 18): rebuild the serving
        layout from `variables` and re-point the jitted steps' params
        OPERAND. The model is the static jit argument and the new
        tree arrives with identical structure/shapes/dtypes, so the
        swap is pure re-placement — zero new
        executables (the `_TRACES` census pins it) and no quiesce:
        in-flight slots keep their KV bytes and decode their next
        token under the new weights. Swapping a speculative DRAFT is
        invisible in the token stream by construction (acceptance
        exactness is draft-independent, ISSUE 15); swapping a TARGET
        changes its tokens — that gate is the caller's contract."""
        params = self._build_params(variables)
        if jax.tree_util.tree_structure(params) \
                != jax.tree_util.tree_structure(self._params):
            raise ValueError(
                "swap_params: new variables produce a different "
                "serving-layout structure — hot-swap is re-placement "
                "over the SAME layout, never a re-architecture")
        old_shapes = [l.shape for l in
                      jax.tree_util.tree_leaves(self._params)]
        new_shapes = [l.shape for l in
                      jax.tree_util.tree_leaves(params)]
        if old_shapes != new_shapes:
            raise ValueError(
                "swap_params: leaf shapes changed — a different model "
                "config cannot hot-swap into a live engine")
        self.variables = variables
        self._params = params
        self._bump("weight_swaps")

    @property
    def stats(self) -> Dict[str, int]:
        """Counters incl. this engine's trace (compile) deltas — an
        engine built over a model another engine already served
        reports 0 new traces (the executables are shared)."""
        d = dict(self._stats)
        d["prefill_traces"] = _TRACES["prefill"] - self._trace0["prefill"]
        d["decode_traces"] = _TRACES["decode"] - self._trace0["decode"]
        return d

    @property
    def degraded(self) -> Optional[str]:
        """None while healthy, else the degradation reason."""
        return self._degraded

    @property
    def draining(self) -> bool:
        """True once drain() was called (stop-admission mode)."""
        return self._draining

    @property
    def idle(self) -> bool:
        """No queued and no in-flight requests."""
        return not self._queue and all(r is None for r in self._req)

    @property
    def slots_active(self) -> int:
        """Occupied cache slots (the router's load signal)."""
        return sum(r is not None for r in self._req)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def obs_name(self) -> str:
        """This engine's registry/event label (see `obs_label`)."""
        return self._obs_name

    @property
    def layout_family(self) -> str:
        """'{weight_dtype}/{cache dtype}' — the numerics contract a
        request's tokens were produced under (ISSUE 17). The router
        reroutes only within one family: fp32 engines pin bitwise
        token identity across failover, and a lossy layout's tokens
        are only comparable to the same layout's."""
        return f"{self.weight_dtype}/{np.dtype(self.cache_dtype).name}"

    def drain(self) -> None:
        """Enter stop-admission mode: subsequent submit() raises
        EngineDraining; already-accepted requests (queued AND
        in-flight) keep stepping to their normal terminal status.
        health()['state'] reports 'draining' until the engine empties,
        then 'drained' — the autoscaler removes an engine only after
        that transition, so scale-down never loses a request.
        Idempotent; there is deliberately no undrain (a drained engine
        is retired — build a fresh one, executables are shared)."""
        if self._draining:
            return
        self._draining = True
        obs.emit_event("engine_drain", plane="serving",
                       engine=self._obs_name,
                       queued=len(self._queue),
                       active=sum(r is not None for r in self._req))

    def health(self) -> Dict[str, object]:
        """Operational snapshot: engine state, slot occupancy, queue
        depth + per-bucket composition, p50/p95 decode-step latency,
        and every reliability counter.

        Percentiles are estimated from the registry's FIXED-BUCKET
        latency histogram over the engine's whole lifetime — bounded
        memory however long the engine lives (ISSUE 5: previously a
        recent-sample deque). None before the first decode step. The
        histogram is fed unconditionally (core health bookkeeping,
        like `stats` — BIGDL_OBS=off gates events/spans/counter
        mirrors, not this). `metrics` is the raw registry view of
        this engine's series, for scrapers that want more than two
        percentiles."""
        def pct(q):
            v = self._m_lat.quantile(q)
            return None if v is None else round(v * 1e3, 3)

        if self._degraded:
            state = "degraded"
        elif self._draining:
            state = "drained" if self.idle else "draining"
        else:
            state = "ok"
        s = self._stats
        return {
            "state": state,
            "degraded_reason": self._degraded,
            "tp": self.tp,
            "role": self.role,
            # serving-layout provenance (ISSUE 17): which attention
            # form decodes and which numerics family tokens carry
            "attn_form": self.attn_form,
            # and which form the decode program's grouped expert
            # matmuls take (no key: a model without experts)
            **self._expert_matmul(self.slots),
            # what the model says of itself (serving/protocol.py: a
            # looped model's passes and row sets; most say nothing)
            **self.model.health_report(),
            # the share of the block table a decode step reads at the
            # slots' current clocks
            "attended_share": round(
                self._decode_read()[1] / self._table.size, 4),
            # rows one layer of each cache kind holds for the seated
            # slots (the serving_kv_rows_held gauge)
            "kv_rows_held": self._kv_rows_held(),
            # what the seated slots keep beside their rows (the
            # serving_slot_state_bytes gauge; 0: no "state" entry)
            "slot_state_bytes": self._slot_state_held(),
            # decode steps since start by sampler path, as shares
            "sampler_path_share": {
                path: round(n / max(1, s["decode_steps"]), 4)
                for path, n in self._sampler_steps.items()},
            # and by the share of the table their read was compiled for
            "read_share_steps": {
                read: round(n / max(1, s["decode_steps"]), 4)
                for read, n in self._read_steps.items()},
            "weight_dtype": self.weight_dtype,
            "cache_dtype": np.dtype(self.cache_dtype).name,
            "model_tag": self.model_tag,
            "handoffs_out": s["handoffs_out"],
            "handoffs_in": s["handoffs_in"],
            "slots": self.slots,
            "slots_active": self.slots_active,
            "queue_depth": self.queue_depth,
            "queue_buckets": bucket_histogram(
                [len(r.prompt) for r in self._queue], self.buckets),
            "decode_p50_ms": pct(0.50),
            "decode_p95_ms": pct(0.95),
            "deadline_misses": s["deadline_misses"], "shed": s["shed"],
            "rejected": s["rejected"], "poisoned": s["poisoned"],
            "retries": s["retries"],
            "watchdog_trips": s["watchdog_trips"],
            "failed": s["failed"], "cancelled": s["cancelled"],
            "requests_done": s["requests_done"],
            "decode_steps": s["decode_steps"],
            "prefix": {
                "enabled": self.prefix_cache_enabled,
                "hits": s["prefix_hits"],
                "blocks_reused": s["prefix_blocks_reused"],
                "tokens_saved": s["prefix_tokens_saved"],
                "bytes_saved": s["prefix_bytes_saved"],
                "evictions": s["pool_evictions"],
                "tree_blocks": self._prefix.num_blocks,
                "pool": self._pool_mgr.stats(),
                "spill": self.spill_enabled,
                "host_blocks": self.host_blocks,
                "host_in_use": self._prefix.host_in_use,
                "spilled": s["kv_spill_blocks"],
                "readmitted": s["kv_readmit_blocks"],
                "host_evictions": s["kv_host_evictions"],
            },
            "metrics": {
                "engine": self._obs_name,
                "decode_step_seconds": {
                    "count": self._m_lat.count,
                    "sum": round(self._m_lat.sum, 6),
                    "p50_ms": pct(0.50), "p95_ms": pct(0.95),
                    "p99_ms": pct(0.99)},
                "requests_total": {
                    st: s[_STATUS_COUNTER[st]] for st in STATUSES},
            },
        }

    # --------------------------------------------------------------- host
    def submit(self, request: Request) -> int:
        n = len(request.prompt)
        if self._degraded:
            raise EngineDegraded(
                f"engine degraded ({self._degraded}); build a fresh "
                "engine — same-model executables are shared, so the "
                "replacement pays no recompile")
        if self._draining:
            raise EngineDraining(
                "engine is draining (stop-admission): route new "
                "requests to another engine in the pool")
        if n == 0:
            raise ValueError("empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (the engine "
                             "always samples at least one token)")
        bucket_for(n, self.buckets)      # raises if no bucket fits
        # duplicate-id guard: a resubmitted in-flight id must never be
        # accepted (it would collide in `completed`)
        if request.id is None:
            rid = next(self._ids)
            while self._in_flight(rid):  # user-chosen ids may have
                rid = next(self._ids)    # claimed counter values
            request.id = rid
        elif self._in_flight(request.id):
            raise ValueError(f"request id {request.id} already in flight "
                             "or completed-unclaimed")
        if request.trace_id is None:
            # first admission anywhere: open the journey (router
            # admission stamps first in a fleet; a bare engine stamps
            # its own — deterministic either way, no clock/RNG).
            # Stamped BEFORE the overload gate below: a request shed
            # on arrival must still carry its trace on the terminal
            # (obs/journey.py renders it as a terminal-only hop)
            request.trace_id = f"{self._obs_name}/{request.id}"
            request.hop = 0
        # expire stale queued requests BEFORE the overload check: a
        # queue full of already-dead TTLs must not reject (or shed a
        # victim from) fresh traffic — and the dead ones must report
        # 'expired', not 'shed'
        self._expire_queued(self._clock())
        if self.max_queue is not None \
                and len(self._queue) >= self.max_queue:
            self._overload(request)
            if request.id in self.completed:     # new request was shed
                return request.id
        self._meta[request.id] = {"t": self._clock()}
        self._queue.append(request, self._expires_at(request))
        obs.emit_event("request_submit", plane="serving",
                       engine=self._obs_name, request=request.id,
                       prompt_len=n, priority=request.priority,
                       tp=self.tp, role=self.role,
                       **self._trace_fields(request))
        return request.id

    def _overload(self, request: Request) -> None:
        """Queue at max_queue: apply the overload policy. Either raises
        (reject), sheds a queued victim (making room), or sheds
        `request` itself (shed-lowest-priority when it IS the lowest —
        its result lands in `completed` and submit returns its id)."""
        if self.overload_policy == "reject":
            self._bump("rejected")
            obs.emit_event("request_rejected", plane="serving",
                           engine=self._obs_name, request=request.id,
                           queue_depth=len(self._queue),
                           **self._trace_fields(request))
            raise OverloadError(
                f"queue full ({self.max_queue}); request {request.id} "
                "rejected (overload_policy='reject')")
        if self.overload_policy == "shed-lowest-priority":
            victim = self._queue.lowest()
            if request.priority <= victim.priority:
                # the new arrival is (joint-)lowest — shed it instead
                self._terminal(request, "shed", "shed")
                return
            self._queue.remove(victim.id)
        else:                                     # shed-oldest
            victim = self._queue.popleft()
        self._terminal(victim, "shed", "shed")

    def cancel(self, request_id: int) -> GenerationResult:
        """Cancel a queued or in-flight request (host-side, between
        steps). The result (status 'shed', finish_reason 'cancelled',
        partial tokens if it was decoding) lands in `completed` and is
        returned. KeyError if the id is not queued or in flight."""
        if self._queue.holds(request_id):
            self._bump("cancelled")
            return self._terminal(self._queue.remove(request_id),
                                  "cancelled", "shed")
        for i, r in enumerate(self._req):
            if r is not None and r.id == request_id:
                self._bump("cancelled")
                res = self._finish(i, "cancelled", "shed")
                self.completed[res.id] = res
                return res
        raise KeyError(f"request {request_id} is not queued or in flight")

    def steal_queued(self, k: int) -> List[Tuple[Request, float]]:
        """Give up to `k` queued requests (with their original submit
        stamps) to the fleet router for rebalancing — the ones THIS
        engine's scheduler would serve last (lowest priority; youngest
        within a priority — the exact inverse of pop_next), so work
        moves from the back of a long line to an engine with idle
        capacity. A request that actually moves is restamped by the
        receiving engine's submit (deadline TTLs restart — the
        conservative direction); one that BOUNCES back comes home via
        _requeue with its original stamp, so a failed move never
        extends a TTL. Never touches in-flight slots."""
        out: List[Tuple[Request, float]] = []
        for _ in range(min(k, len(self._queue))):
            req = self._queue.pop_last()
            meta = self._meta.pop(req.id, None)
            out.append((req, meta["t"] if meta else self._clock()))
        return out

    def _requeue(self, request: Request,
                 t: Optional[float] = None) -> None:
        """Router-only undo of a steal that found no taker: back onto
        the queue, bypassing the admission gates (the request was
        already admitted once). `t` restores the original submit
        stamp — a bounced move must not restart the TTL clock."""
        self._meta[request.id] = {"t": self._clock() if t is None
                                 else t}
        self._queue.append(request, self._expires_at(request))

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._req) if r is None]

    def _deadline_at(self, req: Request) -> float:
        if req.deadline_s is None or req.id not in self._meta:
            return math.inf
        return self._meta[req.id]["t"] + req.deadline_s

    def _expires_at(self, req: Request) -> float:
        """When a QUEUED request expires: its deadline or its
        max-queue-wait TTL, whichever comes first (inf: never). Read
        once, as the request joins the queue."""
        if req.max_queue_wait_s is None:
            return self._deadline_at(req)
        return min(self._deadline_at(req),
                   self._meta[req.id]["t"] + req.max_queue_wait_s)

    def _in_flight(self, request_id) -> bool:
        """Queued, seated, or completed and not yet claimed."""
        return self._queue.holds(request_id) \
            or request_id in self.completed \
            or any(r is not None and r.id == request_id
                   for r in self._req)

    @staticmethod
    def _trace_fields(req: Request) -> Dict[str, object]:
        """Journey-context fields for a request-lifecycle event
        (ISSUE 11): empty when the request predates tracing. The
        tenant stamp rides along (ISSUE 19) so every lifecycle event
        of tenant-tagged traffic names its consumer."""
        out: Dict[str, object] = {}
        t = getattr(req, "trace_id", None)
        if t is not None:
            out["trace"] = t
            out["hop"] = int(getattr(req, "hop", 0))
        tenant = getattr(req, "tenant", None)
        if tenant is not None:
            out["tenant"] = tenant
        return out

    def _span(self, name: str, parent: Optional[int] = None,
              cat: str = "serving"):
        """One span of the scheduling round's tree (obs/spans.py): the
        shared no-op unless the tracer is enabled. A site that has
        counts for it tests `span.id is not None` first and hands them
        to `span.set`, so that an untraced round builds nothing. `cat`
        is "serving" for the tree itself, a closed set of names, and
        `_ADMIT_PARTS` for the leaves that split `admit`."""
        return obs.get_tracer().span(name, cat, clock=self._span_clock,
                                     parent=parent)

    def _bump(self, key: str, n: int = 1) -> None:
        """One increment path: the engine-local stats dict (always,
        core bookkeeping) plus the registry mirror (when telemetry is
        on). Terminal-status keys land in serving_requests_total
        {engine,status}; operational keys in their own counters."""
        self._stats[key] += n
        if not obs.enabled():
            return
        status = _COUNTER_STATUS.get(key)
        if status is not None:
            self._m_requests.labels(engine=self._obs_name,
                                    status=status,
                                    tp=self._obs_tp).inc(n)
        else:
            self._m_ops[key].inc(n)

    def _lifecycle_times(self, req: Request
                         ) -> Tuple[Optional[float], Optional[float]]:
        """(ttft_s, latency_s) for a request reaching terminal NOW,
        from the engine clock — read BEFORE _meta is popped."""
        meta = self._meta.get(req.id)
        if meta is None or "t" not in meta:
            return None, None
        latency = self._clock() - meta["t"]
        tf = meta.get("t_first")
        return (None if tf is None else tf - meta["t"]), latency

    def _observe_terminal(self, req: Request, reason: str, status: str,
                          tokens: int, ttft_s: Optional[float],
                          latency_s: Optional[float]) -> None:
        """Telemetry for a request's terminal transition: structured
        event + (tracer on) a whole-lifecycle span stamped with the
        ENGINE clock, so deadline drills trace deterministically."""
        if not obs.enabled():
            return
        now = self._clock()
        obs.emit_event("request_terminal", plane="serving",
                       engine=self._obs_name, request=req.id,
                       status=status, reason=reason, tokens=tokens,
                       ttft_s=ttft_s, latency_s=latency_s,
                       tp=self.tp, role=self.role,
                       **self._trace_fields(req))
        tracer = obs.get_tracer()
        if tracer.enabled:
            t0 = self._meta.get(req.id, {}).get("t", now)
            tracer.complete(f"request[{status}]", "serving", t0, now,
                            args={"request": req.id, "reason": reason,
                                  "tokens": tokens})

    def _terminal(self, req: Request, reason: str, status: str
                  ) -> GenerationResult:
        """Terminal event for a request that never reached (or is no
        longer in) a slot — result goes straight to `completed`."""
        ttft, latency = self._lifecycle_times(req)
        self._observe_terminal(req, reason, status, 0, ttft, latency)
        self._meta.pop(req.id, None)
        self._admit_fails.pop(req.id, None)
        self._quota_noted.discard(req.id)
        self._bump(_STATUS_COUNTER[status])
        res = GenerationResult(req.id, list(req.prompt), [], reason,
                               status, ttft_s=ttft, latency_s=latency)
        self.completed[req.id] = res
        return res

    def _expire_queued(self, now: float) -> None:
        """Drop queued requests whose deadline or max-queue-wait TTL
        passed — status 'expired', zero tokens. One comparison when
        nothing is due (serving/request_queue.py)."""
        for r in self._queue.pop_expired(now):
            self._terminal(r, "expired", "expired")

    def _alloc_blocks(self, n: int,
                      protect: frozenset = frozenset()
                      ) -> Optional[List[int]]:
        """Take `n` fresh blocks. Under pool pressure, refcount-0
        prefix blocks SPILL to the host tier (ISSUE 16 — bytes kept,
        re-admitted on a later hit), falling back to plain LRU
        eviction when the tier is off or cannot take them; None when
        nothing can free enough (every block pinned by live requests).
        `protect` excludes the chain an in-flight re-admission holds
        from both the spill and host-eviction scans."""
        evicted = 0
        visits = self._prefix.visits
        while self._pool_mgr.free_count < n:
            if self._spill_blocks(n - self._pool_mgr.free_count,
                                  protect):
                continue
            b = self._prefix.evict_one()
            if b is None:
                break
            evicted += 1
        if self._prefix.visits > visits:
            self._bump("pool_eviction_visits",
                       self._prefix.visits - visits)
        if evicted:
            self._bump("pool_evictions", evicted)
            obs.emit_event("prefix_evict", plane="serving",
                           engine=self._obs_name, blocks=evicted)
        return self._pool_mgr.alloc(n)

    def _spill_blocks(self, want: int,
                      protect: frozenset = frozenset()) -> int:
        """Spill up to `want` LRU refcount-0 prefix blocks to the
        host tier (ISSUE 16): ONE batched device→host fetch for the
        whole victim set (the _export_handoff idiom — priced like a
        handoff, never per block per layer), then pure bookkeeping —
        each victim's bytes park on its tree node and its device
        block returns to the free list. A full host tier first evicts
        its LRU childless nodes to oblivion; victims the tier still
        cannot take are left for plain eviction. Returns the number
        spilled."""
        if not self.spill_enabled or want <= 0:
            return 0
        victims = self._prefix.spill_victims(want, protect)
        host_evicted = 0
        room = self.host_blocks - self._prefix.host_in_use
        while victims and room < len(victims):
            if not self._prefix.evict_host_one(protect):
                break
            host_evicted += 1
            room += 1
        victims = victims[:max(room, 0)]
        if host_evicted:
            self._bump("kv_host_evictions", host_evicted)
        if not victims:
            return 0
        idx = jnp.asarray([v.block for v in victims], jnp.int32)
        data = jax.device_get(tuple(                                 # graftlint: disable=hidden-device-sync — THE deliberate spill fetch (ISSUE 16): one batched device→host transfer per spill event covering every victim block across all layers, priced like a handoff export — never per block, never per layer, and only ever under pool pressure
            {k: leaf[idx] for k, leaf in layer.items()}
            for layer in self.pool))
        for j, v in enumerate(victims):
            self._prefix.park(v, tuple(
                {k: layer[k][j] for k in layer} for layer in data))
        self._bump("kv_spill_blocks", len(victims))
        obs.emit_event("kv_spill", plane="serving",
                       engine=self._obs_name, blocks=len(victims),
                       host_in_use=self._prefix.host_in_use,
                       host_evicted=host_evicted, tp=self.tp)
        self._update_pool_gauge()
        return len(victims)

    def _readmit_chain(self, nodes) -> Optional[List[int]]:
        """Commit a matched prefix chain (ISSUE 16): ref the
        device-resident blocks (pinning them against spill/eviction),
        re-admit the host-tier nodes — fresh device blocks plus ONE
        stacked host→device placement (`.at[idx].set` on concrete
        arrays runs eagerly: placement, not compute — zero new
        executables, the compile-guard pins it) — and return the
        chain's device block ids in order, each holding exactly one
        ref for this request (re-admitted blocks: alloc's ref plus
        mark_cached, mirroring a ref'd device hit). None when the
        pool cannot cover re-admission; the chain unwinds to cached
        parking and the caller requeues."""
        dev = [n.block for n in nodes if n.block is not None]
        self._pool_mgr.ref(dev)
        host_nodes = [n for n in nodes if n.block is None]
        if host_nodes:
            new = self._alloc_blocks(len(host_nodes),
                                     protect=frozenset(nodes))
            if new is None:
                self._pool_mgr.unref(dev)
                return None
            datas = [self._prefix.readmit(nd, b)
                     for nd, b in zip(host_nodes, new)]
            idx = jnp.asarray(new, jnp.int32)
            self.pool = tuple(
                {k: leaf.at[idx].set(jnp.asarray(np.stack(
                    [d[li][k] for d in datas])))
                 for k, leaf in layer.items()}
                for li, layer in enumerate(self.pool))
            # keep the tp head-axis placement through the eager
            # scatter, like import_handoff does
            self.pool = self.model.place_pools(self.pool)
            for b in new:
                self._pool_mgr.mark_cached(b)
            self._bump("kv_readmit_blocks", len(new))
            obs.emit_event("kv_readmit", plane="serving",
                           engine=self._obs_name, blocks=len(new),
                           host_in_use=self._prefix.host_in_use,
                           tp=self.tp)
            self._update_pool_gauge()
        return [n.block for n in nodes]

    def _kv_rows_held(self) -> Dict[str, int]:
        """Rows ONE layer of each cache kind holds for the seated
        slots: "full", the table's assigned blocks; "window", each
        seated slot's ring blocks that its clock has reached (0 for a
        model without rings): never more than slots x ring rows."""
        seated = self._table[:, 0] != 0
        ring = np.minimum(self._pos // self.block_size + 1,
                          self._ring_blocks)[seated].sum()
        return {"window": int(ring) * self.block_size,
                "full": int(np.count_nonzero(self._table))
                * self.block_size}

    def _slot_state_held(self) -> int:
        return self._slot_state_bytes * int(
            np.count_nonzero(self._table[:, 0]))

    def _update_rows_gauge(self) -> None:
        if obs.enabled():
            for kind, rows in self._kv_rows_held().items():
                self._m_rows_gauges[kind].set(rows)
            self._m_state_gauge.set(self._slot_state_held())

    def _update_pool_gauge(self) -> None:
        self._update_rows_gauge()
        if obs.enabled():
            in_use = self._pool_mgr.capacity - self._pool_mgr.free_count
            self._m_pool_gauge.set(in_use)
            # bytes view: per-block footprint straight off the pool
            # leaves, so a bf16/int8 cache reads its true residency
            self._m_pool_bytes_gauge.set(
                in_use * self._kv_bytes_per_token * self.block_size)
            self._m_tier_gauges["device"].set(in_use)
            self._m_tier_gauges["host"].set(self._prefix.host_in_use)
            # re-asserted alongside the pool gauge (not only at
            # construction) so an engine built under BIGDL_OBS=off
            # reports its layout once telemetry is switched on, like
            # every counter series does
            self._m_tp_gauge.set(self.tp)

    def _tenant_kv_blocks(self, tenant: str) -> int:
        """Exclusively-owned pool blocks held by `tenant` across the
        active slots (shared prefix-hit blocks are NOT billed — they
        exist once however many tenants reference them)."""
        return sum(len(self._slot_blocks[i][1])
                   for i, r in enumerate(self._req)
                   if r is not None
                   and getattr(r, "tenant", None) == tenant)

    def _quota_blocked(self, req: Request) -> bool:
        """Whether admitting `req` now would exceed its tenant's KV
        quota (ISSUE 19). Emits one tenant_throttled(action=
        'kv_quota') per request id (not per retry round)."""
        tenant = getattr(req, "tenant", None)
        quota = self.tenant_kv_quotas.get(tenant) \
            if tenant is not None else None
        if quota is None:
            return False
        if self._tenant_kv_blocks(tenant) < quota:
            return False
        if req.id not in self._quota_noted:
            self._quota_noted.add(req.id)
            obs.emit_event("tenant_throttled", plane="serving",
                           tenant=tenant, action="kv_quota",
                           engine=self._obs_name, request=req.id)
        return True

    def _admit(self):
        with self._span("admit") as span:
            # what a recorded `admit` counts: the queue's length on
            # entry, the entries its expiry and its pops examined (the
            # queue keeps the count: the top of the expiry heap a
            # round, the head of a line a pop), the requests it seated
            queued = len(self._queue)
            examined = self._queue.examined
            admitted = 0
            with self._span("queue_expire", cat=_ADMIT_PARTS) as part:
                self._expire_queued(self._clock())
                if part.id is not None:
                    part.set(queued=queued,
                             expired=queued - len(self._queue))
            # quota-exceeded requests are set ASIDE and restored to the
            # queue front afterwards (order preserved) — a blocked tenant
            # must never head-of-line-block the other tenants' admissions
            quota_skipped: List[Request] = []
            try:
                for slot in self._free_slots():
                    while self._queue:
                        with self._span("queue_pop",
                                        cat=_ADMIT_PARTS) as part:
                            if part.id is not None:
                                part.set(queued=len(self._queue))
                            req = self._queue.pop_next()
                            blocked = self._quota_blocked(req)
                            if part.id is not None:
                                part.set(request=req.id)
                        if blocked:
                            quota_skipped.append(req)
                            continue
                        if self._admit_into(slot, req):
                            admitted += 1
                            self._admit_fails.pop(req.id, None)
                            self._quota_noted.discard(req.id)
                            break
                        # pool pressure: every evictable/spillable prefix
                        # block is gone and the free list still cannot
                        # cover the suffix. Requeue at the FRONT of the
                        # line (its precedence is preserved) — BOUNDED
                        # (ISSUE 16 bugfix): a pool that never frees
                        # (nothing in flight to release blocks) would
                        # otherwise spin the request through the queue
                        # forever with no terminal and no counter
                        fails = self._admit_fails.pop(req.id, 0) + 1
                        if fails > self.admit_requeue_budget:
                            self._bump("admit_requeue_exhausted")
                            self._terminal(req, "pool_exhausted", "done")
                            continue          # try the next queued request
                        self._admit_fails[req.id] = fails
                        self._queue.appendleft(req,
                                               self._expires_at(req))
                        return
                    if not self._queue:
                        return
            finally:
                for r in reversed(quota_skipped):
                    self._queue.appendleft(r, self._expires_at(r))
                if span.id is not None:
                    span.set(queued=queued, admitted=admitted,
                             scanned=self._queue.examined - examined)

    def _point_table_row(self, slot: int, hit: List[int],
                         new: List[int]) -> np.ndarray:
        """Zero one slot's block-table row and point it at the shared
        `hit` chain followed by the exclusive `new` blocks — the host
        row both seat paths hand to the jitted steps."""
        row = self._table[slot]
        row[:] = 0
        row[:len(hit)] = hit
        row[len(hit):len(hit) + len(new)] = new
        return row

    def _seat_slot(self, slot: int, req: Request, hit: List[int],
                   new: List[int]) -> None:
        """Seat-slot tail shared by `_admit_into` and `import_handoff`
        (PR 10's deferred cleanup — previously ~40 mirrored lines):
        register the prompt's pre-COW-cap blocks in the radix tree
        (their content is valid — the prefill/scatter this seat
        follows is already dispatched, and device program order covers
        any later reader), then point every per-slot host array at the
        request so the next decode step picks it up at clock
        len(prompt)-1. Both callers stay pinned by the bitwise tests
        (test_kv_pool, test_tp_serving, the serve_prefix drill)."""
        prompt = list(req.prompt)
        n = len(prompt)
        if self.prefix_cache_enabled:
            # the prompt's full pre-COW-cap blocks become cacheable the
            # moment their content lands; the already-present hit chain
            # is skipped by insert()
            cap_blocks = (n - 1) // self.block_size
            if cap_blocks:
                owned = self._prefix.insert(
                    prompt,
                    [int(x) for x in self._table[slot, :cap_blocks]])
                for bid in owned:
                    self._pool_mgr.mark_cached(bid)
        self._req[slot] = req
        self._gen[slot] = []
        self._slot_blocks[slot] = [list(hit), list(new)]
        self._pos[slot] = n - 1         # re-decode last prompt token
        self._tok[slot] = prompt[-1]
        self._nout[slot] = 0
        self._seed[slot] = req.seed
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._topp[slot] = req.top_p

    def _prepare_seat(self, slot: int, req: Request, span):
        """What an admission does on the host before its prefill is
        launched: prefix lookup, block allocation, the slot's table
        row, the padded suffix and the prefill's destinations, all
        host arrays that `_prefill_step`'s call places. Returns what
        `_admit_into` hands to the prefill and the seating, or None =
        insufficient pool blocks. `span` is the `seat_prepare` span
        around the call: a seating that went through leaves its counts
        on it."""
        prompt = list(req.prompt)
        n = len(prompt)
        bs = self.block_size
        nodes: List[object] = []
        start = 0
        if self.prefix_cache_enabled:
            # COW cap: reuse at most the full blocks strictly before
            # the re-decoded last prompt token (ops/kv_cache.py).
            # Tier-aware (ISSUE 16): the matched chain may hold
            # host-tier nodes, which _readmit_chain re-admits below
            nodes = self._prefix.lookup_nodes(prompt, (n - 1) // bs)
            start = len(nodes) * bs
            # feasibility trim: the suffix bucket must fit the table
            while nodes and start + bucket_for(n - start,
                                               self.buckets) \
                    > self.cache_len:
                nodes.pop()
                start -= bs
        suffix = prompt[start:]
        b = bucket_for(len(suffix), self.buckets)
        nb_new = -(-b // bs)                  # blocks the suffix covers
        # pin the hit chain BEFORE allocating: the allocator's LRU
        # spill/eviction must never reclaim the very blocks this
        # admission just matched (a refcount-0 cached block is fair
        # game to it) — re-admitting any host-tier links on the way
        hit = self._readmit_chain(nodes)
        if hit is None:
            return None
        new = self._alloc_blocks(nb_new)
        if new is None:
            self._pool_mgr.unref(hit)         # back to cached parking
            return None
        # the prefill's host operands stay NumPy, int32 made on the
        # host: `_prefill_step`'s call places them (as the decode
        # round's, `_dispatch_and_fetch`), and `jnp.asarray(list,
        # dtype=)` would launch a jit(convert_element_type) besides.
        # The row is a COPY: the call may read a NumPy operand after
        # it returns (the CPU backend does), and `_ensure_blocks` can
        # extend this slot's row of `_table` before the round's fetch
        # fences it
        row = self._point_table_row(slot, hit, new)[None, :].copy()
        toks = pad_tokens(suffix, b)[None, :]          # (1, bucket)
        block_ids = np.asarray(new, np.int32)
        placed_bytes = block_ids.nbytes
        if self._ring_blocks:
            # a model with rings takes its destinations by cache kind:
            # the fresh table blocks, and for the slot's rings the
            # prompt's block that each ring block takes
            sources = ring_prompt_sources(n, bs, self._ring_blocks)
            placed_bytes += sources.nbytes
            block_ids = {"table": block_ids, "ring": {
                "slot": np.int32(slot), "sources": sources}}
        elif self._slot_state_bytes:
            # a model with a state: the slot, and the position whose
            # rows it keeps, the last before the token that the first
            # decode step re-decodes (-1: a prompt of one token)
            block_ids = {"table": block_ids, "state": {
                "slot": np.int32(slot), "keep": np.int32(n - 2)}}
        if span.id is not None:
            span.set(prefix_tokens=int(start), new_blocks=len(new),
                     placed_bytes=int(placed_bytes))
        return start, hit, new, row, toks, b, block_ids

    def _admit_into(self, slot: int, req: Request) -> bool:
        """Prefix lookup + block allocation + suffix prefill into
        `slot`. False = insufficient pool blocks (caller requeues)."""
        with self._span("seat_prepare", cat=_ADMIT_PARTS) as part:
            before = self._eviction_counts() \
                if part.id is not None else None
            seat = self._prepare_seat(slot, req, part)
            if before is not None:
                # a failed seating says these all the same: what it
                # evicted before it gave up is gone from the cache
                part.set(request=req.id,
                         **self._eviction_counts(before))
        if seat is None:
            return False
        start, hit, new, row, toks, b, block_ids = seat
        n = len(req.prompt)
        tracer = obs.get_tracer()
        t_admit = self._clock()
        if tracer.enabled:
            # the queued phase closes when the slot is granted
            t_sub = self._meta.get(req.id, {}).get("t", t_admit)
            tracer.complete("queued", "serving", t_sub, t_admit,
                            args={"request": req.id, "slot": slot})
        with self._span("prefill") as span:
            with warnings.catch_warnings():
                # donation is a per-call no-op warning on CPU
                # backends; on TPU it aliases the pool update in place
                warnings.filterwarnings(
                    "ignore", message=".*[Dd]onat",
                    category=UserWarning)
                self.pool = _prefill_step(
                    self.model, self._params, self.pool,
                    toks, np.int32(start), block_ids, row)
            if span.id is not None:
                # THE one span that waits for the device, and only
                # while it is being recorded (obs/spans.py): unfenced
                # it times the dispatch and the prefill program lands
                # in the next decode_step. Tracer off: never reached.
                # `launched_s` is read before the wait: the call,
                # which places its host operands and which an untraced
                # admission pays too
                launched_s = span.elapsed()
                jax.block_until_ready(self.pool)  # graftlint: disable=hidden-device-sync
                span.set(request=req.id, slot=slot, bucket=int(b),
                         prefix_tokens=int(start), fenced=True,
                         launched_s=launched_s,
                         **self._expert_matmul(int(b)),
                         **self.model.prefill_span_args(int(b)))
        with self._span("seat_commit", cat=_ADMIT_PARTS) as part:
            if part.id is not None:
                part.set(request=req.id)
            self._bump("prefill_calls")
            if self._scan_chunks[b] and obs.enabled():
                self._m_scan_chunks.inc(self._scan_chunks[b])
            if start:
                self._bump("prefix_hits")
                self._bump("prefix_blocks_reused", len(hit))
                self._bump("prefix_tokens_saved", start)
                self._bump("prefix_bytes_saved",
                           start * self._kv_bytes_per_token)
                obs.emit_event("prefix_hit", plane="serving",
                               engine=self._obs_name, request=req.id,
                               matched_tokens=start, blocks=len(hit),
                               prompt_len=n, **self._trace_fields(req))
            self._update_pool_gauge()
            self._seat_slot(slot, req, hit, new)
            if self._round_log is not None:
                self._round_log["admitted"].append(req.id)
            return True

    def _eviction_counts(self, before: Optional[dict] = None) -> dict:
        """`evicted_blocks` and `evict_visits` (the LRU index's entries
        examined to choose them) since `before`, an earlier reading of
        this: what `seat_prepare` and `ensure_blocks` say of the
        evictions their allocations made."""
        now = {"evicted_blocks": self._stats["pool_evictions"],
               "evict_visits": self._stats["pool_eviction_visits"]}
        return now if before is None else {
            k: v - before[k] for k, v in now.items()}

    def _expert_matmul(self, tokens: int) -> dict:
        """`expert_matmul`: the form the model's grouped expert matmuls
        take in a program of `tokens` rows, "stream" or "ragged_dot"
        (ops/grouped_matmul.grouped_matmul_form: chosen from the
        platform and the static shapes, so a label like `attn_form`),
        for `health()` and the `decode_step` and `prefill` spans.
        Nothing for a model without experts."""
        form = self.model.expert_matmul_form(self._params, tokens)
        return {} if form is None else {"expert_matmul": form}

    def _finish(self, slot: int, reason: str,
                status: str = "done") -> GenerationResult:
        req = self._req[slot]
        ttft, latency = self._lifecycle_times(req)
        res = GenerationResult(req.id, list(req.prompt),
                               self._gen[slot], reason, status,
                               ttft_s=ttft, latency_s=latency)
        self._observe_terminal(req, reason, status,
                               len(self._gen[slot]), ttft, latency)
        self._meta.pop(req.id, None)
        self._clear_slot(slot, poisoned=(status == "poisoned"))
        self._bump(_STATUS_COUNTER[status])
        return res

    def _clear_slot(self, slot: int, poisoned: bool = False) -> None:
        """Release one slot's per-slot state and blocks with ZERO
        request-lifecycle side effects — the shared tail of _finish
        and the SpeculativeEngine's shadow-mirror release (ISSUE 15;
        quiesce's per-slot sibling: a mirror is not a request, so its
        teardown must never emit a terminal or bump a status
        counter). Keeps the slot-release field list in exactly one
        place."""
        self._req[slot] = None
        self._gen[slot] = []
        self._temp[slot] = 0.0
        self._release_slot(slot, poisoned=poisoned)

    def _release_slot(self, slot: int, poisoned: bool = False) -> None:
        """Return a finished slot's blocks: shared prefix refs drop
        (refcount-0 tree blocks park as cached, reusable); exclusive
        blocks free. A POISONED request's freed exclusive blocks are
        scrubbed to zero on device — and its exclusive tree leaves
        forgotten first — but a SHARED (refcount > 1) block is never
        scrubbed or forgotten: live co-users hold content that is
        bit-identical to what they would have computed cold (the
        serve_prefix drill pins exactly this)."""
        hit, own = self._slot_blocks[slot]
        pool = self._pool_mgr
        freed = pool.unref(hit)
        # deep-to-shallow: forget_block removes LEAVES only, so the
        # exclusive chain must be forgotten from its deepest block up
        # (each removal turns the parent into a leaf) — shallow-first
        # would strand every interior block as reusable cached content
        # a later same-prefix request could hit
        for b in reversed(own):
            if poisoned and pool.in_tree(b) and pool.refcount(b) == 1:
                self._prefix.forget_block(b)
            freed += pool.unref([b])
        if poisoned and freed:
            self._scrub_blocks(freed)
        if poisoned and self._slot_state_bytes \
                and not self._cache_consumed():
            # the scrub of what it wrote outside table blocks. A sound
            # request's row stays: the next prefill rewrites the whole
            # of it (zeros for a prompt of one token) and a decode step
            # rewrites seated slots only, so no result ever reads it
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message=".*[Dd]onat", category=UserWarning)
                self.pool = _clear_slot_state(
                    self._cache_kinds, self.pool, np.int32(slot))
        self._slot_blocks[slot] = [[], []]
        self._table[slot, :] = 0
        self._update_pool_gauge()

    def _scrub_blocks(self, blocks: List[int]) -> None:
        """Zero freed pool blocks a poisoned request wrote. The next
        occupant overwrites every position it can see and
        block_attention zeroes invisible value rows — this scrub is
        the belt to that suspenders, keeping the invariant local:
        nothing a poisoned request wrote survives its eviction (except
        inside a shared block, whose content is by construction the
        same bits a healthy cold run computes). `blocks` are TABLE
        blocks. A ring leaf has none and is left alone: its slot reads
        nothing until it is seated again, and the next occupant's
        prefill rewrites every block of the slot's region
        (ops/kv_cache.write_prompt_ring). A state leaf has none
        either: `_release_slot` zeroes the poisoned slot's row."""
        idx = jnp.asarray(blocks, jnp.int32)
        self.pool = tuple(
            jax.tree_util.tree_map(
                lambda leaf: leaf.at[idx].set(jnp.zeros((), leaf.dtype)),
                layer) if kind == "table" else layer
            for kind, layer in zip(self._cache_kinds, self.pool))
        # keep the tp head-axis placement through the eager scrub
        self.pool = self.model.place_pools(self.pool)

    def _cache_consumed(self) -> bool:
        """True if any pool leaf's buffer was donated/deleted by a
        failed dispatch — such a step is NOT retryable (the input no
        longer exists); only failures raised before execution
        consumed the buffers are."""
        return any(getattr(leaf, "is_deleted", lambda: False)()
                   for leaf in jax.tree_util.tree_leaves(self.pool))

    def quiesce(self, reason: str, watchdog: bool = False) -> None:
        """Degrade WITHOUT touching any request lifecycle — the
        wrapper hook (ISSUE 15). A SpeculativeEngine owns its requests
        through its TARGET engine; when the DRAFT engine's dispatch
        trips the watchdog, the draft must refuse further work,
        surface 'degraded' health and emit engine_degraded for the
        fleet/flight-recorder plane — but its seated rows are shadow
        mirrors, not requests, so _degrade()'s fail-everything path
        would emit terminal events for requests that live (and keep
        decoding, target-only) elsewhere. Idempotent."""
        if self._degraded:
            return
        if watchdog:
            self._bump("watchdog_trips")
        self._degraded = reason
        logger.error("serving engine quiesced: %s", reason)
        obs.emit_event("engine_degraded", plane="serving",
                       engine=self._obs_name, reason=reason)

    def _emit_multi(self, slot: int, tokens: List[int],
                    finites: List[bool], now: float
                    ) -> List[GenerationResult]:
        """Apply one scheduling round's sampled tokens to `slot` —
        ONE code path for the classic single-token step and the
        speculative multi-token round (ISSUE 15). Per token, in the
        exact order the single-token step always used: advance the
        sampling-stream clock, evict on a non-finite logits row
        (status 'poisoned', earlier tokens kept), finish on a stop id
        (the stop token is not emitted), append + TTFT-stamp, then
        max_tokens / deadline / cache_full checks, else advance the
        row clock so the token's successor is decoded next. A
        terminal mid-list discards the remaining tokens — exactly
        what a single-token engine would never have sampled. All
        tokens share this round's `now` (a speculative round emits
        several tokens in one step, so TTL expiry is checked once per
        round rather than once per token — the conservative direction
        is unchanged: expiry can only fire earlier in wall time,
        never later, than the equivalent single-token rounds)."""
        done: List[GenerationResult] = []
        req = self._req[slot]
        for tok, fin in zip(tokens, finites):
            self._nout[slot] += 1
            if not fin:
                # eviction scrubs the poisoned request's freed
                # exclusive blocks (never a shared one) — _release_slot
                done.append(self._finish(slot, "poisoned", "poisoned"))
                return done
            if tok in req.stop_ids:
                done.append(self._finish(slot, "stop_id"))
                return done
            self._gen[slot].append(tok)
            if self._round_log is not None:
                # the per-token stamp of a traced round: the request's
                # id once per token, under the round's one `now`
                self._round_log["emitted"].append(req.id)
            if len(self._gen[slot]) == 1 and req.id in self._meta:
                meta = self._meta[req.id]
                meta["t_first"] = now                 # TTFT stamp
                if self._round_log is not None:
                    obs.get_tracer().instant(
                        "first_token", "serving", ts=now,
                        args={"request": req.id,
                              "ttft_s": now - meta["t"]})
            if len(self._gen[slot]) >= req.max_new_tokens:
                done.append(self._finish(slot, "max_tokens"))
                return done
            elif now >= self._deadline_at(req):
                done.append(self._finish(slot, "expired", "expired"))
                return done
            elif self._pos[slot] + 1 >= self.cache_len:
                done.append(self._finish(slot, "cache_full"))
                return done
            else:
                self._pos[slot] += 1
                self._tok[slot] = tok
        return done

    def _degrade(self, reason: str) -> List[GenerationResult]:
        """Quiesce: fail every in-flight and queued request, refuse new
        submissions. Returns the failed in-flight/queued results (they
        are also recorded in `completed` by run(); queued failures go
        straight to `completed`)."""
        self._degraded = reason
        logger.error("serving engine degraded: %s", reason)
        obs.emit_event("engine_degraded", plane="serving",
                       engine=self._obs_name, reason=reason)
        out = [self._finish(i, "failed", "failed")
               for i, r in enumerate(self._req) if r is not None]
        for r in list(self._queue):
            out.append(self._terminal(r, "failed", "failed"))
        self._queue.clear()
        return out

    def _dispatch_and_fetch(self, poison: np.ndarray, slow_s: float,
                            watchdog: bool = True
                            ) -> Tuple[np.ndarray, np.ndarray]:
        """One decode dispatch + device→host fetch, optionally under
        the watchdog budget. The fetch runs INSIDE the budget: the
        failure mode guarded against is the device call blocking, not
        erroring, and only a wall-clock bound converts that hang into
        a typed StepTimeout. A daemon thread suffices here because
        steady-state PJRT dispatch/fetch releases the GIL while it
        waits (see _watchdog_call)."""
        # the closure may run on the watchdog's thread, where no span
        # is open: hand the enclosing span (decode_step) over
        parent = obs.get_tracer().current()

        def work():
            if slow_s:
                time.sleep(slow_s)    # injected straggler/hang model
            if self._degraded is not None:
                # the watchdog already tripped while this (now
                # abandoned) thread was stuck pre-dispatch: do NOT
                # launch device work nobody will consume — a late
                # dispatch can still be executing at interpreter
                # shutdown and aborts the process (observed with the
                # paged decode's pool gather). A hang INSIDE the real
                # dispatch is beyond this guard — that is the failure
                # mode the watchdog exists to convert.
                return None
            # the nine host operands go into the call as the NumPy
            # arrays they are: its own argument path places one for
            # 0.11 ms on the chip's host, a `jnp.asarray` each costs
            # 0.25 whatever the bytes (PERF.md §6, PR 41). The call may
            # still read them after it returns (the CPU backend does):
            # nothing writes them before the fetch below has fenced
            # the step. `upload` keeps its place and its `bytes` for
            # the span tree's readers; the transfer is in `dispatch`
            host = (self._tok, self._pos, self._seed, self._nout,
                    self._temp, self._topk, self._topp, poison,
                    self._table)
            with self._span("upload", parent) as span:
                if span.id is not None:
                    span.set(bytes=sum(a.nbytes for a in host))
            with self._span("dispatch", parent), \
                    warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message=".*[Dd]onat", category=UserWarning)
                nxt, finite, pools, aux = _decode_step(
                    self.model, self._params, self.pool, *host)
            # THE one deliberate per-step device→host fetch: the host
            # needs the token, so the fetch doubles as the fence for
            # the decode dispatch, inside the watchdog budget above.
            # The model's `aux` comes after it, and only while the
            # step is being recorded (the step has ended: no wait)
            with self._span("fetch", parent) as span:
                nxt, finite = np.asarray(nxt), np.asarray(finite)  # graftlint: disable=hidden-device-sync
                if aux and span.id is not None:
                    aux = jax.device_get(aux[0])  # graftlint: disable=hidden-device-sync
                else:
                    aux = None
                return nxt, finite, pools, aux

        nxt, finite, pools, aux = _watchdog_call(
            work, self.step_timeout_s if watchdog else None)
        self.pool = pools
        self._aux = aux
        return nxt, finite

    def _report_aux(self, span) -> None:
        """What a recorded step's `aux` says, in the model's words
        (`decode_aux_report`), onto the `decode_step` span. Nothing of
        it goes into a counter: the aux is fetched only while the
        tracer records, and a counter that counts only then reads 0 on
        a production engine's scrape."""
        aux, self._aux = self._aux, None
        if aux is not None:
            span.set(**self.model.decode_aux_report(aux))

    def _ensure_blocks(self, horizons=None, exhaust: str = "finish"
                       ) -> Optional[List[GenerationResult]]:
        """Pre-dispatch block growth: a row whose next write position
        crossed into an uncovered block gets a fresh one appended to
        its table (copy-on-write — generated tokens never extend into
        a shared block). If the pool cannot supply one even after LRU
        eviction, the request finishes 'pool_exhausted' (status done,
        partial tokens kept — the block-pool sibling of cache_full).
        With the default pool sizing this cannot happen: worst-case
        zero-sharing demand is exactly slots * blocks_per_slot.

        `horizons` (ISSUE 15, speculative decoding): optional per-slot
        int lookahead — the table must also cover positions
        pos..pos+horizon, so a verify round's k+1 position-rows (and
        the draft chain's writes) land in owned blocks. Default (None)
        is the classic single-position behavior.

        `exhaust='abort'` (the speculative wrapper's DRAFT mode):
        exhaustion returns None instead of finishing the slot — a
        shadow mirror must never emit a request_terminal (the quiesce
        contract); blocks already granted stay registered on their
        slots and release with them."""
        with self._span("ensure_blocks") as span:
            before = self._eviction_counts() \
                if span.id is not None else None
            try:
                done: List[GenerationResult] = []
                for i, req in enumerate(self._req):
                    if req is None:
                        continue
                    h = 0 if horizons is None else int(horizons[i])
                    lo = int(self._pos[i]) // self.block_size
                    hi = (int(self._pos[i]) + h) // self.block_size
                    for bi in range(lo, hi + 1):
                        if self._table[i, bi] != 0:
                            continue
                        new = self._alloc_blocks(1)
                        if new is None:
                            if exhaust == "abort":
                                return None
                            done.append(
                                self._finish(i, "pool_exhausted"))
                            break
                        self._table[i, bi] = new[0]
                        self._slot_blocks[i][1].append(new[0])
                return done
            finally:
                if before is not None:
                    span.set(**self._eviction_counts(before))

    def rollback_slot(self, slot: int) -> int:
        """Cache rollback hook (ISSUE 15): detach and free the slot's
        exclusive table blocks strictly beyond the block containing the
        next write position (`_pos[slot]`). A pure block-TABLE/length
        edit, never a scrub: a rejected draft suffix's k/v sit at
        positions beyond the row clock in EXCLUSIVE blocks (the PR-8
        COW cap keeps every decode-era write out of shared blocks), so
        they are masked on read and overwritten in place — only whole
        lookahead blocks past the current block are returned to the
        pool here, restoring the engine-wide invariant that a table
        never extends beyond its clock's block between rounds. Entries
        past the clock's block are exclusively owned by construction
        (the shared hit chain ends at the COW cap, which the clock has
        already passed). Returns the number of blocks freed."""
        bi = int(self._pos[slot]) // self.block_size
        row = self._table[slot]
        own = self._slot_blocks[slot][1]
        freed = 0
        for j in range(bi + 1, row.shape[0]):
            b = int(row[j])
            if not b:
                continue
            own.remove(b)
            self._pool_mgr.unref([b])
            row[j] = 0
            freed += 1
        if freed:
            self._update_pool_gauge()
        return freed

    # -------------------------------------------- disaggregated prefill
    def _step_prefill(self) -> List[GenerationResult]:
        """Prefill-tier scheduling round (role='prefill'): admit +
        prefill exactly like a serving engine — same buckets, same
        radix prefix reuse, same executables — then export every
        filled slot as a HandoffPackage instead of decoding. The slot
        frees immediately, so one prefill engine pipelines a stream of
        long prompts without ever holding decode capacity; queued-TTL
        expiries (inside _admit) settle into `completed` as usual."""
        self._admit()
        for i, req in enumerate(self._req):
            if req is not None:
                self._export_handoff(i)
        return []

    def _export_handoff(self, slot: int) -> HandoffPackage:
        """Package one prefilled slot for disaggregated decode: fetch
        the prompt's KV block contents to host — ONE deliberate
        device→host transfer per REQUEST (the disaggregation boundary;
        priced like a prefill, never per-token) — then free the slot.
        The exported arrays are GLOBAL values, so the package imports
        into any sharding layout (tp included) bit-identically; the
        freed blocks park in this engine's radix tree, so repeated
        long prompts amortize their prefill here too."""
        req = self._req[slot]
        n = len(req.prompt)
        nb = -(-n // self.block_size)           # blocks covering [0, n)
        idx = jnp.asarray([int(b) for b in self._table[slot, :nb]],
                          jnp.int32)
        kv = jax.device_get(tuple(                                   # graftlint: disable=hidden-device-sync — the one deliberate handoff fetch, once per request (the disaggregation boundary), never per token or per layer: device_get over the whole indexed tree batches all layers into a single transfer
            {k: leaf[idx] for k, leaf in layer.items()}
            for layer in self.pool))
        meta = self._meta.pop(req.id, None)
        pkg = HandoffPackage(req, kv,
                             meta["t"] if meta else self._clock(),
                             self._obs_name)
        self._req[slot] = None
        self._gen[slot] = []
        self._temp[slot] = 0.0
        self._release_slot(slot)
        self._handoffs.append(pkg)
        self._bump("handoffs_out")
        obs.emit_event("handoff_export", plane="serving",
                       engine=self._obs_name, request=req.id,
                       prompt_len=n, blocks=nb,
                       **self._trace_fields(req))
        return pkg

    def take_handoffs(self) -> List[HandoffPackage]:
        """Drain the packages a prefill-role engine exported (the
        router's harvest point; empty on serving-role engines)."""
        out, self._handoffs = self._handoffs, []
        return out

    # ------------------------------------- fleet-scale KV plane (ISSUE 16)
    def prefix_match_tokens(self, prompt: Sequence[int]) -> int:
        """Router affinity probe: prompt tokens this engine's radix
        tree already holds (EITHER tier, COW cap applied), WITHOUT
        touching LRU stamps — probing every pool engine must not
        perturb anyone's eviction order. Pure host bookkeeping."""
        n = len(prompt)
        if not self.prefix_cache_enabled or n == 0:
            return 0
        return self._prefix.peek_blocks(
            prompt, (n - 1) // self.block_size) * self.block_size

    def export_tree(self) -> List[Dict[str, object]]:
        """Export this engine's radix tree as host-side entries for
        warm-state migration (ISSUE 16): one entry per tree node —
        the full prefix tokens from the root plus the block's bytes
        in the HandoffPackage per-layer {'k','v'} layout (one
        (block_size, H*D) row per array; fp32 reference layout).
        Device-resident blocks are fetched in ONE batched transfer;
        host-tier blocks are already bytes. Parents precede children,
        so a survivor can import_tree() the list in order. Safe on a
        degraded engine (the migration trigger) — tree content is
        immutable once inserted; returns [] when the pool buffers
        were consumed by a failed donated dispatch."""
        entries = self._prefix.export_entries()
        if not entries:
            return []
        dev = [(toks, node) for toks, node in entries
               if node.block is not None]
        if dev and self._cache_consumed():
            # the device bytes died with the donated dispatch, but
            # host-tier nodes are plain RAM: salvage the chains whose
            # ENTIRE ancestry is host-resident (a child below a lost
            # device block has no graftable parent)
            ok: set = set()
            keep = []
            for toks, node in entries:     # preorder: parents first
                if node.block is not None:
                    continue
                parent = node.parent
                if parent.parent is not None and id(parent) not in ok:
                    continue
                ok.add(id(node))
                keep.append((toks, node))
            entries, dev = keep, []
            if not entries:
                return []
        data = None
        if dev:
            idx = jnp.asarray([node.block for _, node in dev],
                              jnp.int32)
            data = jax.device_get(tuple(                             # graftlint: disable=hidden-device-sync — THE deliberate migration fetch (ISSUE 16): one batched device→host transfer per tree export covering every exported block across all layers (the handoff-export idiom) — runs once per engine degradation/drain, never on a serving hot path
                {k: leaf[idx] for k, leaf in layer.items()}
                for layer in self.pool))
        pos = {id(node): j for j, (_, node) in enumerate(dev)}
        out: List[Dict[str, object]] = []
        for toks, node in entries:
            if node.block is None:
                kv = node.host
            else:
                j = pos[id(node)]
                kv = tuple({k: layer[k][j] for k in layer}
                           for layer in data)
            out.append({"tokens": list(toks), "kv": kv})
        return out

    def import_tree(self, entries: Sequence[Dict[str, object]]
                    ) -> int:
        """Seed migrated chains into THIS engine's HOST tier
        (ISSUE 16): pure placement into host RAM — zero device work,
        zero compute, zero new executables; grafted blocks re-admit
        on their first prefix hit like any spilled block. Requires
        the spill tier (`spill=True`); incumbents win, host capacity
        applies (LRU childless host nodes make room). Returns the
        number of blocks grafted."""
        if not self.spill_enabled or not entries:
            return 0
        ref = _first_leaf(self.pool)
        for e in entries:
            kv = e["kv"]
            got = _first_leaf(kv)
            if len(kv) != len(self.pool) \
                    or tuple(got.shape) != tuple(ref.shape[1:]) \
                    or got.dtype != ref.dtype:
                raise ValueError(
                    f"migrated tree entry layout {len(kv)} layers x "
                    f"{tuple(got.shape)} ({got.dtype}) "
                    f"does not match this engine's {len(self.pool)} "
                    f"layers x {tuple(ref.shape[1:])} ({ref.dtype}) — "
                    "migration requires a same-layout fleet")
        grafted = 0
        for e in sorted(entries, key=lambda e: len(e["tokens"])):
            if self._prefix.graft_host(e["tokens"], e["kv"]):
                grafted += 1
        if grafted:
            self._update_pool_gauge()
        return grafted

    def import_handoff(self, pkg: HandoffPackage) -> bool:
        """Seat a prefilled package directly into a slot, skipping
        prefill: allocate exclusive blocks (LRU-evicting cached
        prefixes under pressure), scatter the imported contents into
        the pool, point the slot's table row at them, and enter the
        decode loop at clock len(prompt)-1 — the first decode step
        re-decodes the last prompt token exactly as a locally
        prefilled request would, and the tokens come out BIT-IDENTICAL
        (the contents are bitwise what local prefill writes — the
        full-extent-reduction discipline, ops/kv_cache.py). False when
        no free slot or insufficient blocks (the caller retries next
        round). The prompt's pre-COW-cap blocks register in the radix
        tree, so a handed-off prompt seeds prefix reuse here too."""
        if self.role == "prefill":
            raise ValueError("import_handoff on a prefill-role engine")
        if self._ring_blocks or self._slot_state_bytes:
            raise NotImplementedError(
                "import_handoff into an engine whose model keeps ring "
                "or state leaves: a package carries table blocks, and "
                f"{self.model.kept_outside_blocks()}")
        if self._degraded:
            raise EngineDegraded(
                f"engine degraded ({self._degraded}); hand off to a "
                "healthy engine")
        if self._draining:
            raise EngineDraining(
                "engine is draining (stop-admission): hand off to "
                "another engine in the pool")
        req = pkg.request
        if self._in_flight(req.id):
            raise ValueError(f"request id {req.id} already in flight "
                             "or completed-unclaimed")
        got, ref = _first_leaf(pkg.kv), _first_leaf(self.pool)
        pkg_bs = int(got.shape[1])
        if len(pkg.kv) != len(self.pool) \
                or pkg_bs != self.block_size \
                or got.shape[1:] != ref.shape[1:] \
                or got.dtype != ref.dtype:
            # config error, not transient pressure: a mismatched fleet
            # (different block_size/model/cache dtype) can never seat
            # this package — a silent dtype cast in particular would
            # break the handoff bit-identity contract, not just crash
            raise ValueError(
                f"handoff package layout {len(pkg.kv)} layers x "
                f"{tuple(got.shape[1:])} (block_size "
                f"{pkg_bs}, {got.dtype}) does not match "
                f"this engine's {len(self.pool)} layers x "
                f"{tuple(ref.shape[1:])} (block_size "
                f"{self.block_size}, {ref.dtype}) — "
                "prefill and decode tiers must share model, "
                "block_size and cache_dtype")
        free = self._free_slots()
        if not free:
            return False
        prompt = list(req.prompt)
        n = len(prompt)
        nb = int(got.shape[0])
        if nb > self._table.shape[1]:
            # prompt spans more blocks than one slot's table row can
            # hold here (importer has a shorter max_len) — the backlog
            # retries and run()'s stuck-backlog guard names the cause
            return False
        bs = self.block_size
        nodes: List[object] = []
        if self.prefix_cache_enabled:
            # same lookup + COW cap as _admit_into: blocks the
            # importer already caches for this prefix are REUSED, not
            # re-scattered — their content is bitwise the package's
            # content for the same tokens (warm == cold), and without
            # this the allocator would evict the cached chain to make
            # room for its own duplicate under pool pressure.
            # Tier-aware (ISSUE 16): a spilled chain re-admits here
            # exactly like at a direct admission
            nodes = self._prefix.lookup_nodes(prompt, (n - 1) // bs)
        nh = len(nodes)
        # pin the hit chain BEFORE allocating (the _admit_into rule:
        # LRU spill/eviction must never eat the chain this import
        # matched), re-admitting any host-tier links on the way
        hit = self._readmit_chain(nodes)
        if hit is None:
            return False
        new = self._alloc_blocks(nb - nh)
        if new is None:
            self._pool_mgr.unref(hit)     # back to cached parking
            return False
        slot = free[0]
        if req.trace_id is not None:
            # the request moved across the disaggregation boundary:
            # the seat here opens a new journey hop (obs/journey.py)
            req.hop += 1
        idx = jnp.asarray(new, jnp.int32)
        self.pool = tuple(
            {k: leaf.at[idx].set(jnp.asarray(pkg.kv[li][k][nh:]))
             for k, leaf in layer.items()}
            for li, layer in enumerate(self.pool))
        # host-side scatter may drop the tp head-axis placement —
        # re-commit so the jitted steps keep their shardings
        self.pool = self.model.place_pools(self.pool)
        self._point_table_row(slot, hit, new)
        self._seat_slot(slot, req, hit, new)
        self._meta[req.id] = {"t": pkg.submit_t}
        if nh:
            # hits/blocks count like any admission; tokens/bytes-saved
            # stay prefill-side metrics — this import skipped a
            # SCATTER, the prefill itself already ran on the exporter
            self._bump("prefix_hits")
            self._bump("prefix_blocks_reused", nh)
            obs.emit_event("prefix_hit", plane="serving",
                           engine=self._obs_name, request=req.id,
                           matched_tokens=nh * bs, blocks=nh,
                           prompt_len=n, **self._trace_fields(req))
        self._update_pool_gauge()
        self._bump("handoffs_in")
        obs.emit_event("handoff_import", plane="serving",
                       engine=self._obs_name, request=req.id,
                       prompt_len=n, blocks=nb, source=pkg.source,
                       tp=self.tp, role=self.role,
                       **self._trace_fields(req))
        return True

    def step(self) -> List[GenerationResult]:
        """Admit queued requests into free slots, run ONE decode step
        over all slots, evict finished/poisoned/expired sequences.
        Returns the requests that reached a terminal state this step.
        A watchdog trip or exhausted retry budget degrades the engine
        and returns every in-flight/queued request as 'failed'."""
        if self._degraded:
            return []
        if self.role == "prefill":
            return self._step_prefill()
        # one span tree per scheduling round (obs/spans.py; PERF.md §3
        # has the table): round > admit > prefill, ensure_blocks,
        # decode_step > upload / dispatch / fetch, emit. That tree is
        # category "serving" and CLOSED: the benchmark's readers take
        # that category and sum self times by a fixed tuple of names,
        # so a new child of `admit` in it would silently leave
        # *_round_host_share. What an admission is made of
        # (queue_expire, queue_pop, seat_prepare, seat_commit: leaves
        # under `admit`, beside `prefill`) is category _ADMIT_PARTS,
        # seen by who asks for every category and by the idle-gap
        # attribution
        with self._span("round") as span:
            if span.id is None:
                return self._round()
            log = self._round_log = {"admitted": [], "emitted": []}
            try:
                return self._round()
            finally:
                self._round_log = None
                # attn_impl: a constant, read by benchmarks/harness/
                # span_tree.py's `span tree:` print (ROADMAP A0)
                span.set(attn_impl="xla", attn_form=self.attn_form,
                         **log)

    def _decode_read(self) -> Tuple[str, int]:
        """The share of the table a decode step's read is compiled for
        at the slots' clocks, by name, and the blocks it gathers: the
        rows form reads each slot's live chunks (ops/kv_cache.
        decode_read: the program's own roundings), the head-split form
        all of it."""
        if self.attn_form != "rows":
            return READ_SHARE_NAMES[-1], self._table.size
        return decode_read(self._pos, self._table, self.block_size)

    def _decode_read_report(self) -> dict:
        """What the model adds to a recorded `decode_step` span about
        the step's read of the cache (models/window_moe.py: the rows
        visible and gathered, by cache kind), and what the step reads
        of the seated slots' state and writes again (`state_bytes`,
        where the model keeps one)."""
        out = self.model.decode_read_report(
            self._pos, self._table, self.block_size)
        if self._slot_state_bytes:
            out["state_bytes"] = self._slot_state_held()
        return out

    def _round(self) -> List[GenerationResult]:
        self._admit()
        done = self._ensure_blocks()
        n_active = sum(r is not None for r in self._req)
        if not n_active:
            return done
        plan = faults.get_plan()
        stepno = self._stats["decode_steps"]
        sampler_path = step_path(self._temp, self._topk, self._topp,
                                 self.model.cfg.vocab_size)
        read_share, read_blocks = self._decode_read()
        poison = np.zeros(self.slots, bool)
        if plan.fires("serve_nan", stepno):
            active = [i for i, r in enumerate(self._req) if r is not None]
            poison[active[0]] = True    # lowest active slot: determinate
        for attempt in range(self.step_retries + 1):
            try:
                plan.maybe_raise("serve_err", stepno)
                slow_s = 0.0
                if plan.fires("serve_slow", stepno):
                    slow_s = (self.step_timeout_s or 0.05) * 5
                tc0 = self._clock()
                # one span per ATTEMPT: a retried round holds one
                # `decode_step` for each, in order, the failed ones
                # ending at their exception
                with self._span("decode_step") as span_d:
                    if span_d.id is not None:
                        # cache rows the step's attention has to read:
                        # each seated slot's clock, and the row it
                        # writes; and the blocks its read gathers, of
                        # the table's
                        span_d.set(step=stepno, active=n_active,
                                   cached_tokens=int(sum(
                                       self._pos[i] + 1 for i, r
                                       in enumerate(self._req)
                                       if r is not None)),
                                   attended_blocks=read_blocks,
                                   table_blocks=self._table.size,
                                   sampler_path=sampler_path,
                                   **self._expert_matmul(self.slots),
                                   **self._decode_read_report())
                    nxt, finite = self._dispatch_and_fetch(poison,
                                                           slow_s)
                    self._report_aux(span_d)
                # dispatch+fetch wall time into the fixed-bucket
                # histogram UNCONDITIONALLY: health() percentiles are
                # core engine bookkeeping (this store replaced the
                # recent-latency deque), not optional telemetry — the
                # kill switch gates events/spans/counter mirrors only.
                # Timed on the INJECTABLE clock (graftlint
                # nondeterministic-drill): drills with a fake clock get
                # bit-deterministic latency records too
                self._m_lat.observe(self._clock() - tc0)
                break
            except StepTimeout as e:
                self._bump("watchdog_trips")
                return self._degrade(
                    f"watchdog trip at decode step {stepno}: {e}")
            except Exception as e:              # noqa: BLE001
                if self._cache_consumed():
                    # the failed dispatch already donated the cache
                    # buffers (donate_argnums on TPU; no-op on CPU):
                    # re-dispatching the deleted cache can only fail
                    # with a misleading buffer error, so don't burn
                    # the retry budget — degrade with the real cause
                    return self._degrade(
                        f"decode step {stepno} failed after cache "
                        f"donation (buffers consumed, not "
                        f"retryable): {e}")
                if attempt >= self.step_retries:
                    return self._degrade(
                        f"decode step {stepno} failed after "
                        f"{attempt + 1} attempt(s): {e}")
                self._bump("retries")
                logger.warning("decode step %d attempt %d failed (%s); "
                               "retrying", stepno, attempt + 1, e)
                if self.retry_backoff_s:
                    time.sleep(self.retry_backoff_s * (2 ** attempt))
        self._bump("decode_steps")
        self._sampler_steps[sampler_path] += 1
        self._read_steps[read_share] += 1
        if obs.enabled():
            self._m_sampler[sampler_path].inc()
            self._m_read[read_share].inc()
        now = self._clock()
        with self._span("emit"):
            for i, req in enumerate(self._req):
                if req is None:
                    continue
                done.extend(self._emit_multi(i, [int(nxt[i])],
                                             [bool(finite[i])], now))
        if self._ring_blocks:
            # a ring fills by the step, with no allocation to hang the
            # gauge on
            self._update_rows_gauge()
        return done

    def run(self, requests: Optional[Sequence[Request]] = None
            ) -> List[GenerationResult]:
        """Submit `requests` (if given), then step until queue and
        slots drain. Returns `requests`' results in submission order
        (or, with no argument, everything that finished, id order).
        Results of OTHER requests that finished during the call —
        e.g. queued earlier via submit() — land in `self.completed`,
        never dropped. Shed/expired/poisoned/failed requests return
        with their terminal status (never a KeyError); a 'reject'
        overload raises OverloadError out of the submission phase."""
        if self.role == "prefill":
            # step() exports instead of finishing, so nothing would
            # ever land in completed — drive a prefill tier through an
            # EngineRouter, which harvests take_handoffs()
            raise ValueError(
                "run() on a prefill-role engine: it exports "
                "HandoffPackages instead of decoding — front it with "
                "EngineRouter(prefill_engines=[...])")
        ids = [self.submit(r) for r in requests] if requests else None
        while self._queue or any(r is not None for r in self._req):
            for res in self.step():
                self.completed[res.id] = res
        if ids is None:
            out = sorted(self.completed.values(), key=lambda r: r.id)
            self.completed = {}
            return out
        return [self.completed.pop(i) for i in ids]
