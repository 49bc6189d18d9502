"""hidden-device-sync — no device→host fetches on hot/emission paths.

Two contracts meet here:

* obs emission consumes ALREADY-FETCHED host values — zero new device
  syncs (tests/test_obs.py pins compile counts; a sync hiding in an
  emission helper would stall the decode loop once per event);
* the serving decode loop performs exactly ONE deliberate fetch per
  step (the watchdog-guarded `np.asarray` in `_dispatch_and_fetch`) —
  any other `.item()`/`np.asarray`/`device_get` on that path is a
  stealth device round-trip that stalls the batch.

The deliberate fetch carries an inline
`# graftlint: disable=hidden-device-sync` with its justification;
everything else is a finding. Scope: all of `bigdl_tpu/obs/`, plus
hot-path functions (decode/prefill/step/dispatch/sample/work/emit/
observe, and the paged-cache lookup/insert/evict/alloc paths —
ISSUE 8: block-table and radix-tree surgery runs between EVERY decode
step, so a sync there stalls the whole batch once per admission) in
`serving/`, `ops/kv_cache.py` and `models/transformer.py`.

ISSUE 10 widens the hot set to the sharded-serving paths: handoff
export/import (`_export_handoff` carries the ONE suppressed
per-request fetch — the disaggregation boundary; anything else on a
handoff path is a stealth sync per package) and pool placement
(`place_pools` runs on the step path after eager pool surgery — it
must re-COMMIT shardings, never fetch). `serving/tp.py` is inside the
`serving/` scope like the rest of the plane; its `gather_serving_
params` (the checkpoint form — a deliberate whole-tree fetch) is
host-side setup by name, not a hot path.

ISSUE 11 extends the scope to the journey/flight-recorder layer
(`obs/journey.py`, `obs/flightrecorder.py` — named explicitly below
even though the `bigdl_tpu/obs/` prefix already covers them: shrinking
the obs/ scope must not silently drop them) and the hot-name set to
journey/record/dump/bundle/flight functions: the flight recorder runs
INSIDE emit (an EventLog listener), so a sync in a dump path would
stall the decode loop once per incident-adjacent event — everything it
records must be an already-emitted host dict.

ISSUE 15 widens the hot-name set to the speculative-decoding paths:
verify/rollback/mirror/spec functions (`serving/speculative.py` —
already inside the `serving/` scope). The verify dispatch carries the
round's ONE suppressed target fetch and each draft chain step its
bounded draft fetch (the chain is sequential by construction); the
acceptance loop, rollback (a pure table/length edit) and mirror
seating run BETWEEN every verify round, so a stealth sync there
stalls the whole batch once per round — same bar as the block-table
surgery paths.

ISSUE 16 widens the hot-name set to the host spill tier:
spill/readmit/migrate functions (prefix_cache.py tree surgery, the
engine's spill cascade and re-admission, the router's warm-state
migration). Spill export carries ONE suppressed batched `device_get`
(host parking is the point — the bytes must come down) and
re-admission/tree import their deliberate eager `device_put`-side
placement; everything else on those paths is host bookkeeping over
block ids and numpy arrays, so any other fetch is a stealth sync per
eviction or per admission.

ISSUE 17 adds quant/repack to the hot-name set: `serving/quant.py`'s
repack (already inside the `serving/` prefix) must stay device-side
jnp ops: quantization happens once at engine
construction, but a fetch hiding in `quantize_serving_params` would
pull the whole fp32 tree to the host.

ISSUE 18 adds `parallel/param_layout.py` to the scope and
swap/distill/adapt to the hot-name set (the speculation flywheel).
The param-layout spine's shard/unstack/spec helpers run inside
jitted step traces (zero2 slices) and on the engine-construction /
hot-swap path; `swap_params`/`swap_draft` execute BETWEEN decode
rounds on a LIVE engine — a fetch there stalls serving once per
swap, and the swap is pure re-placement (structure/shape checks on
tree metadata, never values). The adaptive-k ladder (`_evaluate_k`)
and the distiller's corpus walk are host arithmetic over already-
fetched ints; `gather_tree`'s np.asarray is the deliberate,
documented exception (explicit gather API, not a step path).
"""

from __future__ import annotations

import ast
import re

from bigdl_tpu.analysis.engine import Rule, register
from bigdl_tpu.analysis.rules._common import call_name, last_segment

_SYNC_CALLS = {"np.asarray", "numpy.asarray", "np.array",
               "numpy.array", "jax.device_get", "device_get",
               "jax.block_until_ready"}
_SYNC_METHODS = {"item", "block_until_ready", "tolist", "__array__"}
_HOT_FN = re.compile(
    r"(decode|prefill|dispatch|step|sample|work|emit|observe"
    r"|lookup|insert|evict|alloc|handoff|place"
    r"|journey|record|dump|bundle|flight"
    r"|verify|rollback|mirror|spec"
    r"|spill|readmit|migrate"
    r"|quant|repack"
    r"|swap|distill|adapt)")


@register
class HiddenDeviceSync(Rule):
    name = "hidden-device-sync"
    severity = "error"
    description = ("device→host fetch on a decode/step hot path or "
                   "obs emission path")
    scope = ("bigdl_tpu/obs/", "bigdl_tpu/obs/journey.py",
             "bigdl_tpu/obs/flightrecorder.py",
             "bigdl_tpu/serving/",
             "bigdl_tpu/ops/kv_cache.py",
             "bigdl_tpu/models/transformer.py",
             "bigdl_tpu/parallel/param_layout.py")

    def _in_scope(self, ctx, node) -> bool:
        fns = ctx.enclosing_functions(node)
        if not fns:
            return False
        if ctx.path.startswith("bigdl_tpu/obs/"):
            return True
        return any(_HOT_FN.search(fn.name) for fn in fns)

    def check(self, ctx):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            hit = None
            if name in _SYNC_CALLS:
                hit = name
            elif isinstance(node.func, ast.Attribute) \
                    and not node.args and not node.keywords \
                    and last_segment(name) in _SYNC_METHODS:
                hit = f".{last_segment(name)}()"
            if hit is None or not self._in_scope(ctx, node):
                continue
            yield self.finding(
                ctx, node,
                f"{hit} forces a device→host sync on a hot/emission "
                f"path — consume already-fetched host values (the one "
                f"deliberate per-step fetch carries an inline "
                f"suppression with its why)")
