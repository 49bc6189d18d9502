"""trace-env-read — no `os.environ` reads inside compute-path functions.

The bug class behind the PR-1 flash-attention bwd-tiles patch: an env
var read while jit traces a function is baked into the first compiled
executable for that shape, and changing the variable afterwards is a
silent no-op (the jit cache is keyed on shapes, not on the
environment). Any function in the compute packages can end up under a
`jit` trace (layers run inside the caller's jitted train step), so the
rule is structural, not call-graph-based: env reads in `ops/`, `nn/`,
`parallel/`, `models/` and `serving/` must happen at module import
time — snapshot the knob into `bigdl_tpu/utils/envknobs.py` and read
the snapshot.

Module-top-level reads (import time, by construction before any trace)
are allowed.

ISSUE 17's addition rides the existing prefixes: `serving/quant.py`
(layout choices are CONSTRUCTOR args on the engine, never env — a
quantization knob read here would freeze the first engine's layout
into every later one).

ISSUE 18 likewise: the speculation flywheel's knobs — adaptive
lookahead (`adapt_k`, `k_min`, `adapt_window`, `raise_at`,
`lower_at`, `collapse_at`, `probe_every` on `SpeculativeEngine`) and
distillation (`seq_len`, `batch_size`, `learningrate`, `epochs`,
`zero`, `mesh` on `DraftDistiller`) — are CONSTRUCTOR args, never
env, and `parallel/param_layout.py` rides the `parallel/` prefix:
the spine's shard helpers run inside the zero2 step trace, exactly
where an env read would freeze into the first executable.
"""

from __future__ import annotations

import ast

from bigdl_tpu.analysis.engine import Rule, register
from bigdl_tpu.analysis.rules._common import call_name, dotted

_READ_CALLS = {"os.environ.get", "os.getenv", "environ.get",
               "os.environ.pop", "os.environ.setdefault"}


@register
class TraceEnvRead(Rule):
    name = "trace-env-read"
    severity = "error"
    description = ("os.environ read inside a compute-path function — "
                   "resolved at trace time, baked into the compiled "
                   "executable; snapshot at import via "
                   "utils/envknobs instead")
    scope = ("bigdl_tpu/ops/", "bigdl_tpu/nn/", "bigdl_tpu/parallel/",
             "bigdl_tpu/models/", "bigdl_tpu/serving/")

    def check(self, ctx):
        for node in ast.walk(ctx.tree):
            hit = None
            if isinstance(node, ast.Call) \
                    and call_name(node) in _READ_CALLS:
                hit = call_name(node)
            elif isinstance(node, ast.Subscript) \
                    and dotted(node.value) == "os.environ":
                hit = "os.environ[...]"
            if hit is None:
                continue
            if not ctx.enclosing_functions(node):
                continue  # module-top-level = import time: fine
            yield self.finding(
                ctx, node,
                f"{hit} inside a function is a trace-time env read "
                f"(value is frozen into the first compiled executable "
                f"per shape; later changes are a silent no-op) — "
                f"snapshot the knob at import in "
                f"bigdl_tpu/utils/envknobs.py and read the snapshot")
