"""retrace-hazard — no Python-value branches on traced arguments
inside jit roots.

A `jit`-decorated function branching on a *traced* argument either
concretization-errors (`if x > 0:`) or, when the value sneaks in as a
Python scalar (a non-static kwarg, a `float()`/`bool()` coercion),
silently retraces per distinct value — the resharding/retrace hazard
class of arXiv 2004.13336, and the reason the serving plane pins the
#buckets+1 compile contract.

The rule inspects functions decorated `@jax.jit` /
`@functools.partial(jax.jit, ...)`: an `if`/`while` test or a
`bool()`/`float()`/`int()` coercion that touches a *bare* non-static
parameter is flagged. Shape metadata (`x.shape`, `x.ndim`, `x.dtype`,
`len(x)`, `isinstance(x, ...)`) is static under trace and allowed, as
are parameters named in `static_argnums`/`static_argnames`.

ISSUE 10: `shard_map`-wrapped bodies are trace roots too — the
sharded serving plane (serving/tp.py) builds its paged trio as local
functions handed to `shard_map(body, mesh=..., ...)`, which traces
`body` exactly like jit traces its function and has NO static-arg
escape hatch: every parameter is a traced operand. A function passed
as the first argument to a `shard_map(...)` call anywhere in the
module is therefore checked with all parameters traced.

ISSUE 17: Pallas KERNEL BODIES are trace roots too — a function
handed to `pl.pallas_call` (directly, wrapped in
`functools.partial(...)`, or via a variable holding such a partial —
the `ops/flash_attention.py` launch idiom) is
traced with its Ref parameters as traced operands. The partial's
bound arguments are the kernel's static escape hatch (grid constants
like tile sizes and `dup_batch` are Python values by construction);
everything unbound is a Ref, and a Python branch on a Ref would
concretize at trace time exactly like a jit-root branch.
"""

from __future__ import annotations

import ast

from bigdl_tpu.analysis.engine import Rule, register
from bigdl_tpu.analysis.rules._common import call_name, functions, \
    jit_decoration, last_segment, param_names

_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "aval", "sharding",
                 "itemsize"}
_STATIC_FNS = {"len", "isinstance", "getattr", "hasattr", "type"}
_COERCIONS = {"bool", "float", "int"}


@register
class RetraceHazard(Rule):
    name = "retrace-hazard"
    severity = "warning"
    description = ("Python-value branch/coercion on a traced argument "
                   "inside a jit root")
    scope = ("bigdl_tpu/",)

    def check(self, ctx):
        shard_bodies = self._shard_map_bodies(ctx.tree)
        kernel_bodies = self._pallas_kernel_bodies(ctx.tree)
        for fn in functions(ctx.tree):
            jit = jit_decoration(fn)
            if jit is None:
                if fn.name in shard_bodies:
                    # shard_map body: no static-arg escape —
                    # everything the mesh hands in is a traced operand
                    nums, names = set(), set()
                elif fn.name in kernel_bodies:
                    # pallas kernel body: partial-bound args are the
                    # static escape; unbound params are traced Refs
                    nums, names = kernel_bodies[fn.name]
                else:
                    continue
            else:
                nums, names = jit
            params = param_names(fn)
            traced = {p for i, p in enumerate(params)
                      if i not in nums and p not in names}
            traced.discard("self")
            yield from self._check_fn(ctx, fn, traced)

    @staticmethod
    def _pallas_kernel_bodies(tree):
        """Kernel name -> (static positional indexes, static kwarg
        names) for functions handed to pallas_call — directly, as an
        inline `functools.partial(kernel, ...)`, or via a variable
        assigned such a partial (the ops/ launch idiom). The partial's
        bound leading positionals / kwargs are static; every other
        parameter is a traced Ref (ISSUE 17)."""

        def unpartial(expr):
            if isinstance(expr, ast.Name):
                return expr.id, set(), set()
            if isinstance(expr, ast.Call) \
                    and last_segment(call_name(expr)) == "partial" \
                    and expr.args \
                    and isinstance(expr.args[0], ast.Name):
                return (expr.args[0].id,
                        set(range(1, len(expr.args))) | {0},
                        {kw.arg for kw in expr.keywords if kw.arg})
            return None

        # variables holding a partial: name -> partial info
        partials = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                info = unpartial(node.value)
                if info is not None and (info[1] or info[2]):
                    partials[node.targets[0].id] = info

        out = {}
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and last_segment(call_name(node)) == "pallas_call"
                    and node.args):
                continue
            first = node.args[0]
            info = unpartial(first)
            if isinstance(first, ast.Name) and first.id in partials:
                info = partials[first.id]
            if info is None:
                continue
            name, pos, kws = info
            # partial(fn, a, b) binds fn's FIRST len-1 params; the
            # recorded indexes 1..n map to param slots 0..n-1
            nums = {i - 1 for i in pos if i} if pos else set()
            out[name] = (nums, kws)
        return out

    @staticmethod
    def _shard_map_bodies(tree):
        """Names of local functions handed to shard_map(body, ...) —
        traced exactly like jit roots (serving/tp.py's paged trio)."""
        out = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and last_segment(call_name(node)) == "shard_map" \
                    and node.args \
                    and isinstance(node.args[0], ast.Name):
                out.add(node.args[0].id)
        return out

    def _bare_traced_names(self, ctx, expr, traced):
        """Name nodes of traced params used by VALUE (not via static
        metadata like .shape/.ndim, len(), or an `is None` pytree-
        structure test — all static under trace)."""
        out = []
        for node in ast.walk(expr):
            if not (isinstance(node, ast.Name) and node.id in traced):
                continue
            parent = ctx.parent(node)
            if isinstance(parent, ast.Attribute) \
                    and parent.value is node \
                    and parent.attr in _STATIC_ATTRS:
                continue
            if isinstance(parent, ast.Call) \
                    and call_name(parent) in _STATIC_FNS \
                    and node in parent.args:
                continue
            if isinstance(parent, ast.Compare) \
                    and all(isinstance(op, (ast.Is, ast.IsNot))
                            for op in parent.ops) \
                    and all(isinstance(c, ast.Constant)
                            and c.value is None
                            for c in parent.comparators):
                continue  # `x is (not) None`: argument-structure test
            out.append(node)
        return out

    def _check_fn(self, ctx, fn, traced):
        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While)):
                for name in self._bare_traced_names(ctx, node.test,
                                                    traced):
                    kind = "while" if isinstance(node, ast.While) \
                        else "if"
                    yield self.finding(
                        ctx, node,
                        f"`{kind}` on traced argument "
                        f"`{name.id}` inside a jit root — "
                        f"concretizes/retraces per value; use "
                        f"lax.cond/jnp.where, or mark the argument "
                        f"static if it is host metadata")
            elif isinstance(node, ast.Call) \
                    and call_name(node) in _COERCIONS and node.args:
                for name in self._bare_traced_names(ctx, node.args[0],
                                                    traced):
                    yield self.finding(
                        ctx, node,
                        f"{call_name(node)}() coerces traced argument "
                        f"`{name.id}` to a Python value inside a jit "
                        f"root — forces a sync or a per-value retrace")
