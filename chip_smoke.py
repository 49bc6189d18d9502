"""chip_smoke.py — does the system still start on the chip?

The one hardware entry point: trains the 186M TransformerLM a few steps
through `Optimizer(...).optimize()`, serves it through
`EngineRouter([InferenceEngine(...)])`, checks every Pallas kernel family
compiled by Mosaic (`interpret=False`) against its in-repo reference, and
— when the host has four chips — repeats train and serve over a mesh.
All in ONE process: a chip belongs to one process at a time, so nothing
here spawns a child.

    python chip_smoke.py          # from the repo root, on a TPU machine

It fails (non-zero exit, no result line) when JAX finds no TPU, when any
check fails, or when the repo is not beside it. Each leg prints one JSON
line of what it asserted plus set-up facts (compile and wall seconds —
facts about this run, not benchmark metrics); the last line of stdout is
`{"ok": true, "device": {...}}`.

`main()` has fixed sizes and needs a TPU. The legs are plain functions of
their sizes and an explicit `impl`, so tests/test_chip_smoke.py drives the
same control flow tiny on CPU and chip time is not spent on typos.
"""

from __future__ import annotations

import json
import os
import sys
import time

# The 186M bench configuration (bench.py `lm186m` row): full width, full
# depth, S=2048, batch 8, attn_saved remat, bf16 mixed precision.
LM_186M = dict(vocab=32000, dim=1024, layers=12, heads=16, seq=2048)
TRAIN_BATCH = 8
TRAIN_STEPS = 5
# The mesh leg trains the same width at depth 2: what it adds to the
# one-chip leg is sharding and collectives, which depth does not change,
# and the data-parallel step's TPU codegen time grows with depth (AOT for
# v5e from the sandbox: 113 s at 2 layers, 387 s at 12 — PERF.md).
MESH_TRAIN_LAYERS = 2
SERVE = dict(slots=8, buckets=(512, 2048), block_size=16, new_tokens=8)
# prompt lengths as fractions of max_len: both buckets, short and long
PROMPT_FRACS = (0.004, 0.02, 0.1, 0.2, 0.25, 0.26, 0.35, 0.45, 0.03,
                0.15, 0.6, 0.9)
BILSTM = dict(batch=128, seq=128, hidden=128)      # bench.py bilstm row

# Tolerances, written down before the first chip run. Errors are
# max|x - ref| / max|ref| against a reference computed at
# jax.default_matmul_precision("highest"); the bound is what single-pass
# bf16 MXU arithmetic can cost, not what interpret mode achieves (~1e-6).
TOL_FWD = 2e-2
TOL_GRAD = 5e-2
# the engine's greedy pick may trail the fp32 reference's best log-prob
# by at most this many nats (a wrong token would trail by ~2.5)
TOL_GREEDY_NATS = 0.25


class SmokeFailure(AssertionError):
    """A check failed. Never caught here: it ends the run non-zero."""


class CompileClock:
    """Sums JAX's backend-compile seconds (a cache hit costs its
    retrieval only), so each leg can report cold vs warm compile."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.total += duration


class Leg:
    """One leg's record: what it asserted, its facts, one JSON line."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock
        self.asserted, self.facts = [], {}
        self._t0, self._c0 = time.perf_counter(), clock.total

    def check(self, ok, what: str) -> None:
        if not ok:
            raise SmokeFailure(f"{self.name}: {what}")
        self.asserted.append(what)

    def done(self) -> None:
        print(json.dumps({
            "leg": self.name, "ok": True, "asserted": self.asserted,
            "compile_s": round(self.clock.total - self._c0, 2),
            "wall_s": round(time.perf_counter() - self._t0, 2),
            **self.facts}), flush=True)


def _rel_err(x, ref) -> float:
    """max|x - ref| / max|ref|, on the host (no compile per shape)."""
    import numpy as np

    x = np.asarray(x, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(x - ref)) / max(np.max(np.abs(ref)), 1e-6))


# ----------------------------------------------------------------- device

def device_line() -> dict:
    """Print what JAX found; return the result line's device object."""
    import jax
    import jaxlib

    from bigdl_tpu.utils.engine import setup_compile_cache

    cache_dir = setup_compile_cache()
    dev = jax.devices()[0]
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:    # not installed off-TPU: a fact, not a fault
        libtpu = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"leg": "device", **device, "jax": jax.__version__,
                      "jaxlib": jaxlib.__version__, "libtpu": libtpu,
                      "compile_cache_dir": cache_dir,
                      "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")}),
          flush=True)
    return device


# ------------------------------------------------------------------ train

def train_leg(clock, *, vocab, dim, layers, heads, seq, batch, steps,
              attn_impl=None, expect_attn="pallas", mesh=None,
              name="train"):
    """The README Quickstart path on the LM: Optimizer + ChunkedSoftmaxCE
    + Adam + bf16 policy for `steps` iterations. Returns (trained
    model, the attention tiles the step used). `attn_impl=None` lets
    the platform choose — on the chip that must resolve to the Mosaic
    kernel (`expect_attn`)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn, obs
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.text import synthetic_next_token
    from bigdl_tpu.models.transformer import TransformerConfig, TransformerLM
    from bigdl_tpu.ops.flash_attention import (_default_impl,
                                               flash_attention_plan)
    from bigdl_tpu.optim import Adam, Optimizer, Trigger

    leg = Leg(name, clock)
    cfg = TransformerConfig(vocab_size=vocab, max_len=seq, dim=dim,
                            num_heads=heads, num_layers=layers, remat=True,
                            remat_policy="attn_saved")
    model = TransformerLM(cfg, attn_impl=attn_impl)
    model.build(jax.random.PRNGKey(0))

    # which attention will the step trace? Ask what the model asks, at
    # the shape the step uses (per-device batch under a mesh)
    local_batch = batch // (mesh.size if mesh is not None else 1)
    impl = attn_impl or _default_impl()
    plan = flash_attention_plan(seq, seq, dim // heads, local_batch * heads,
                                2, True)
    block_q, block_k = plan.block_q, plan.block_k
    leg.check(impl == expect_attn,
              f"attention impl resolved to {impl!r}, expected "
              f"{expect_attn!r}")
    leg.facts.update(attn_impl=impl, attn_blocks=[block_q, block_k])

    end_when = Trigger.max_iteration(steps)
    placement = {}
    if mesh is not None:
        end_when = _placement_probe(end_when, mesh, placement)
    # ONE batch, seen every step: with working gradients the loss on
    # it must fall, whatever the step count or the size
    opt = (Optimizer(model, DataSet.array(
               synthetic_next_token(batch, vocab, seq)),
               nn.ChunkedSoftmaxCE(), batch_size=batch)
           .set_optim_method(Adam(3e-4))
           .set_precision("bf16")
           .set_end_when(end_when))
    if mesh is not None:
        opt.set_mesh(mesh)
    seen = len(obs.get_event_log().events("train_step"))
    opt.optimize()
    losses = [e["loss"] for e in
              obs.get_event_log().events("train_step")[seen:]]

    leg.check(len(losses) == steps, f"{steps} train_step events carry a "
              f"loss (got {len(losses)})")
    leg.check(all(l == l and abs(l) != float("inf") for l in losses),
              f"every loss finite: {losses}")
    leg.check(losses[-1] < losses[0],
              f"last loss {losses[-1]:.4f} below first {losses[0]:.4f}")
    leg.facts.update(losses=[round(l, 4) for l in losses],
                     params=int(sum(a.size for _, a in model.parameters())))
    if mesh is not None:
        _check_placement(leg, mesh, placement)
    leg.done()
    return model, (block_q, block_k)


def _placement_probe(end_when, mesh, out: dict):
    """Wrap the end trigger (called once per iteration, mid-run) to
    record what is resident on each mesh device while training is live
    — after `optimize()` returns, the sharded state is already freed."""
    import jax

    def probe(state):
        if state["neval"] >= 1 and not out:
            spanning = [a for a in jax.live_arrays()
                        if a.sharding.device_set == set(mesh.devices.flat)]
            big = max(spanning, key=lambda a: a.size, default=None)
            out["largest_spanning"] = None if big is None else {
                "shape": list(big.shape), "dtype": str(big.dtype),
                "spec": str(getattr(big.sharding, "spec", None)),
                "shard_devices": sorted(
                    s.device.id for s in big.addressable_shards)}
            out["bytes_in_use"] = _bytes_in_use(mesh)
        return end_when(state)

    return probe


def _bytes_in_use(mesh) -> dict:
    return {d.id: (d.memory_stats() or {}).get("bytes_in_use")
            for d in mesh.devices.flat}


def _check_bytes_in_use(leg, in_use: dict) -> None:
    if all(v is None for v in in_use.values()):
        # CPU devices report no memory_stats; the shard checks stand
        leg.facts["memory_stats"] = "not reported by this backend"
        return
    leg.check(all(v for v in in_use.values()),
              f"bytes_in_use non-zero on each of {len(in_use)} devices: "
              f"{in_use}")


def _check_placement(leg, mesh, placement) -> None:
    n = mesh.size
    big = placement.get("largest_spanning")
    leg.check(big is not None and len(set(big["shard_devices"])) == n,
              f"a live training array has addressable shards on {n} "
              f"distinct devices: {big}")
    leg.facts["placement"] = placement
    _check_bytes_in_use(leg, placement["bytes_in_use"])


# ------------------------------------------------------------------ serve

def make_requests(vocab, max_len, new_tokens, seed=0):
    """A dozen seeded requests: prompt lengths across both prefill
    buckets, even ones greedy, odd ones sampled (temperature/top-k/
    top-p), tokens from the grammar the train leg learned."""
    import numpy as np

    from bigdl_tpu.serving import Request

    rng = np.random.RandomState(seed)
    reqs = []
    for i, frac in enumerate(PROMPT_FRACS):
        n = min(max(2, int(frac * max_len)), max_len - new_tokens)
        prompt = ((rng.randint(0, vocab) + np.arange(n)) % vocab).tolist()
        sampled = dict(temperature=0.8, top_k=40, top_p=0.95) if i % 2 \
            else {}
        reqs.append(Request(prompt=prompt, max_new_tokens=new_tokens,
                            seed=100 + i, **sampled))
    return reqs


def serve_leg(clock, model, *, slots, buckets, block_size, new_tokens,
              tp_mesh=None, name="serve"):
    """Serve `model` through EngineRouter([InferenceEngine]) under an
    armed step watchdog; every request must finish `done` on a healthy
    engine that compiled decode once. Returns (results, requests)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import EngineRouter, InferenceEngine

    leg = Leg(name, clock)
    cfg = model.cfg
    eng = InferenceEngine(
        model, model.variables, slots=slots, max_len=cfg.max_len,
        prefill_buckets=buckets, block_size=block_size, tp_mesh=tp_mesh,
        # armed: the constructor's pre-warm must keep the first compile
        # out of the budget, and no healthy step may trip it
        step_timeout_s=60.0)
    router = EngineRouter([eng])
    requests = make_requests(cfg.vocab_size, cfg.max_len, new_tokens)
    results = router.run(requests)

    stats, health = eng.stats, eng.health()
    leg.check(all(r.status == "done" for r in results),
              f"every request done: {[r.status for r in results]}")
    leg.check(all(len(r.tokens) == new_tokens and
                  all(0 <= t < cfg.vocab_size for t in r.tokens)
                  for r in results),
              f"{new_tokens} in-vocabulary tokens per request")
    leg.check(health["state"] == "ok", f"engine health {health['state']!r}")
    leg.check(stats["decode_traces"] == 1,
              f"decode traced once (got {stats['decode_traces']})")
    used = {min(b for b in buckets if b >= len(r.prompt)) for r in requests}
    leg.check(stats["prefill_traces"] <= len(buckets),
              f"prefill traced at most once per bucket "
              f"(got {stats['prefill_traces']} for {sorted(used)})")
    leg.check(stats["retries"] == 0 and stats["watchdog_trips"] == 0
              and stats["failed"] == 0,
              "zero retries, watchdog trips and failed requests")
    leg.facts.update(attn_form=health["attn_form"], tp=health["tp"],
                     requests=len(results),
                     decode_steps=stats["decode_steps"],
                     prefill_traces=stats["prefill_traces"])

    # reference: the plain fp32 forward (attention_reference, highest
    # matmul precision) on every greedy prompt that fits the small
    # bucket, right-padded to it — causal, so padding cannot reach back
    ref_len = min(buckets)
    greedy = [(r, q) for r, q in zip(results, requests)
              if q.temperature <= 0 and len(q.prompt) <= ref_len]
    toks = np.zeros((len(greedy), ref_len), np.int32)
    for row, (_, q) in enumerate(greedy):
        toks[row, :len(q.prompt)] = q.prompt
    ref_model = TransformerLM(cfg, attn_impl="reference")
    ends = jnp.asarray([len(q.prompt) - 1 for _, q in greedy])

    def last_logp(variables, toks):     # only (n, V) leaves the device
        return ref_model.apply(variables, toks)[0][jnp.arange(len(greedy)),
                                                   ends]

    with jax.default_matmul_precision("highest"):
        last = np.asarray(jax.jit(last_logp)(
            {"params": model.variables["params"], "state": {}},
            jnp.asarray(toks)))
    gaps = [float(row.max() - row[r.tokens[0]])
            for row, (r, _) in zip(last, greedy)]
    leg.check(len(gaps) >= 2 and max(gaps) <= TOL_GREEDY_NATS,
              f"greedy first tokens within {TOL_GREEDY_NATS} nats of the "
              f"fp32 reference's best (gaps {[round(g, 4) for g in gaps]})")
    leg.facts["greedy_gap_nats_max"] = round(max(gaps), 5)

    if tp_mesh is not None:
        _check_engine_placement(leg, eng, tp_mesh)
    leg.done()
    return results, requests


def _check_engine_placement(leg, eng, mesh) -> None:
    """The sharded engine is alive: its weights and KV pools must have
    shards on every mesh device, and every device must hold bytes."""
    import jax

    n = mesh.size
    pool_devs = {s.device.id for leaf in jax.tree_util.tree_leaves(eng.pool)
                 for s in leaf.addressable_shards}
    w_devs = {s.device.id
              for leaf in jax.tree_util.tree_leaves(eng._params)
              for s in leaf.addressable_shards}
    leg.check(len(pool_devs) == n and len(w_devs) == n,
              f"KV pools on devices {sorted(pool_devs)} and weights on "
              f"{sorted(w_devs)}: {n} distinct devices each")
    k0 = eng.pool[0]["k"]
    leg.facts["placement"] = {
        "pool_k0_global": list(k0.shape),
        "pool_k0_shard": list(k0.addressable_shards[0].data.shape),
        "pool_devices": sorted(pool_devs), "weight_devices": sorted(w_devs)}
    in_use = _bytes_in_use(mesh)
    leg.facts["placement"]["bytes_in_use"] = in_use
    _check_bytes_in_use(leg, in_use)


# ---------------------------------------------------------------- kernels

def kernel_leg(clock, model, *, impl, flash_blocks, bilstm,
               name="kernels"):
    """Every Pallas family at the shape its caller uses, against its
    in-repo reference. `impl` is "pallas" on the chip (Mosaic,
    interpret=False) and "interpret" in the CPU test."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.ops import fused_rnn
    from bigdl_tpu.ops.flash_attention import (attention_reference,
                                               flash_attention)

    leg = Leg(name, clock)
    cfg = model.cfg
    h, d = cfg.num_heads, cfg.dim // cfg.num_heads
    rng = np.random.RandomState(0)

    # --- flash attention fwd + bwd: one batch row of the train shape,
    # the train step's tiles, bf16 like the mixed-precision step
    s = cfg.max_len
    q, k, v = (jnp.asarray(rng.randn(h, s, d), jnp.bfloat16)
               for _ in range(3))
    bq, bk = flash_blocks

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, impl=impl, block_q=bq,
            block_k=bk).astype(jnp.float32) ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(attention_reference(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True) ** 2)

    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, impl=impl, block_q=bq, block_k=bk))(q, k, v)
    grads = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda q, k, v: attention_reference(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True))(q, k, v)
        ref_grads = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
    err = _rel_err(out, ref)
    gerr = max(_rel_err(g, r) for g, r in zip(grads, ref_grads))
    leg.check(err <= TOL_FWD, f"flash fwd rel err {err:.3g} <= {TOL_FWD}")
    leg.check(gerr <= TOL_GRAD,
              f"flash dq/dk/dv rel err {gerr:.3g} <= {TOL_GRAD}")
    leg.facts["flash"] = {"shape": [h, s, d], "blocks": [bq, bk],
                          "fwd_err": err, "grad_err": gerr}

    # --- fused BiLSTM scan: forward and gradient vs the lax.scan path
    b, t, hid = bilstm["batch"], bilstm["seq"], bilstm["hidden"]
    zxf, zxb = (jnp.asarray(0.2 * rng.randn(b, t, 4 * hid), jnp.float32)
                for _ in range(2))
    wf, wb = (jnp.asarray(0.1 * rng.randn(hid, 4 * hid), jnp.float32)
              for _ in range(2))

    def rnn_loss(which):
        def f(zxf, zxb, wf, wb):
            yf, yb = fused_rnn.bilstm_scan(zxf, zxb, wf, wb, impl=which)
            return jnp.sum(yf ** 2) + jnp.sum(yb ** 2)
        return f

    yf, yb = jax.jit(lambda *a: fused_rnn.bilstm_scan(*a, impl=impl))(
        zxf, zxb, wf, wb)
    g = jax.jit(jax.grad(rnn_loss(impl), argnums=(0, 1, 2, 3)))(
        zxf, zxb, wf, wb)
    with jax.default_matmul_precision("highest"):
        rf, rb = jax.jit(lambda *a: fused_rnn.bilstm_scan(*a, impl="xla"))(
            zxf, zxb, wf, wb)
        rg = jax.jit(jax.grad(rnn_loss("xla"), argnums=(0, 1, 2, 3)))(
            zxf, zxb, wf, wb)
    err = max(_rel_err(yf, rf), _rel_err(yb, rb))
    gerr = max(_rel_err(a, r) for a, r in zip(g, rg))
    leg.check(err <= TOL_FWD, f"fused bilstm fwd rel err {err:.3g} <= "
              f"{TOL_FWD}")
    leg.check(gerr <= TOL_GRAD, f"fused bilstm grad rel err {gerr:.3g} <= "
              f"{TOL_GRAD}")
    leg.facts["fused_bilstm"] = {"shape": [b, t, hid], "fwd_err": err,
                                 "grad_err": gerr}

    # --- int8 quantized linear lowers and stays close (not Pallas; the
    # one other hardware-only path the old validation script held)
    lin = nn.Linear(256, 128)
    lv = lin.init(jax.random.PRNGKey(1))
    qm, qv = nn.QuantizedLinear.from_float(lin, lv)
    x = jnp.asarray(rng.randn(16, 256), jnp.float32)
    yq, _ = jax.jit(lambda v, x: qm.apply(v, x))(qv, x)
    with jax.default_matmul_precision("highest"):
        yr, _ = lin.apply(lv, x)
    err = _rel_err(yq, yr)
    leg.check(err <= 0.05, f"int8 linear rel err {err:.3g} <= 0.05")
    leg.facts["int8_linear_err"] = err

    leg.facts["impl"] = impl
    leg.done()


# ------------------------------------------------------------------- mesh

def mesh_leg(clock, model, *, n, lm, batch, steps, serve, attn_impl=None,
             expect_attn="pallas"):
    """Train data-parallel and serve tensor-parallel over `n` devices,
    asserting where the sharded state sits."""
    import jax

    from bigdl_tpu.parallel import make_mesh

    devices = jax.devices()[:n]
    train_leg(clock, **lm, batch=batch, steps=steps, attn_impl=attn_impl,
              expect_attn=expect_attn,
              mesh=make_mesh({"data": n}, devices=devices),
              name=f"mesh_train[data={n}]")
    serve_leg(clock, model, **serve,
              tp_mesh=make_mesh({"model": n}, devices=devices),
              name=f"mesh_serve[model={n}]")


# ------------------------------------------------------------------- main

def main() -> int:
    import jax

    device = device_line()
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU — jax found platform "
              f"{device['platform']!r} ({device['kind']}), JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}. This script checks "
              "hardware and has no CPU mode.", file=sys.stderr)
        return 2

    clock = CompileClock()
    t0 = time.perf_counter()
    model, flash_blocks = train_leg(clock, **LM_186M, batch=TRAIN_BATCH,
                                    steps=TRAIN_STEPS)
    serve_leg(clock, model, **SERVE)
    kernel_leg(clock, model, impl="pallas", flash_blocks=flash_blocks,
               bilstm=BILSTM)
    if jax.device_count() >= 4:
        mesh_leg(clock, model, n=4,
                 lm={**LM_186M, "layers": MESH_TRAIN_LAYERS},
                 batch=TRAIN_BATCH, steps=TRAIN_STEPS, serve=SERVE)
    else:
        # a statement, not a pass: the mesh paths did not run here
        print(json.dumps({"leg": "mesh", "ok": None,
                          "skipped": f"mesh: skipped "
                                     f"({jax.device_count()} device)"}),
              flush=True)
    print(json.dumps({"leg": "total", "compile_s": round(clock.total, 2),
                      "wall_s": round(time.perf_counter() - t0, 2)}),
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
