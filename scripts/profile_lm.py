"""Transformer-LM step profile — decomposed fenced timings + MFU.

Reference parity: models/utils/DistriOptimizerPerf.scala-style synthetic
harness (SURVEY.md §5.1), specialized to the LM flagship so the time
sinks in the 186M/S=2048 training step can be attributed (VERDICT r1
next-round item 1).

The primary instrument is component decomposition (it was written
when no device-side profiler trace could be had; ROADMAP A0 adds the
trace reduction): each piece of the step (attention fwd, attention
fwd+bwd, loss head, full fwd, full step, optimizer update) is jitted
separately and timed with the fenced-fetch methodology (see bench.py
"Measurement notes"). Component times don't add exactly to the full
step (fusion boundaries differ) but rank the sinks reliably.

Usage:
    python scripts/profile_lm.py                 # 186M config
    python scripts/profile_lm.py --dim 512 --layers 8   # 43M config
    python scripts/profile_lm.py --trace /tmp/lm_trace  # + profiler trace
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))



def lm_matmul_flops_per_token(cfg, vocab_tied=True):
    """See models/transformer.lm_train_matmul_flops_per_token — the
    canonical analytic count (kept here as an alias for older tooling)."""
    from bigdl_tpu.models.transformer import lm_train_matmul_flops_per_token

    return lm_train_matmul_flops_per_token(cfg)


def param_count(params):
    import jax

    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


def fenced(fn, args, iters, fetch):
    """Time `iters` chained calls of fn; fence with a host fetch."""
    out = fn(*args)
    float(fetch(out))  # compile + warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    float(fetch(out))
    return (time.perf_counter() - t0) / iters


def measure(report, key, fn, args, iters, fetch):
    """fenced() with OOM/compile-failure tolerance + incremental print."""
    try:
        t = fenced(fn, args, iters, fetch)
        report[key] = round(t * 1e3, 3)
    except Exception as e:  # RESOURCE_EXHAUSTED etc: record, keep going
        report[key] = f"FAILED: {str(e)[:160]}"
    print(json.dumps({key: report[key]}), flush=True)


def chain_time(fn, x0, n=8, reps=3):
    """Per-call time of `fn` with the dispatch floor amortized away:
    scan n dependent applications inside ONE jit (each call feeds the
    next), so the per-dispatch latency is paid once per n calls, not
    once per call."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    looped = jax.jit(lambda x: lax.scan(
        lambda c, _: (fn(c), None), x, None, length=n)[0])
    out = looped(x0)
    float(jnp.sum(out).astype(jnp.float32))  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = looped(out)
    float(jnp.sum(out).astype(jnp.float32))
    return (time.perf_counter() - t0) / (reps * n)


def measure_chain(report, key, fn, x0, n=8):
    try:
        t = chain_time(fn, x0, n=n)
        report[key] = round(t * 1e3, 3)
    except Exception as e:
        report[key] = f"FAILED: {str(e)[:160]}"
    print(json.dumps({key: report[key]}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--trace", default=None, help="jax.profiler trace dir")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--remat-policy", default="full",
                    choices=["full", "dots", "attn_saved"])
    ap.add_argument("--attn-impl", default=None,
                    choices=[None, "pallas", "reference", "xla"],
                    help="attention implementation for the in-model runs")
    ap.add_argument("--skip-components", action="store_true")
    ap.add_argument("--loss", default="fused",
                    choices=["fused", "logsoftmax"],
                    help="fused = logits+LSE chunked loss; logsoftmax = "
                    "materialize full log-probs then NLL (round-1 path)")
    args = ap.parse_args()

    from bigdl_tpu.utils.engine import setup_compile_cache

    setup_compile_cache()

    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import TransformerConfig, TransformerLM
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.utils.precision import DEFAULT_MIXED as policy

    cfg = TransformerConfig(
        vocab_size=args.vocab, max_len=args.seq, dim=args.dim,
        num_heads=args.heads, num_layers=args.layers, remat=args.remat,
        remat_policy=args.remat_policy)
    model = TransformerLM(cfg, attn_impl=args.attn_impl)
    variables = model.init(jax.random.PRNGKey(0))
    params = variables["params"]
    n_params = param_count(params)
    method = Adam(3e-4)
    slots = method.init_slots(params)

    B, S, e, H = args.batch, args.seq, args.dim, args.heads
    D = e // H
    rng = np.random.RandomState(0)
    # rotate a batch pool: identical executions may be memoized server-side
    POOL = 4
    toks = [jnp.asarray(rng.randint(0, args.vocab, (B, S)), jnp.int32)
            for _ in range(POOL)]
    tgts = [jnp.asarray(rng.randint(0, args.vocab, (B, S)), jnp.int32)
            for _ in range(POOL)]

    report = {
        "config": {"dim": e, "layers": args.layers, "heads": H,
                   "vocab": args.vocab, "seq": S, "batch": B,
                   "remat": args.remat, "remat_policy": args.remat_policy,
                   "loss": args.loss},
        "n_params": n_params,
    }
    flops_tok = lm_matmul_flops_per_token(cfg)
    report["train_flops_per_token"] = flops_tok

    # what the Mosaic attention of this model compiles to: tiles, cells,
    # the backward's form, how far the causal skip engages
    from bigdl_tpu.ops.flash_attention import flash_attention_plan

    plan = flash_attention_plan(S, S, D, B * H, 2, cfg.causal)
    report["flash_attention_plan"] = plan._asdict()
    print(json.dumps({"flash_attention_plan": plan._asdict()}), flush=True)

    # ---- loss on logits ---------------------------------------------
    def lm_loss(p, tokens, targets):
        pc = policy.cast_to_compute(p)
        if args.loss == "logsoftmax":
            logp, _ = model.apply({"params": pc, "state": {}}, tokens)
            logp = logp.astype(jnp.float32)
            picked = jnp.take_along_axis(
                logp, targets[..., None], axis=-1)[..., 0]
            return -picked.mean()
        # fused: model minus final log_softmax, chunked LSE loss
        return model.loss({"params": pc, "state": {}}, tokens, targets)

    # ---- components (in-jit chained loops: see chain_time) ----------
    from bigdl_tpu.ops.flash_attention import flash_attention

    if args.skip_components:
        _run_full(args, report, model, cfg, params, slots, method, policy,
                  toks, tgts, POOL, B, S, flops_tok, lm_loss)
        return

    k_c = jnp.asarray(rng.randn(B * H, S, D), jnp.bfloat16)
    v_c = jnp.asarray(rng.randn(B * H, S, D), jnp.bfloat16)
    q0 = jnp.asarray(rng.randn(B * H, S, D), jnp.bfloat16)

    # MXU ceiling as this harness sees it: big chained bf16 matmul
    on_tpu = jax.devices()[0].platform == "tpu"
    mm = 4096 if on_tpu else 512
    mm_a0 = jnp.asarray(rng.randn(mm, mm), jnp.bfloat16)
    mm_b = jnp.asarray(rng.randn(mm, mm), jnp.bfloat16)
    measure_chain(report, "pure_matmul_ms", lambda a: a @ mm_b, mm_a0,
                  n=32 if on_tpu else 4)
    if isinstance(report.get("pure_matmul_ms"), float):
        fl = 2 * mm ** 3
        report["pure_matmul_tflops"] = round(
            fl / (report["pure_matmul_ms"] / 1e3) / 1e12, 1)
        print(json.dumps(
            {"pure_matmul_tflops": report["pure_matmul_tflops"]}),
            flush=True)

    measure_chain(report, "attn_fwd_ms_per_layer",
                  lambda q: flash_attention(q, k_c, v_c, causal=True), q0)

    att_grad = jax.grad(
        lambda q: flash_attention(q, k_c, v_c, causal=True)
        .astype(jnp.float32).sum())
    measure_chain(report, "attn_fwdbwd_ms_per_layer",
                  lambda q: att_grad(q).astype(jnp.bfloat16), q0)

    # XLA reference attention for comparison (materializes S×S)
    from bigdl_tpu.ops.flash_attention import attention_reference
    measure_chain(report, "attn_xla_fwd_ms_per_layer",
                  lambda q: attention_reference(q, k_c, v_c, causal=True)
                  .astype(jnp.bfloat16), q0)

    # one transformer block WITHOUT attention (matmul/LN/gelu chain)
    bp0 = jax.tree_util.tree_map(lambda p: p[0], params["blocks"])
    bp0 = policy.cast_to_compute(bp0)

    def block_noattn(x):
        from bigdl_tpu.nn.normalization import layer_norm

        y = layer_norm(x, bp0["ln1_g"], bp0["ln1_b"])
        y = (y @ bp0["wq"] + bp0["bq"])
        a = y @ bp0["wo"] + bp0["bo"]
        x = x + a
        y = layer_norm(x, bp0["ln2_g"], bp0["ln2_b"])
        y = jax.nn.gelu(y @ bp0["w1"] + bp0["b1"])
        y = y @ bp0["w2"] + bp0["b2"]
        return x + y

    x0 = jnp.asarray(rng.randn(B, S, e), jnp.bfloat16)
    measure_chain(report, "block_noattn_fwd_ms", block_noattn, x0)

    # loss head alone: hidden (B,S,e) -> scalar, fwd+bwd
    hidden = jnp.asarray(rng.randn(B, S, e), jnp.bfloat16)
    headw = policy.cast_to_compute(params["embed"]).T

    def head_loss(h, w, tg):
        if args.loss == "logsoftmax":
            logits = h @ w
            logp = jax.nn.log_softmax(
                logits.astype(jnp.float32), axis=-1)
            return -jnp.take_along_axis(
                logp, tg[..., None], axis=-1)[..., 0].mean()
        from bigdl_tpu.ops.losses import softmax_cross_entropy_chunked

        return softmax_cross_entropy_chunked(h, w, tg)

    head_g = jax.grad(lambda h: head_loss(h, headw, tgts[0]))
    measure_chain(report, "loss_head_fwdbwd_ms",
                  lambda h: (h - 1e-3 * head_g(h)).astype(jnp.bfloat16),
                  hidden, n=4)

    _run_full(args, report, model, cfg, params, slots, method, policy,
              toks, tgts, POOL, B, S, flops_tok, lm_loss)


def _run_full(args, report, model, cfg, params, slots, method, policy,
              toks, tgts, POOL, B, S, flops_tok, lm_loss):
    import jax
    import jax.numpy as jnp

    # full forward
    fwd = jax.jit(lm_loss)
    measure(report, "fwd_ms", fwd, (params, toks[0], tgts[0]), args.iters,
            lambda o: o)

    # fwd + bwd
    grad_fn = jax.jit(jax.value_and_grad(lm_loss))
    measure(report, "fwdbwd_ms", grad_fn, (params, toks[0], tgts[0]),
            args.iters, lambda o: o[0])

    # optimizer update alone
    zeros_g = jax.tree_util.tree_map(jnp.zeros_like, params)
    upd = jax.jit(lambda g, p, s: method.update(
        g, p, s, jnp.asarray(3e-4, jnp.float32), 1))
    measure(report, "optimizer_ms", upd, (zeros_g, params, slots),
            args.iters, lambda o: jax.tree_util.tree_leaves(o[0])[0].sum())

    # ---- full train step --------------------------------------------
    @jax.jit
    def step(p, s, tokens, targets):
        loss, g = jax.value_and_grad(lm_loss)(p, tokens, targets)
        new_p, new_s = method.update(g, p, s, jnp.asarray(3e-4), 1)
        return new_p, new_s, loss

    try:
        p, s = params, slots
        new = step(p, s, toks[0], tgts[0])
        float(new[2])
        p, s = new[0], new[1]

        if args.trace:
            with jax.profiler.trace(args.trace):
                p2, s2, loss = step(p, s, toks[1], tgts[1])
                float(loss)

        t0 = time.perf_counter()
        loss = None
        for i in range(args.iters):
            p, s, loss = step(p, s, toks[i % POOL], tgts[i % POOL])
        float(loss)
        step_s = (time.perf_counter() - t0) / args.iters
        tok_s = B * S / step_s
        report["step_ms"] = round(step_s * 1e3, 3)
        report["tokens_per_sec"] = round(tok_s, 1)
        report["achieved_tflops"] = round(tok_s * flops_tok / 1e12, 2)
        from bigdl_tpu.utils.engine import bf16_utilization

        mfu = bf16_utilization(tok_s * flops_tok)
        report["mfu"] = None if mfu is None else round(mfu, 4)
    except Exception as e:
        report["step_ms"] = f"FAILED: {str(e)[:160]}"
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
