"""Plain GPT-2 (Radford et al. 2019; huggingface.co/openai-community/gpt2-medium
config.json): forward, loss, gradients and Adam in straightforward jax.numpy.

The yardstick for every cell of the `gpt2` family. It imports nothing of the
program and takes nothing the program has made: weights come from `init(seed)`
here, and the family file (benchmarks/families/gpt2.py) hands the SAME arrays
to the program in the program's own tree layout.

No kernels, no cache, no batching tricks: pre-LN blocks, learned positions,
tied output head, tanh-GELU (`gelu_new`), full (S, S) causal attention scores.
Callers wrap calls in `jax.default_matmul_precision("highest")`: on a TPU a
float32 matmul otherwise runs as one bfloat16 pass.

`precision` selects the arithmetic of the *control* (the nearest precision
below the one a configuration states), never of the reference itself:
  None   float32 throughout (the reference)
  "bf16" parameters and activations in bfloat16 (control of a float32 config)
  "fp8"  every matmul as an fp8 step runs it: operands rounded to
         float8_e4m3 forward, gradients to float8_e5m2 backward, per-tensor
         scales (control of a bfloat16 config)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

LAYER_KEYS = ("ln1_g", "ln1_b", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
              "bo", "ln2_g", "ln2_b", "w_fc", "b_fc", "w_proj", "b_proj")


def init(seed: int, cfg: dict) -> dict:
    """GPT-2's initialiser (HF `_init_weights`): normal(0, initializer_range)
    for every matrix and both embeddings, the two residual projections scaled
    by 1/sqrt(2 n_layer), LayerNorm gain 1, every bias 0. One traceable
    function of the seed (a uint32 scalar, Python or traced): jit it WITH
    THE SEED AS AN ARGUMENT and the weights are made on the device by one
    program that is the same for every seed, so the compile cache holds it."""
    e, l, v, p = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"], \
        cfg["n_positions"]
    f = cfg.get("n_inner") or 4 * e
    std = cfg.get("initializer_range", 0.02)
    key = jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32))

    def normal(i, shape, scale=1.0):
        return jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32) * (std * scale)

    res = (2 * l) ** -0.5
    ones, zeros = jnp.ones, jnp.zeros
    return {
        "wte": normal(0, (v, e)), "wpe": normal(1, (p, e)),
        "ln1_g": ones((l, e)), "ln1_b": zeros((l, e)),
        "wq": normal(2, (l, e, e)), "wk": normal(3, (l, e, e)),
        "wv": normal(4, (l, e, e)), "wo": normal(5, (l, e, e), res),
        "bq": zeros((l, e)), "bk": zeros((l, e)), "bv": zeros((l, e)),
        "bo": zeros((l, e)),
        "ln2_g": ones((l, e)), "ln2_b": zeros((l, e)),
        "w_fc": normal(6, (l, e, f)), "b_fc": zeros((l, f)),
        "w_proj": normal(7, (l, f, e), res), "b_proj": zeros((l, e)),
        "lnf_g": ones((e,)), "lnf_b": zeros((e,)),
    }


def _round_fp8(x, dtype=jnp.float8_e4m3fn, largest=448.0):
    """Round to an 8-bit float under a per-tensor scale (amax -> the
    format's largest finite value)."""
    x = x.astype(jnp.float32)
    s = largest / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(dtype).astype(jnp.float32) / s


def fp8_op(op):
    """`op(a, b)` (a matmul, a convolution) as an fp8 training step would
    run it: forward on operands rounded to e4m3, backward on the same
    rounded operands and the incoming gradient rounded to e5m2."""
    @jax.custom_vjp
    def f(a, b):
        return op(_round_fp8(a), _round_fp8(b))

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        _, vjp = jax.vjp(op, _round_fp8(res[0]), _round_fp8(res[1]))
        return vjp(_round_fp8(g, jnp.float8_e5m2, 57344.0))

    f.defvjp(fwd, bwd)
    return f


_fp8_matmul = fp8_op(jnp.matmul)


def _mm(a, b, precision):
    if precision == "fp8":
        return _fp8_matmul(a, b)
    return jnp.matmul(a, b)


def _ln(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, -1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps)).astype(x.dtype) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def hidden(params: dict, tokens, cfg: dict, precision=None, remat=False):
    """(B, S) int tokens -> (B, S, E) final-LayerNorm hidden states.
    `remat` recomputes each block in the backward pass (jax.checkpoint): it
    changes what is kept in memory, not what is computed."""
    if precision == "bf16":
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params)
    h, eps = cfg["n_head"], cfg.get("layer_norm_epsilon", 1e-5)
    b, s = tokens.shape
    e = params["wte"].shape[1]
    d = e // h
    x = params["wte"][tokens] + params["wpe"][:s]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, lp):
        y = _ln(x, lp["ln1_g"], lp["ln1_b"], eps)

        def heads(w, bias):
            return (_mm(y, w, precision) + bias).reshape(
                b, s, h, d).transpose(0, 2, 1, 3)

        q, k, v = (heads(lp["wq"], lp["bq"]), heads(lp["wk"], lp["bk"]),
                   heads(lp["wv"], lp["bv"]))
        scores = _mm(q, k.transpose(0, 1, 3, 2), precision) * (d ** -0.5)
        scores = jnp.where(causal, scores.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        a = _mm(probs, v, precision).transpose(0, 2, 1, 3).reshape(b, s, e)
        x = x + _mm(a, lp["wo"], precision) + lp["bo"]
        y = _ln(x, lp["ln2_g"], lp["ln2_b"], eps)
        y = _gelu_new(_mm(y, lp["w_fc"], precision) + lp["b_fc"])
        return x + _mm(y, lp["w_proj"], precision) + lp["b_proj"], None

    x, _ = lax.scan(jax.checkpoint(block) if remat else block, x,
                    {k: params[k] for k in LAYER_KEYS})
    return _ln(x, params["lnf_g"], params["lnf_b"], eps)


def logits(params, tokens, cfg, precision=None, remat=False):
    """(B, S) tokens -> (B, S, V) float32 logits through the tied head."""
    hid = hidden(params, tokens, cfg, precision, remat)
    wte = params["wte"].astype(hid.dtype)
    return _mm(hid, wte.T, precision).astype(jnp.float32)


def loss(params, tokens, targets, cfg, precision=None, remat=False):
    """Mean next-token negative log-likelihood over every position."""
    lg = logits(params, tokens, cfg, precision, remat)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, targets[..., None].astype(jnp.int32), axis=-1))


def loss_and_grad_rows(params, tokens, targets, cfg, precision=None,
                       rows_per_block: int = 1):
    """Loss and gradient of the mean over ALL rows, in blocks of rows with
    each block rematerialised, so that float32 activations of a full-size
    batch fit."""
    from benchmarks.reference.optim import loss_and_grad_in_blocks

    return loss_and_grad_in_blocks(
        lambda p, x, y: loss(p, x, y, cfg, precision, remat=True),
        params, tokens, targets, rows_per_block)
