"""Plain reference of the `ouro` family (ByteDance Seed's looped language
models, arXiv:2510.25741; here Ouro-2.6B): a decoder whose stack of layers
is run `total_ut_steps` times over ONE set of weights, each pass with keys
and values of its own, the final norm between the passes and an exit gate
after each. Read off huggingface.co/ByteDance/Ouro-2.6B's `config.json`
(`model_type: ouro`), whose keys are named in `code` below; what it does
not carry is marked [m] (the family's modelling code, as ISSUE 49 records
it) and listed under `assumed` in the configuration's file.

The yardstick of every cell of the family. It imports nothing of the
program: weights come from `init(seed, cfg)` here, and the family file
(benchmarks/families/loop_lm.py) hands the SAME arrays to the program.
float32 `jax.numpy`; callers wrap calls in
`jax.default_matmul_precision("highest")`. No kernels, no cache, no scan,
no batching: one sequence at a time, A PLAIN PYTHON LOOP OVER PASSES AND
LAYERS, the attention as one masked softmax over the full (S, S) scores.

With d = `hidden_size`, S positions, Hq = `num_attention_heads` over G =
`num_key_value_heads` heads of D = `head_dim`, RMSNorm's eps
`rms_norm_eps`, no bias anywhere [m]:

  h = Emb[tok]                                          (no scale)
  for pass t in 0 .. `total_ut_steps` - 1:
    for layer l in 0 .. `num_hidden_layers` - 1 (`layer_types`: every one
    `full_attention`), the same weights in every pass:
      a = RMSNorm_1(h)                 four gain vectors a layer [m]
      q, k, v = a W_q, a W_k, a W_v
      q, k <- rotated: the pairs (i, i + D/2) of all D numbers by
              position * `rope_theta`^(-2i/D), the same in every pass (no
              embedding of the pass [m])
      o = softmax(q k^T / sqrt(D), causal) v over the keys and values of
          THIS pass and this layer [m]: a pass never reads another's
      h <- h + RMSNorm_2(o W_o)
      m = RMSNorm_3(h);  h <- h + RMSNorm_4((silu(m W_g) * m W_u) W_d)
    h <- RMSNorm_final(h): the normed h is what pass t + 1 takes in [m]
    lambda_t = sigmoid(w_exit . h + b_exit): one vector and one bias for
               every pass [m]
  logits = h W_head over the last pass's normed state
                                          (`tie_word_embeddings: false`)

The probability of leaving after pass t is lambda_t * prod_{j<t} (1 -
lambda_j), the last pass takes the rest (`exit_probabilities`); generation
leaves at the first pass whose cumulated probability reaches
`early_exit_threshold` [m]: at the published 1.0 always the last, so every
token runs every pass and the logits above are what is served.

Departures from the source, each for a reason:
  - Weights are seeded random and bfloat16-VALUED (the precision the
    configuration states), held as bfloat16 and upcast to float32 one
    matrix at a time, so that the reference fits one chip beside them.
    ASSUMED (the config carries no initialiser): every matrix and the
    embedding normal(0, `initializer_range` or 0.02), norm gains 1, the
    exit gate zero (lambda_t = 1/2: nothing reads it at threshold 1).
  - Every matrix is (in, out): y = x W, where the source stores (out, in).

`precision` selects the arithmetic of the *control*, never of the
reference: None is float32; "fp8" rounds both operands of every matmul to
float8_e4m3 under per-tensor scales (the nearest precision below
bfloat16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.gpt2 import _mm  # matmul, or its fp8 control


def layer_plan(cfg: dict) -> list:
    """The kind of every (pass, layer) that is run, pass-major."""
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(kinds)} layer_types, num_hidden_layers "
                         f"{cfg['num_hidden_layers']}")
    if set(kinds) != {"full_attention"}:
        raise ValueError(f"layer types {sorted(set(kinds))}: every one "
                         "'full_attention'")
    return kinds * cfg["total_ut_steps"]


def _normal(seed, part, cfg: dict):
    """How a part of the tree (0: the top; n + 1: layer n) draws a matrix:
    normal in bfloat16 at the initialiser's scale. `part` may be traced."""
    std = cfg.get("initializer_range", 0.02)
    key = jax.random.fold_in(
        jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32)), part)
    count = iter(range(10 ** 6))

    def normal(*shape):
        return (jax.random.normal(jax.random.fold_in(key, next(count)),
                                  shape, jnp.float32) * std
                ).astype(jnp.bfloat16)

    return normal


def init_top(seed, cfg: dict) -> dict:
    normal = _normal(seed, 0, cfg)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": normal(v, d), "head": normal(d, v),
            "norm": jnp.ones((d,), jnp.float32),
            "exit_w": jnp.zeros((d,), jnp.float32),
            "exit_b": jnp.zeros((), jnp.float32)}


def init_layer(seed, cfg: dict, n) -> dict:
    """Layer n (may be traced): ONE set of weights, whatever the pass."""
    normal = _normal(seed, n + 1, cfg)
    d, f, dh = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    hq, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]

    def ones():
        return jnp.ones((d,), jnp.float32)

    return {"norm_1": ones(), "w_q": normal(d, hq * dh),
            "w_k": normal(d, g * dh), "w_v": normal(d, g * dh),
            "w_o": normal(hq * dh, d), "norm_2": ones(), "norm_3": ones(),
            "w_g": normal(d, f), "w_u": normal(d, f), "w_d": normal(f, d),
            "norm_4": ones()}


def init(seed, cfg: dict) -> dict:
    """One traceable function of the seed (a uint32 scalar)."""
    return {**init_top(seed, cfg),
            "layers": [init_layer(seed, cfg, n)
                       for n in range(cfg["num_hidden_layers"])]}


def _f32(w):
    return w.astype(jnp.float32)


def _rms(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rotated(x, theta):
    """x (S, H, D) at positions 0..S-1: the pairs (i, i + D/2)."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None] * inv)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], -1)


def keys_and_values(lp, x, cfg, precision=None):
    """What a layer keeps of the stream x (S, d) in one pass: its rotated
    keys and its values, (S, G, D) each."""
    s = x.shape[0]
    g, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    a = _rms(x, lp["norm_1"], cfg["rms_norm_eps"])
    k = _mm(a, _f32(lp["w_k"]), precision).reshape(s, g, dh)
    v = _mm(a, _f32(lp["w_v"]), precision).reshape(s, g, dh)
    return _rotated(k, cfg["rope_theta"]), v


def layer(lp, x, k, v, cfg, precision=None):
    """One layer on the stream x (S, d) of ONE sequence in one pass,
    attending the keys and values k, v (S, G, D) it is given."""
    s = x.shape[0]
    eps, dh = cfg["rms_norm_eps"], cfg["head_dim"]
    hq, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a = _rms(x, lp["norm_1"], eps)
    q = _rotated(_mm(a, _f32(lp["w_q"]), precision).reshape(s, hq, dh),
                 cfg["rope_theta"])
    # every query head beside its key-value head: repeat each of the G
    kk = jnp.repeat(k, hq // g, axis=1).transpose(1, 2, 0)  # (Hq, D, S)
    vv = jnp.repeat(v, hq // g, axis=1).transpose(1, 0, 2)  # (Hq, S, D)
    score = _mm(q.transpose(1, 0, 2), kk, precision) * dh ** -0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal, score, -1e30), axis=-1)
    o = _mm(p, vv, precision).transpose(1, 0, 2).reshape(s, hq * dh)
    x = x + _rms(_mm(o, _f32(lp["w_o"]), precision), lp["norm_2"], eps)
    m = _rms(x, lp["norm_3"], eps)
    f = _mm(jax.nn.silu(_mm(m, _f32(lp["w_g"]), precision))
            * _mm(m, _f32(lp["w_u"]), precision), _f32(lp["w_d"]), precision)
    return x + _rms(f, lp["norm_4"], eps)


def embed(params, tokens):
    return _f32(params["embed"][tokens])


def final(params, x, cfg: dict):
    return _rms(x, params["norm"], cfg["rms_norm_eps"])


def exit_gate(params, x):
    """lambda of the normed stream x (S, d), (S,)."""
    return jax.nn.sigmoid(x @ params["exit_w"] + params["exit_b"])


def hidden(params: dict, tokens, cfg: dict, precision=None, parts=None):
    """(S,) tokens of ONE sequence -> ((S, d) the last pass's normed
    stream, (T, S) the exit gate of every pass). `parts`, or None: the
    functions (`keys_and_values`, `layer`) to call in place of this
    module's own (the family file's jitted ones: a program a layer, not
    one of all the passes)."""
    if precision not in (None, "fp8"):
        raise ValueError(f"precision {precision!r}: None (the reference) or "
                         "'fp8' (its control)")
    layer_plan(cfg)
    kv, one_layer = parts or (
        lambda lp, x: keys_and_values(lp, x, cfg, precision),
        lambda lp, x, k, v: layer(lp, x, k, v, cfg, precision))
    x = embed(params, tokens)
    rows = {}       # (pass, layer) -> the keys and values kept there
    gates = []
    for t in range(cfg["total_ut_steps"]):
        for l, lp in enumerate(params["layers"]):
            rows[t, l] = kv(lp, x)
            k, v = rows[t, l]       # this pass's own: never another's
            x = one_layer(lp, x, k, v)
        x = final(params, x, cfg)   # every pass ends in the final norm
        gates.append(exit_gate(params, x))
    return x, jnp.stack(gates)


def exit_probabilities(gates):
    """(T, S) gates -> (T, S): the probability of leaving after pass t,
    lambda_t * prod_{j<t} (1 - lambda_j), the last pass taking the rest."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], 0)
    p = gates * before
    return p.at[-1].set(before[-1])


def head(params, hid, cfg: dict, precision=None):
    """(N, d) normed states -> (N, V) float32 logits."""
    return _mm(hid, _f32(params["head"]), precision)


def forward(params, tokens, cfg, precision=None):
    """(B, S) tokens -> ((B, S, V) float32 logits, (B, T, S) exit gates),
    a sequence at a time."""
    out = []
    for toks in tokens:
        hid, gates = hidden(params, toks, cfg, precision)
        out.append((head(params, hid, cfg, precision), gates))
    return tuple(jnp.stack(x) for x in zip(*out))


def logits(params, tokens, cfg, precision=None):
    return forward(params, tokens, cfg, precision)[0]
