"""Plain reference of the `granitemoehybrid` family at `num_local_experts`
0 (IBM's Granite 4.0-H models; here granite-4.0-h-micro): a decoder whose
layers are either a Mamba-2 mixer (arXiv:2405.21060) or grouped-query
attention WITHOUT any position signal, each followed by one gated MLP, the
stream scaled by Granite's four multipliers and read out through a TIED
head. Read off huggingface.co/ibm-granite/granite-4.0-h-micro's
`config.json` (`model_type: granitemoehybrid`), whose keys are named in
`code` below; what it does not carry (the initialisers) is listed under
`assumed` in the configuration's file.

The yardstick of every cell of the family. It imports nothing of the
program: weights come from `init(seed, cfg)` here, and the family file
(benchmarks/families/granite_hybrid.py) hands the SAME arrays to the
program. float32 `jax.numpy`; callers wrap calls in
`jax.default_matmul_precision("highest")`. No kernels, no cache, no state
kept, no chunks, no batching: one sequence at a time, THE RECURRENCE AS A
PLAIN `lax.scan` OVER POSITIONS, the convolution as four shifted adds, the
attention as one masked softmax over the full (S, S) scores (a block of
query rows at a time, so that 4,096 positions fit one chip).

With d = `hidden_size`, S positions t = 0..S-1, every row before position
0 zero, RMSNorm's eps `rms_norm_eps`, no bias but the convolution's:

  stream     x = `embedding_multiplier` * Emb[tok]; for layer l of
             `layer_types`:
               x <- x + `residual_multiplier` * Mixer_l(RMSNorm(x))
               x <- x + `residual_multiplier` * W_out(silu(g) * v),
                    [g ; v] = W_in RMSNorm(x), each half
                    `shared_intermediate_size`
             logits = RMSNorm(x) Emb^T / `logits_scaling`
                                                  (`tie_word_embeddings`)
  attention  q = u W_q in `num_attention_heads` heads of D = d / heads,
             k, v = u W_k, u W_v in `num_key_value_heads` heads (query
             head h reads key-value head h // R); NO rotation and no
             learned position (`position_embedding_type: "nope"`);
             y = softmax(`attention_multiplier` * q k^T, causal) v W_o:
             the scale is the multiplier, NOT 1 / sqrt(D)
  mamba      H = `mamba_n_heads`, P = `mamba_d_head`, N = `mamba_d_state`,
             K = `mamba_d_conv`, one group (`mamba_n_groups`):
             [z ; xBC ; dt] = u W_inproj          (H P ; H P + 2 N ; H)
             xBC_t <- silu(sum_{j<K} w[j] * xBC_{t-(K-1)+j} + b): depthwise
                                                   (`mamba_conv_bias`)
             [x ; B ; C] = xBC                    (H x P ; N ; N)
             Delta_t = softplus(dt_t + dt_bias), A = -exp(A_log), a head
             h_t = exp(Delta_t A) h_{t-1} + Delta_t * x_t (x) B_t
                                                   (h: H x P x N, h_{-1} = 0)
             y_t = h_t C_t + D * x_t
             y <- RMSNorm(y * silu(z)) * w_norm: the gate FIRST, one group
                  of H P numbers
             out = y W_outproj

Departures from the source, each for a reason:
  - Weights are seeded random and bfloat16-VALUED (the precision the
    configuration states), held as bfloat16 and upcast to float32 one
    matrix at a time, so that the reference fits one chip beside them.
    ASSUMED (the config carries no initialiser): every matrix and the
    embedding normal(0, `initializer_range` or 0.02), norm gains 1, and
    Mamba-2's own for the rest: A uniform in 1..16 (A_log its logarithm),
    dt_bias the inverse softplus of a log-uniform 1e-3..1e-1, D = 1, the
    depthwise taps and their bias uniform in +-1/sqrt(K) (the default of
    the convolution module the source's modelling code builds; at 0.02
    the convolution's output would be a fiftieth of its input and x, B
    and C all but zero). Those stay float32.
  - Every matrix is (in, out): y = x W, where the source stores (out, in).

`precision` selects the arithmetic of the *control*, never of the
reference: None is float32; "fp8" rounds both operands of every matmul to
float8_e4m3 under per-tensor scales (the nearest precision below
bfloat16). The recurrence has no matmul and stays float32 in both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.gpt2 import _mm  # matmul, or its fp8 control

QUERY_ROWS = 512        # query rows a block of the score matrix
LAYER_KINDS = ("mamba", "attention")


def layer_plan(cfg: dict) -> list:
    """The kind of every layer that is run, in order."""
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(kinds)} layer_types, num_hidden_layers "
                         f"{cfg['num_hidden_layers']}")
    bad = [k for k in kinds if k not in LAYER_KINDS]
    if bad:
        raise ValueError(f"layer types {bad}: each one of {LAYER_KINDS}")
    return kinds


def mamba_sizes(cfg: dict):
    """(H, P, N, K, the inner width H P, the convolved width H P + 2 N)."""
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    if cfg["mamba_n_groups"] != 1:
        raise ValueError(f"mamba_n_groups {cfg['mamba_n_groups']}: one group")
    if h * p != cfg["mamba_expand"] * cfg["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand "
                         "x hidden_size")
    return h, p, n, cfg["mamba_d_conv"], h * p, h * p + 2 * n


def _draws(seed, part, cfg: dict):
    """How a part of the tree (0: the top; n + 1: layer n) draws a leaf:
    `normal` in bfloat16 at the initialiser's scale, `uniform` in float32
    between two bounds. `part` may be traced."""
    std = cfg.get("initializer_range", 0.02)
    key = jax.random.fold_in(
        jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32)), part)
    count = iter(range(10 ** 6))

    def normal(*shape):
        return (jax.random.normal(jax.random.fold_in(key, next(count)),
                                  shape, jnp.float32) * std
                ).astype(jnp.bfloat16)

    def uniform(lo, hi, *shape):
        return jax.random.uniform(jax.random.fold_in(key, next(count)),
                                  shape, jnp.float32, lo, hi)

    return normal, uniform


def init_top(seed, cfg: dict) -> dict:
    normal, _ = _draws(seed, 0, cfg)
    d = cfg["hidden_size"]
    return {"embed": normal(cfg["vocab_size"], d),
            "norm": jnp.ones((d,), jnp.float32)}


def init_layer(seed, cfg: dict, n, kind: str) -> dict:
    """Layer n (may be traced) of the kind given (static)."""
    normal, uniform = _draws(seed, n + 1, cfg)
    d, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    lp = {"input_norm": jnp.ones((d,), jnp.float32),
          "post_norm": jnp.ones((d,), jnp.float32)}
    if kind == "mamba":
        h, _, _, k, inner, conv = mamba_sizes(cfg)
        dt = jnp.exp(uniform(jnp.log(1e-3), jnp.log(1e-1), h))
        lp.update(
            in_proj=normal(d, inner + conv + h),
            conv=uniform(-k ** -0.5, k ** -0.5, k, conv),
            conv_bias=uniform(-k ** -0.5, k ** -0.5, conv),
            A_log=jnp.log(uniform(1.0, 16.0, h)),
            # softplus(dt_bias) = dt
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            D=jnp.ones((h,), jnp.float32),
            gate_norm=jnp.ones((inner,), jnp.float32),
            out_proj=normal(inner, d))
    else:
        hq, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        dh = d // hq
        lp.update(w_q=normal(d, hq * dh), w_k=normal(d, g * dh),
                  w_v=normal(d, g * dh), w_o=normal(hq * dh, d))
    lp.update(w_in=normal(d, 2 * f), w_out=normal(f, d))
    return lp


def init(seed, cfg: dict) -> dict:
    """One traceable function of the seed (a uint32 scalar)."""
    return {**init_top(seed, cfg),
            "layers": [init_layer(seed, cfg, n, kind)
                       for n, kind in enumerate(layer_plan(cfg))]}


def _f32(w):
    return w.astype(jnp.float32)


def _rms(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _shifted(x, by: int):
    """Row t of the result is row t - by of x (S, W); zero before 0."""
    return x if by == 0 else jnp.concatenate(
        [jnp.zeros_like(x[:by]), x[:-by]], 0)


def attention(lp, u, cfg, precision=None):
    s, d = u.shape
    hq, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // hq
    if cfg.get("position_embedding_type", "nope") != "nope":
        raise ValueError("a position signal: this family has none")
    q = _mm(u, _f32(lp["w_q"]), precision).reshape(s, hq, dh)
    k = _mm(u, _f32(lp["w_k"]), precision).reshape(s, g, dh)
    v = _mm(u, _f32(lp["w_v"]), precision).reshape(s, g, dh)
    # every query head beside its key-value head: repeat each of the G
    k = jnp.repeat(k, hq // g, axis=1).transpose(1, 2, 0)   # (Hq, D, S)
    v = jnp.repeat(v, hq // g, axis=1).transpose(1, 0, 2)   # (Hq, S, D)
    rows = QUERY_ROWS if s % QUERY_ROWS == 0 else s
    pos = jnp.arange(s)

    def block(args):
        i, qi = args                                        # (rows, Hq, D)
        score = _mm(qi.transpose(1, 0, 2), k, precision) \
            * cfg["attention_multiplier"]
        visible = pos[None, :] <= (i * rows + jnp.arange(rows))[:, None]
        p = jax.nn.softmax(jnp.where(visible, score, -1e30), axis=-1)
        return _mm(p, v, precision).transpose(1, 0, 2).reshape(rows, -1)

    o = lax.map(block, (jnp.arange(s // rows),
                        q.reshape(s // rows, rows, hq, dh))).reshape(s, -1)
    return _mm(o, _f32(lp["w_o"]), precision)


def mamba(lp, u, cfg, precision=None):
    s = u.shape[0]
    h, p, n, taps, inner, conv = mamba_sizes(cfg)
    zxbcdt = _mm(u, _f32(lp["in_proj"]), precision)
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv],
                  zxbcdt[:, inner + conv:])
    # the depthwise convolution: tap j weighs the row K - 1 - j back
    xbc = sum(lp["conv"][j] * _shifted(xbc, taps - 1 - j)
              for j in range(taps)) + lp["conv_bias"]
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :inner].reshape(s, h, p)
    b, c = xbc[:, inner:inner + n], xbc[:, inner + n:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])                # (S, H)
    a = -jnp.exp(lp["A_log"])                               # (H,)

    def step(state, row):
        x_t, b_t, c_t, dt_t = row
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t
        return state, jnp.sum(state * c_t, -1) + lp["D"][:, None] * x_t

    _, y = lax.scan(step, jnp.zeros((h, p, n), jnp.float32), (x, b, c, dt))
    y = y.reshape(s, inner) * jax.nn.silu(z)                # the gate first
    y = _rms(y, lp["gate_norm"], cfg["rms_norm_eps"])
    return _mm(y, _f32(lp["out_proj"]), precision)


def mlp(lp, u, cfg, precision=None):
    f = cfg["shared_intermediate_size"]
    gv = _mm(u, _f32(lp["w_in"]), precision)
    return _mm(jax.nn.silu(gv[:, :f]) * gv[:, f:], _f32(lp["w_out"]),
               precision)


def layer(lp, x, kind: str, cfg: dict, precision=None):
    """One layer on the stream x (S, d) of ONE sequence."""
    eps, rm = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = mamba if kind == "mamba" else attention
    x = x + rm * mixer(lp, _rms(x, lp["input_norm"], eps), cfg, precision)
    return x + rm * mlp(lp, _rms(x, lp["post_norm"], eps), cfg, precision)


def embed(params, tokens, cfg: dict):
    return cfg["embedding_multiplier"] * _f32(params["embed"][tokens])


def final(params, x, cfg: dict):
    return _rms(x, params["norm"], cfg["rms_norm_eps"])


def hidden(params: dict, tokens, cfg: dict, precision=None):
    """(S,) tokens of ONE sequence -> (S, d) final-norm hidden states."""
    if precision not in (None, "fp8"):
        raise ValueError(f"precision {precision!r}: None (the reference) or "
                         "'fp8' (its control)")
    x = embed(params, tokens, cfg)
    for lp, kind in zip(params["layers"], layer_plan(cfg)):
        x = layer(lp, x, kind, cfg, precision)
    return final(params, x, cfg)


def head(params, hid, cfg: dict, precision=None):
    """(N, d) final-norm hidden states -> (N, V) float32 logits."""
    return _mm(hid, _f32(params["embed"]).T, precision) \
        / cfg["logits_scaling"]


def logits(params, tokens, cfg, precision=None):
    """(B, S) tokens -> (B, S, V) float32 logits, a sequence at a time."""
    return lax.map(lambda t: head(
        params, hidden(params, t, cfg, precision), cfg, precision), tokens)
