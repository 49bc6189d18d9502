"""Plain reference of the `afmoe` family (Arcee's Trinity models): a decoder
whose layers attend either a sliding window or everything before them, with
grouped-query heads, per-head q/k norms, an output gate and four norms a
layer, and whose feed-forward is dense in the first `num_dense_layers`
layers and a sigmoid-routed mixture of experts with a shared expert in the
rest. Read off a `config.json` of the family, here
huggingface.co/arcee-ai/Trinity-Mini (`model_type: afmoe`), and, for what
the config does not carry, off the family's public modeling code
(`modeling_afmoe.py`): each such point is marked (code) below and listed
under `assumed` in the configuration's file.

The yardstick of every cell of the family. It imports nothing of the
program: weights come from `init(seed, cfg)` here, and the family file
(benchmarks/families/afmoe.py) hands the SAME arrays to the program.
float32 `jax.numpy`; callers wrap calls in
`jax.default_matmul_precision("highest")`. No kernels, no cache, no
batching: one sequence at a time, the keys and values of every position
held whole, the window a mask over the full (S, S) score matrix (computed
a block of query rows at a time, so that 7,168 positions fit one chip), and
a loop over ALL experts, each weighted by the routing (zero where a token
did not choose it).

With x a token's residual, Hq query heads over G key-value heads of D,
query head h reading key-value head h // (Hq/G), no biases, RMSNorm's eps
`rms_norm_eps`:

  embedding  x = Emb[tok] * sqrt(hidden_size)            (`mup_enabled`)
  attention  h = RMSNorm_in(x); q = h W_q, k = h W_k, v = h W_v, g = h W_og
             q <- RMSNorm_q(q), k <- RMSNorm_k(k), over each head's D (code)
             `sliding_attention`: q, k <- RoPE(q, k); key j visible to
                 query i iff j <= i and i - j < `sliding_window`
             `full_attention`: NO rotation (code); key j visible iff j <= i
             o = softmax(q . k / sqrt(D)) v;  o <- o * sigmoid(g)   (code)
             x <- x + RMSNorm_post_attn(o W_o)                      (code)
  RoPE       theta `rope_theta` over D, the HALF-SPLIT pairs (i, i + D/2)
             rotated by pos * theta^(-2i/D) (`rotate_half`)         (code)
  FFN        h2 = RMSNorm_pre_mlp(x);  x <- x + RMSNorm_post_mlp(f(h2))
  dense f    (silu(h2 W_g) * h2 W_u) W_d
  MoE f      s = sigmoid(h2 W_r); the chosen are the top-k of s + b (the
             selection bias (code): it selects and does not weigh);
             w_i = route_scale * s_i / (sum_chosen s_j + 1e-20)
             (`route_norm`); y = sum_i w_i E_i(h2) + E_shared(h2), every
             expert a gated FFN
  output     RMSNorm(x) W_head, an untied head

Which layers: `layer_types` gives every published layer's attention kind;
`kept_layers`, where the file has it, the published layers that are run, in
order (the cut in depth); the first `num_dense_layers` of those that are
run have the dense FFN.

Departures from the source, each for a reason:
  - Weights are seeded random and bfloat16-VALUED (the precision the
    configuration states), held as bfloat16 and upcast to float32 one
    matrix, and one expert, at a time, so that the reference fits one
    chip beside them. ASSUMED: normal(0, `initializer_range` or 0.02),
    norm gains 1.
  - The selection bias is drawn non-zero, normal(0, `expert_bias_std` or
    0.02): a trained checkpoint's is learned and keeps the experts' loads
    even; a zero one could not tell selecting by s + b from selecting by s
    (benchmarks/reference/mla_moe.py has the readings that chose 0.02).
  - Every matrix is (in, out): y = x W, where the source stores (out, in).
  - One group of experts (`n_group`, `topk_group`, `num_expert_groups`,
    `num_limited_groups` all 1); `rope_scaling: null`.

`precision` selects the arithmetic of the *control*, never of the
reference: None is float32; "fp8" rounds both operands of every matmul but
the router's to float8_e4m3 under per-tensor scales (the nearest precision
below bfloat16; the router stays float32, as the configuration states it
for every precision).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.gpt2 import _mm  # matmul, or its fp8 control

QUERY_ROWS = 512        # query rows a block of the score matrix


def layer_plan(cfg: dict) -> list:
    """[(attention kind, FFN kind)] of the layers that are run."""
    kept = cfg.get("kept_layers", range(len(cfg["layer_types"])))
    kinds = [cfg["layer_types"][i] for i in kept]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(kinds)} layers kept, num_hidden_layers "
                         f"{cfg['num_hidden_layers']}")
    return [(kind, "dense" if n < cfg["num_dense_layers"] else "moe")
            for n, kind in enumerate(kinds)]


def init(seed, cfg: dict) -> dict:
    """One traceable function of the seed (a uint32 scalar): jit it WITH
    THE SEED AS AN ARGUMENT and all weights are made on the device by one
    program, the same for every seed."""
    std = cfg.get("initializer_range", 0.02)
    bias_std = cfg.get("expert_bias_std", 0.02)
    d, v, dh = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    hq, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    key = jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32))
    count = iter(range(10 ** 6))

    def normal(*shape, scale=std, dtype=jnp.bfloat16):
        return (jax.random.normal(jax.random.fold_in(key, next(count)),
                                  shape, jnp.float32) * scale).astype(dtype)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def layer(ffn):
        lp = {
            "input_norm": ones(d), "w_q": normal(d, hq * dh),
            "w_k": normal(d, g * dh), "w_v": normal(d, g * dh),
            "w_og": normal(d, hq * dh), "q_norm": ones(dh),
            "k_norm": ones(dh), "w_o": normal(hq * dh, d),
            "post_attn_norm": ones(d), "pre_mlp_norm": ones(d),
            "post_mlp_norm": ones(d),
        }
        if ffn == "dense":
            f = cfg["intermediate_size"]
            lp.update(w_g=normal(d, f), w_u=normal(d, f), w_d=normal(f, d))
        else:
            e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
            fs = cfg["num_shared_experts"] * f
            lp.update(
                w_r=normal(d, e),
                b_r=normal(e, scale=bias_std, dtype=jnp.float32),
                e_g=normal(e, d, f), e_u=normal(e, d, f),
                e_d=normal(e, f, d), s_g=normal(d, fs), s_u=normal(d, fs),
                s_d=normal(fs, d))
        return lp

    return {"embed": normal(v, d), "head": normal(d, v), "norm": ones(d),
            "layers": [layer(ffn) for _, ffn in layer_plan(cfg)]}


def _f32(w):
    return w.astype(jnp.float32)


def _rms(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, pos, theta):
    """x (S, H, D) rotated at positions pos (S,): the pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (pos.astype(jnp.float32)[:, None] * inv)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _gated(h, w_g, w_u, w_d, precision):
    return _mm(jax.nn.silu(_mm(h, _f32(w_g), precision))
               * _mm(h, _f32(w_u), precision), _f32(w_d), precision)


def attention(lp, x, kind, cfg, precision=None):
    s = x.shape[0]
    hq, g, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(s)
    h = _rms(x, lp["input_norm"], eps)
    q = _rms(_mm(h, _f32(lp["w_q"]), precision).reshape(s, hq, dh),
             lp["q_norm"], eps)
    k = _rms(_mm(h, _f32(lp["w_k"]), precision).reshape(s, g, dh),
             lp["k_norm"], eps)
    v = _mm(h, _f32(lp["w_v"]), precision).reshape(s, g, dh)
    gate = _mm(h, _f32(lp["w_og"]), precision)
    if kind == "sliding_attention":
        q, k = (_rope(q, pos, cfg["rope_theta"]),
                _rope(k, pos, cfg["rope_theta"]))
    elif kind != "full_attention":
        raise ValueError(f"layer type {kind!r}")
    # every query head beside its key-value head: repeat each of the G
    k = jnp.repeat(k, hq // g, axis=1).transpose(1, 2, 0)   # (Hq, D, S)
    v = jnp.repeat(v, hq // g, axis=1).transpose(1, 0, 2)   # (Hq, S, D)
    rows = QUERY_ROWS if s % QUERY_ROWS == 0 else s

    def block(args):
        i, qi = args                                        # (rows, Hq, D)
        score = _mm(qi.transpose(1, 0, 2), k, precision) * dh ** -0.5
        iq = (i * rows + jnp.arange(rows))[:, None]
        visible = pos[None, :] <= iq
        if kind == "sliding_attention":
            visible &= iq - pos[None, :] < cfg["sliding_window"]
        p = jax.nn.softmax(jnp.where(visible, score, -1e30), axis=-1)
        return _mm(p, v, precision).transpose(1, 0, 2).reshape(rows, -1)

    o = lax.map(block, (jnp.arange(s // rows),
                        q.reshape(s // rows, rows, hq, dh))).reshape(s, -1)
    o = o * jax.nn.sigmoid(gate)
    return _rms(_mm(o, _f32(lp["w_o"]), precision), lp["post_attn_norm"],
                eps)


def routing(lp, h, cfg):
    """(S, E) float32: w_i where token t chose expert i, else 0."""
    s = jax.nn.sigmoid(jnp.matmul(h, _f32(lp["w_r"])))
    _, chosen = lax.top_k(s + lp["b_r"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("route_norm", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg["route_scale"]
    return jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None],
                                chosen].set(w)


def ffn(lp, x, cfg, precision=None):
    eps = cfg["rms_norm_eps"]
    h = _rms(x, lp["pre_mlp_norm"], eps)
    if "w_g" in lp:
        y = _gated(h, lp["w_g"], lp["w_u"], lp["w_d"], precision)
    else:
        def one_expert(acc, xs):
            w_g, w_u, w_d, column = xs
            return acc + column[:, None] * _gated(h, w_g, w_u, w_d,
                                                  precision), None

        y, _ = lax.scan(one_expert, jnp.zeros_like(x),
                        (lp["e_g"], lp["e_u"], lp["e_d"],
                         routing(lp, h, cfg).T))
        y = y + _gated(h, lp["s_g"], lp["s_u"], lp["s_d"], precision)
    return _rms(y, lp["post_mlp_norm"], eps)


def hidden(params: dict, tokens, cfg: dict, precision=None):
    """(S,) tokens of ONE sequence -> (S, D) final-norm hidden states."""
    if precision not in (None, "fp8"):
        raise ValueError(f"precision {precision!r}: None (the reference) or "
                         "'fp8' (its control)")
    x = _f32(params["embed"][tokens])
    if cfg.get("mup_enabled", False):
        x = x * cfg["hidden_size"] ** 0.5
    for lp, (kind, _) in zip(params["layers"], layer_plan(cfg)):
        x = x + attention(lp, x, kind, cfg, precision)
        x = x + ffn(lp, x, cfg, precision)
    return _rms(x, params["norm"], cfg["rms_norm_eps"])


def head(params, hid, precision=None):
    """(N, D) final-norm hidden states -> (N, V) float32 logits."""
    return _mm(hid, _f32(params["head"]), precision)


def logits(params, tokens, cfg, precision=None):
    """(B, S) tokens -> (B, S, V) float32 logits, a sequence at a time."""
    return lax.map(lambda t: head(params, hidden(params, t, cfg, precision),
                                  precision), tokens)
