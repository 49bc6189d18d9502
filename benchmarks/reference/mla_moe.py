"""Plain reference of the `mla_moe` family: a decoder whose every layer
has multi-head latent attention (MLA) and whose feed-forward is dense in
the first `first_k_dense_replace` layers and a sigmoid-routed mixture of
experts with a shared expert in the rest. The equations are DeepSeek-V3's
(DeepSeek-AI 2024, arXiv:2412.19437, sections 2.1.1 and 2.1.2; the public
`modeling_deepseek.py` for what the paper leaves open), read off a
`config.json` of the family, here
huggingface.co/jdopensource/JoyAI-LLM-Flash (`model_type: joyai_llm_flash`).

The yardstick of every cell of the family. It imports nothing of the
program: weights come from `init(seed, cfg)` here, and the family file
(benchmarks/families/mla_moe.py) hands the SAME arrays to the program.
float32 `jax.numpy`; callers wrap calls in
`jax.default_matmul_precision("highest")`. No kernels, no cache, no
batching: one sequence at a time, naive attention over per-head keys and
values expanded from the latent, full (S, S) causal scores, and a loop
over ALL experts, each weighted by the routing (zero where a token did
not choose it).

With x a token's residual, h = RMSNorm(x) (eps `rms_norm_eps`), no biases:

  attention  c_q = RMSNorm(h W_DQ);  q = c_q W_UQ  = H x (nope + rope)
             [c_kv ; k_r] = h W_DKV;  c_kv <- RMSNorm(c_kv)
             k_rope = RoPE(k_r), one for all heads;  q_rope <- RoPE(q_rope)
             per head [k_nope ; v] = c_kv W_UKV
             score = (q_nope . k_nope + q_rope . k_rope) / sqrt(qk_head_dim)
             causal softmax;  out = concat_h(sum p v) W_O
  RoPE       theta `rope_theta` over the rope dims, the pairs (2i, 2i+1)
             rotated by pos * theta^(-2i/d) (`rope_interleave: true`)
  dense FFN  (silu(h W_g) * h W_u) W_d
  MoE FFN    s = sigmoid(h W_r); the chosen are the top-k of s + b
             (`e_score_correction_bias`; it selects and does not weigh);
             w_i = routed_scaling_factor * s_i / sum_chosen s_j;
             y = sum_i w_i E_i(h) + E_shared(h), every expert a gated FFN
  output     RMSNorm(x) W_head, an untied head

Departures from the source, each for a reason:
  - Weights are seeded random and bfloat16-VALUED (the precision the
    configuration states), held as bfloat16 and upcast to float32 one
    matrix, and one expert, at a time, so that the reference fits one
    chip beside them. ASSUMED: normal(0, 0.02) (the catalog row carries
    no `initializer_range`; 0.02 is the family's), norm gains 1.
  - `e_score_correction_bias` is drawn non-zero, normal(0, 0.02) (a
    trained checkpoint's is learned, and what it learns is to keep the
    experts' loads even; a zero one could not tell selecting by s + b
    from selecting by s, and one of 0.1 moves an expert's threshold by
    most of a standard deviation of its score: 64 tokens then reach 125
    of the 256 experts and not 214, the busiest gets ten times the
    mean, and how many are reached, which sets a decode step's time,
    hangs on the seed. PERF.md, PR 28, has the readings.)
  - Every matrix is (in, out): y = x W, where the source stores (out, in).
  - `n_group = topk_group = 1`: no group-limited routing (one group).
  - `rope_scaling: null`: no YaRN factor, no mscale.
  - The multi-token-prediction block (`num_nextn_predict_layers`) is not
    part of the forward pass: the family's inference code drops it too.

`precision` selects the arithmetic of the *control*, never of the
reference: None is float32; "fp8" rounds both operands of every matmul
but the router's to float8_e4m3 under per-tensor scales (the nearest
precision below bfloat16; the router stays float32, as the configuration
states it for every precision).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.gpt2 import _mm  # matmul, or its fp8 control


def layer_kinds(cfg: dict) -> list:
    k = cfg["first_k_dense_replace"]
    return ["dense" if i < k else "moe"
            for i in range(cfg["num_hidden_layers"])]


def init(seed, cfg: dict) -> dict:
    """One traceable function of the seed (a uint32 scalar): jit it WITH
    THE SEED AS AN ARGUMENT and all weights are made on the device by one
    program, the same for every seed. The two standard deviations are
    the configuration's where it gives them (`initializer_range`,
    `e_score_correction_bias_std`: the toy of tests/bench takes a wider
    bias for its 8 experts)."""
    std = cfg.get("initializer_range", 0.02)
    bias_std = cfg.get("e_score_correction_bias_std", 0.02)
    d, h, v = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["vocab_size"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    key = jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32))
    count = iter(range(10 ** 6))

    def normal(*shape, scale=std, dtype=jnp.bfloat16):
        return (jax.random.normal(jax.random.fold_in(key, next(count)),
                                  shape, jnp.float32) * scale).astype(dtype)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def layer(kind):
        lp = {
            "input_norm": ones(d),
            "w_dq": normal(d, cfg["q_lora_rank"]),
            "q_norm": ones(cfg["q_lora_rank"]),
            "w_uq": normal(cfg["q_lora_rank"], h * (nope + rope)),
            "w_dkv": normal(d, rank + rope), "kv_norm": ones(rank),
            "w_ukv": normal(rank, h * (nope + vd)),
            "w_o": normal(h * vd, d), "post_norm": ones(d),
        }
        if kind == "dense":
            f = cfg["intermediate_size"]
            lp.update(w_g=normal(d, f), w_u=normal(d, f), w_d=normal(f, d))
        else:
            e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
            fs = cfg["n_shared_experts"] * f
            lp.update(
                w_r=normal(d, e),
                b_r=normal(e, scale=bias_std, dtype=jnp.float32),
                e_g=normal(e, d, f), e_u=normal(e, d, f),
                e_d=normal(e, f, d), s_g=normal(d, fs), s_u=normal(d, fs),
                s_d=normal(fs, d))
        return lp

    return {"embed": normal(v, d), "head": normal(d, v), "norm": ones(d),
            "layers": [layer(k) for k in layer_kinds(cfg)]}


def _f32(w):
    return w.astype(jnp.float32)


def _rms(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, pos, cfg):
    """x (S, ..., d) rotated at positions pos (S,)."""
    d = x.shape[-1]
    inv = cfg["rope_theta"] ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (pos.astype(jnp.float32)[:, None] * inv).reshape(
        (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if cfg.get("rope_interleave", True):
        a, b = x[..., 0::2], x[..., 1::2]       # the pairs (2i, 2i+1)
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         -1).reshape(x.shape)
    a, b = x[..., :d // 2], x[..., d // 2:]     # the pairs (i, i + d/2)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _gated(h, w_g, w_u, w_d, precision):
    return _mm(jax.nn.silu(_mm(h, _f32(w_g), precision))
               * _mm(h, _f32(w_u), precision), _f32(w_d), precision)


def attention(lp, x, cfg, precision=None):
    s = x.shape[0]
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, eps = cfg["qk_nope_head_dim"], cfg["rms_norm_eps"]
    pos = jnp.arange(s)
    h = _rms(x, lp["input_norm"], eps)
    c_q = _rms(_mm(h, _f32(lp["w_dq"]), precision), lp["q_norm"], eps)
    q = _mm(c_q, _f32(lp["w_uq"]), precision).reshape(s, heads, -1)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, cfg)
    lat = _mm(h, _f32(lp["w_dkv"]), precision)
    c_kv = _rms(lat[:, :rank], lp["kv_norm"], eps)
    k_rope = _rope(lat[:, rank:], pos, cfg)             # (S, rope)
    kv = _mm(c_kv, _f32(lp["w_ukv"]), precision).reshape(s, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = cfg.get("qk_head_dim", nope + q_rope.shape[-1]) ** -0.5
    score = (_mm(q_nope.transpose(1, 0, 2), k_nope.transpose(1, 2, 0),
                 precision)
             + _mm(q_rope.transpose(1, 0, 2), k_rope.T, precision)) * scale
    causal = pos[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(causal, score, -1e30), axis=-1)
    o = _mm(p, v.transpose(1, 0, 2), precision)         # (H, S, v)
    return _mm(o.transpose(1, 0, 2).reshape(s, -1), _f32(lp["w_o"]),
               precision)


def routing(lp, h, cfg):
    """(S, E) float32: w_i where token t chose expert i, else 0."""
    s = jax.nn.sigmoid(jnp.matmul(h, _f32(lp["w_r"])))
    _, chosen = lax.top_k(s + lp["b_r"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None],
                                chosen].set(w)


def ffn(lp, x, cfg, precision=None):
    h = _rms(x, lp["post_norm"], cfg["rms_norm_eps"])
    if "w_g" in lp:
        return _gated(h, lp["w_g"], lp["w_u"], lp["w_d"], precision)

    def one_expert(acc, xs):
        w_g, w_u, w_d, column = xs
        return acc + column[:, None] * _gated(h, w_g, w_u, w_d,
                                              precision), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(x),
                    (lp["e_g"], lp["e_u"], lp["e_d"],
                     routing(lp, h, cfg).T))
    return y + _gated(h, lp["s_g"], lp["s_u"], lp["s_d"], precision)


def hidden(params: dict, tokens, cfg: dict, precision=None):
    """(S,) tokens of ONE sequence -> (S, D) final-norm hidden states."""
    if precision not in (None, "fp8"):
        raise ValueError(f"precision {precision!r}: None (the reference) or "
                         "'fp8' (its control)")
    x = _f32(params["embed"][tokens])
    for lp in params["layers"]:
        x = x + attention(lp, x, cfg, precision)
        x = x + ffn(lp, x, cfg, precision)
    return _rms(x, params["norm"], cfg["rms_norm_eps"])


def logits(params, tokens, cfg, precision=None):
    """(B, S) tokens -> (B, S, V) float32 logits, a sequence at a time."""
    return lax.map(lambda t: _mm(hidden(params, t, cfg, precision),
                                 _f32(params["head"]), precision), tokens)
