"""A decoder language model given as a LIST OF LAYER KINDS, with
multi-head latent attention (MLA) and dropless sigmoid-routed experts:
the DeepSeek-V3 line of architectures (DeepSeek-AI 2024,
arXiv:2412.19437, sections 2.1.1 and 2.1.2), whose `config.json` keys
the configuration below keeps under their own names.

No reference counterpart (the reference has no language model with a
cache). This is the serving side only, through the same paged trio as
`TransformerLM` (`init_block_pool`, `prefill_paged`,
`decode_step_paged`), so `InferenceEngine` and `EngineRouter` serve it
with no branch of their own; `apply` is the plain full-sequence
forward. Training (a loss, flash kernels with a 192-wide query and a
128-wide value) is ROADMAP B-I.

Every layer: x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x)).

Attention (all layers). `c_q = RMSNorm(h W_DQ)`, `q = c_q W_UQ` = H
heads of (nope + rope); `[c_kv ; k_r] = h W_DKV`, `c_kv <-
RMSNorm(c_kv)`, `k_rope = RoPE(k_r)`, one for all heads; per head
`[k_nope ; v] = c_kv W_UKV`; `q_rope <- RoPE(q_rope)`; score =
`(q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)`, causal
softmax in float32. WHAT A TOKEN LEAVES IN THE CACHE IS
`[c_kv ; k_rope]`, after the norm and after RoPE: one row of
`kv_lora_rank + qk_rope_head_dim` numbers a layer, not H keys and
values. Two paths, one mathematics:

  prefill  the naive form: the gathered table's rows are expanded to
           per-head keys and values (one request, so one table) and
           go through `ops/kv_cache.block_attention` over the full
           table extent with the `j <= start + i` mask, as
           `TransformerLM.prefill_paged` does;
  decode   the absorbed form (`ops/kv_cache.latent_paged_attention`):
           W_UK goes into the query and W_UV onto the output, so the
           per-head keys and values of 64 tables are never held.

FFN, by the layer's kind: `dense` is `(silu(h W_g) * h W_u) W_d`;
`moe` is `parallel/moe.DroplessMoE`.

Precision: weights in the dtype they are given in (bfloat16 on the
chip), matmul operands in that dtype with float32 accumulation, the
residual stream, RMSNorm statistics, RoPE, softmax and the router in
float32.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Module
from bigdl_tpu.ops.kv_cache import (block_attention, gather_block_rows,
                                    init_row_pool, latent_paged_attention,
                                    write_decode_rows, write_prompt_rows)
from bigdl_tpu.parallel.moe import DroplessMoE, ExpertsReport, gated_ffn
from bigdl_tpu.serving.protocol import ServedModel

LAYER_KINDS = ("dense", "moe")


@dataclass(frozen=True)
class LatentMoEConfig:
    """`layers` is the model: one kind per layer. The rest are the
    source's widths under the source's names."""
    layers: Tuple[str, ...]
    vocab_size: int
    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    max_position_embeddings: int = 4096

    def __post_init__(self):
        bad = [k for k in self.layers if k not in LAYER_KINDS]
        if bad or not self.layers:
            raise ValueError(f"layers {self.layers!r}: each one of "
                             f"{LAYER_KINDS}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (RoPE pairs)")

    @property
    def max_len(self) -> int:
        """No positional table: RoPE reaches as far as the source says."""
        return self.max_position_embeddings

    @classmethod
    def from_source(cls, cfg: dict) -> "LatentMoEConfig":
        """From a `config.json` of the family (its keys as they are)."""
        only = {
            "rope_scaling": None, "n_group": 1, "topk_group": 1,
            "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "rope_interleave": True, "hidden_act": "silu",
            "attention_bias": False, "tie_word_embeddings": False,
            "moe_layer_freq": 1}
        require_source_values(cfg, only)
        if cfg.get("num_nextn_predict_layers", 0):
            raise NotImplementedError(
                "num_nextn_predict_layers > 0: the multi-token-prediction "
                "block is not built (ROADMAP B-I 8); drop it, as the "
                "family's inference code does")
        dense = cfg["first_k_dense_replace"]
        layers = tuple("dense" if i < dense else "moe"
                       for i in range(cfg["num_hidden_layers"]))
        names = [f for f in cls.__dataclass_fields__ if f != "layers"]
        return cls(layers=layers, **{k: cfg[k] for k in names if k in cfg})


def require_source_values(cfg: dict, only: dict) -> None:
    """`from_source`'s refusal of what a list model does not build: a
    key of `only` that the source's `config.json` gives another value
    (absent: the value it has here)."""
    for key, value in only.items():
        if cfg.get(key, value) != value:
            raise NotImplementedError(
                f"{key}={cfg[key]!r}: this model does {key}={value!r} "
                "only")


def rms_norm(x, gain, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return x32 * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def rope_interleaved(x, pos, theta):
    """Rotate the pairs (2i, 2i+1) of the last axis by pos * theta^(-2i/d)
    (`rope_interleave: true`): x (T, ..., d), pos (T,), float32 out."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv            # (T, d/2)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _mm(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


class LatentMoELM(ExpertsReport, Module, ServedModel):
    """See the module docstring. Parameters are per layer from the
    start (layers differ), so the serving engine makes no second copy:
    `{"embed" (V, D), "head" (D, V), "norm" (D,), "layers": (dict,)*L}`,
    every matrix (in, out). Its rows live in table blocks like
    `TransformerLM`'s: the prefix cache, the spill tier and the handoff
    roles serve it (serving/protocol.py)."""

    unserved = {"speculative": "the verify step's rows of one slot "
                               "would route through the experts "
                               "together: not validated"}

    def __init__(self, config: LatentMoEConfig, name=None):
        super().__init__(name=name)
        c = self.cfg = config
        self.moe = DroplessMoE(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            c.num_experts_per_tok,
            shared_hidden=c.n_shared_experts * c.moe_intermediate_size,
            scale=c.routed_scaling_factor, normalize=c.norm_topk_prob)
        self.row_width = c.kv_lora_rank + c.qk_rope_head_dim
        # the pool's row: whole 128-lane tiles (init_block_pool)
        self.pool_width = -(-self.row_width // 128) * 128
        self.sm_scale = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5

    # ------------------------------------------------------------ weights

    def init_params(self, rng, std: float = 0.02, dtype=jnp.float32):
        c = self.cfg
        d, h = c.hidden_size, c.num_attention_heads
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        keys = iter(jax.random.split(rng, 16 * len(c.layers) + 2))

        def w(*shape):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        def layer(kind):
            lp = {
                "ln1": jnp.ones((d,), jnp.float32),
                "wq_a": w(d, c.q_lora_rank),
                "q_norm": jnp.ones((c.q_lora_rank,), jnp.float32),
                "wq_b": w(c.q_lora_rank, h * qk),
                "wkv_a": w(d, self.row_width),
                "kv_norm": jnp.ones((c.kv_lora_rank,), jnp.float32),
                "wkv_b": w(c.kv_lora_rank,
                           h * (c.qk_nope_head_dim + c.v_head_dim)),
                "wo": w(h * c.v_head_dim, d),
                "ln2": jnp.ones((d,), jnp.float32),
            }
            if kind == "dense":
                f = c.intermediate_size
                lp.update(w_gate=w(d, f), w_up=w(d, f), w_down=w(f, d))
            else:
                e, f = c.n_routed_experts, c.moe_intermediate_size
                fs = c.n_shared_experts * f
                lp["moe"] = {
                    "router": w(d, e).astype(jnp.float32),
                    "router_bias": w(e).astype(jnp.float32),
                    "w_gate": w(e, d, f), "w_up": w(e, d, f),
                    "w_down": w(e, f, d), "ws_gate": w(d, fs),
                    "ws_up": w(d, fs), "ws_down": w(fs, d)}
            return lp

        return {"embed": w(c.vocab_size, d), "head": w(d, c.vocab_size),
                "norm": jnp.ones((d,), jnp.float32),
                "layers": tuple(layer(k) for k in c.layers)}

    # ------------------------------------------------------- layer pieces

    def _queries_and_row(self, lp, h, pos):
        """h (T, D) normed, pos (T,) → q_nope (T, H, nope), q_rope
        (T, H, rope) rotated, and the cache row (T, pool_width): the
        normed c_kv, the rotated k_rope and the padding, float32."""
        c = self.cfg
        t = h.shape[0]
        c_q = rms_norm(_mm(h, lp["wq_a"]), lp["q_norm"], c.rms_norm_eps)
        q = _mm(c_q.astype(h.dtype), lp["wq_b"]).reshape(
            t, c.num_attention_heads, -1)
        q_nope, q_rope = (q[..., :c.qk_nope_head_dim],
                          q[..., c.qk_nope_head_dim:])
        lat = _mm(h, lp["wkv_a"])
        c_kv = rms_norm(lat[:, :c.kv_lora_rank], lp["kv_norm"],
                        c.rms_norm_eps)
        k_rope = rope_interleaved(lat[:, c.kv_lora_rank:], pos,
                                  c.rope_theta)
        pad = jnp.zeros((t, self.pool_width - self.row_width), jnp.float32)
        return (q_nope, rope_interleaved(q_rope, pos, c.rope_theta),
                jnp.concatenate([c_kv, k_rope, pad], axis=-1))

    def _naive_attention(self, lp, q_nope, q_rope, rows, visible, valid):
        """Queries (s, H, .) of one request over its rows (S,
        pool_width), each expanded to per-head keys and values →
        (s, H * v)."""
        c = self.cfg
        s_tab, h = rows.shape[0], c.num_attention_heads
        dt = lp["wkv_b"].dtype
        kv = _mm(rows[:, :c.kv_lora_rank].astype(dt), lp["wkv_b"]).astype(
            dt).reshape(s_tab, h, -1)
        k_rope = jnp.broadcast_to(
            rows[:, None, c.kv_lora_rank:self.row_width].astype(dt),
            (s_tab, h, c.qk_rope_head_dim))
        k = jnp.concatenate([kv[..., :c.qk_nope_head_dim], k_rope], -1)
        q = jnp.concatenate([q_nope, q_rope], -1).astype(dt)

        def heads_first(a):                     # (S, H, d) → (1, H, S, d)
            return a.transpose(1, 0, 2)[None]

        a = block_attention(heads_first(q), heads_first(k),
                            heads_first(kv[..., c.qk_nope_head_dim:]),
                            visible, valid, self.sm_scale)
        return a[0].transpose(1, 0, 2).reshape(q.shape[0], -1)

    def _ffn(self, lp, kind, x):
        """x (T, D) float32 residual → (the FFN's output, the tokens
        each expert got or None)."""
        h32 = rms_norm(x, lp["ln2"], self.cfg.rms_norm_eps)
        if kind == "dense":
            h = h32.astype(lp["w_gate"].dtype)
            return gated_ffn(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None
        return self.moe.forward(lp["moe"],
                                h32.astype(lp["moe"]["w_gate"].dtype), h32)

    # ------------------------------------------------------- full forward

    def apply(self, variables, tokens, training=False, rng=None):
        """(B, S) tokens → (B, S, V) float32 logits: every sequence on
        its own, naive attention, no cache."""
        p = variables["params"]
        c = self.cfg
        s = tokens.shape[1]
        pos = jnp.arange(s)
        visible = (pos[None, None, :] <= pos[None, :, None])
        valid = jnp.ones((1, s), bool)

        def one(toks):
            x = p["embed"][toks].astype(jnp.float32)
            for lp, kind in zip(p["layers"], c.layers):
                h = rms_norm(x, lp["ln1"], c.rms_norm_eps).astype(
                    lp["wq_a"].dtype)
                q_nope, q_rope, rows = self._queries_and_row(lp, h, pos)
                a = self._naive_attention(lp, q_nope, q_rope, rows,
                                          visible, valid)
                x = x + _mm(a.astype(h.dtype), lp["wo"])
                x = x + self._ffn(lp, kind, x)[0]
            h = rms_norm(x, p["norm"], c.rms_norm_eps)
            return _mm(h.astype(p["head"].dtype), p["head"])

        return jax.lax.map(one, tokens), variables.get("state", {})

    # ------------------------------------------------------ the paged trio

    def init_block_pool(self, num_blocks: int, block_size: int,
                        dtype=jnp.float32, slots: int = 1):
        """Per-layer latent pools: a TUPLE of L dicts {'kv'}, each
        (num_blocks, block_size, W): blocks are axis 0 and block 0 is
        scratch (ops/kv_cache.init_block_pool); `slots` is not used.
        A row is `[c_kv ; k_rope ; zeros]`, W = rank + rope rounded up
        to whole 128-lane tiles (576 -> 640). One leaf a layer, because the decode score
        contracts the row whole; padded, because a v5e lays
        `bf16[N, 16, 576]` out with the BLOCK dimension minor-most
        rather than pad 576 lanes, and both programs then transpose
        every leaf on the way in and again for the donated output (so
        it does a 64-wide leaf of k_rope alone; `[N, 8, 1152]`, two
        tokens a row, is unpadded and block-major but its gathered
        table needs a lane-splitting relayout every step). The zeros
        cost a ninth of the pool and of its reads; the compiler keeps
        this shape block-major and updates it in place
        (tests/bench/test_aot_mla_moe.py)."""
        return tuple(
            {"kv": init_row_pool(num_blocks, block_size, self.pool_width,
                                 dtype)}
            for _ in self.cfg.layers)

    def prefill_paged(self, variables, tokens, pools, table, block_ids,
                      start):
        """As `TransformerLM.prefill_paged`: ONE request's suffix
        (1, bucket) at positions [start, start + bucket) written into
        `block_ids` and attended through the slot's whole `table`
        (1, max_blocks) with the mask j <= start + i. Returns the
        pools; the engine re-decodes the last prompt token."""
        p = variables["params"] if "params" in variables else variables
        c = self.cfg
        if tokens.shape[0] != 1:
            raise ValueError("prefill_paged fills one request (batch 1), "
                             f"got batch {tokens.shape[0]}")
        s = tokens.shape[1]
        start = jnp.asarray(start, jnp.int32)
        pos = start + jnp.arange(s)
        jpos = jnp.arange(table.shape[1] * pools[0]["kv"].shape[1])
        visible = jpos[None, None, :] <= pos[None, :, None]  # (1, s, S)
        valid = jpos[None, :] < start + s                    # (1, S)
        x = p["embed"][tokens[0]].astype(jnp.float32)
        new_pools = []
        for lp, kind, pl in zip(p["layers"], c.layers, pools):
            h = rms_norm(x, lp["ln1"], c.rms_norm_eps).astype(
                lp["wq_a"].dtype)
            q_nope, q_rope, row = self._queries_and_row(lp, h, pos)
            pool = write_prompt_rows(pl["kv"], row, block_ids)
            new_pools.append({"kv": pool})
            a = self._naive_attention(
                lp, q_nope, q_rope, gather_block_rows(pool, table)[0],
                visible, valid)
            x = x + _mm(a.astype(h.dtype), lp["wo"])
            x = x + self._ffn(lp, kind, x)[0]
        return tuple(new_pools)

    def decode_step_paged(self, variables, tokens, pos, pools, table):
        """As `TransformerLM.decode_step_paged`: tokens/pos (B,), table
        (B, max_blocks); writes each row's latent at (table[pos // bs],
        pos % bs), attends in the absorbed form. Returns (logits
        (B, V) float32, pools, aux): `aux` is int32 (MoE layers, E),
        the tokens each expert got in this step."""
        p = variables["params"] if "params" in variables else variables
        c = self.cfg
        b = tokens.shape[0]
        bs = pools[0]["kv"].shape[1]
        block_ids = table[jnp.arange(b), pos // bs]
        offsets = pos % bs
        heads, nope = c.num_attention_heads, c.qk_nope_head_dim
        x = p["embed"][tokens].astype(jnp.float32)
        new_pools, counts = [], []
        for lp, kind, pl in zip(p["layers"], c.layers, pools):
            dt = lp["wq_a"].dtype
            h = rms_norm(x, lp["ln1"], c.rms_norm_eps).astype(dt)
            q_nope, q_rope, row = self._queries_and_row(lp, h, pos)
            pool = write_decode_rows(pl["kv"], row, block_ids, offsets)
            new_pools.append({"kv": pool})
            w_ukv = lp["wkv_b"].reshape(c.kv_lora_rank, heads, -1)
            q_lat = jnp.einsum("bhn,chn->bhc", q_nope.astype(dt),
                               w_ukv[..., :nope],
                               preferred_element_type=jnp.float32)
            o_lat = latent_paged_attention(q_lat, q_rope, pool, table,
                                           pos, c.kv_lora_rank,
                                           self.sm_scale)
            o = jnp.einsum("bhc,chv->bhv", o_lat.astype(dt),
                           w_ukv[..., nope:],
                           preferred_element_type=jnp.float32)
            x = x + _mm(o.reshape(b, -1).astype(dt), lp["wo"])
            y, n = self._ffn(lp, kind, x)
            x = x + y
            if n is not None:
                counts.append(n)
        h = rms_norm(x, p["norm"], c.rms_norm_eps)
        logits = _mm(h.astype(p["head"].dtype), p["head"])
        return logits, tuple(new_pools), jnp.stack(counts)
