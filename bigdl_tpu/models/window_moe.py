"""A decoder language model given as a LIST OF LAYER KINDS, each kind
saying how the layer attends AND what its feed-forward is: layers that
attend a sliding window mixed with layers that attend everything,
grouped-query heads with per-head q/k norms and an output gate, four
norms a layer, dropless sigmoid-routed experts with a shared one. The
`afmoe` family (Arcee's Trinity models), whose `config.json` keys the
configuration below keeps under their own names; what the config does
not carry (the gate, the q/k norms, no rotation on full layers, the
norms' places, the half-split rotary pairs, the selection bias) is the
family's public modeling code's.

No reference counterpart (the reference has no language model with a
cache). The serving side only, through the same paged trio as
`TransformerLM` and `LatentMoELM` (`init_block_pool`, `prefill_paged`,
`decode_step_paged`), so `InferenceEngine` and `EngineRouter` serve it
with no branch of their own; `apply` is the plain full-sequence
forward. Training (a loss, a flash kernel with a window) is ROADMAP
B-I.

With T tokens, d = hidden_size, Hq query heads over G key-value heads
of D (query head h reads key-value head h // (Hq/G)):

  x = Emb[tok] * sqrt(d)                                  (mup_enabled)
  every layer, attention kind a, feed-forward kind f:
    h = RMSNorm_in(x);  q = h W_q, k = h W_k, v = h W_v, g = h W_g
    q <- RMSNorm_q(q), k <- RMSNorm_k(k): over a head's D numbers
    a = sliding_attention: q, k <- RoPE(q, k), the pairs (i, i + D/2);
        key j visible to query i iff j <= i and i - j < sliding_window
    a = full_attention:    NO rotation; key j visible iff j <= i
    o = softmax(q . k / sqrt(D)) @ v;   o <- o * sigmoid(g)
    x <- x + RMSNorm_post_attn(o W_o)
    h2 = RMSNorm_pre_mlp(x)
    f = dense: (silu(h2 W_gate) * h2 W_up) W_down
    f = moe:   `parallel/moe.DroplessMoE` (sigmoid scores, top-k of
               s + b, weights s / sum * route_scale, a shared expert)
    x <- x + RMSNorm_post_mlp(f)
  logits = RMSNorm(x) W_head, untied. No bias anywhere.

WHAT A TOKEN LEAVES IN THE CACHE, a layer: `k` after its norm (and
after RoPE on a sliding layer) and `v`, G * D lanes each. WHERE, by the
layer's attention kind, which the engine asks of `cache_kinds()`:

  table  a full layer: rows in blocks that the slot's block table
         names, as every other model's; they grow with the context.
  ring   a sliding layer: slot s owns `window / block_size + 1` blocks
         of the leaf for good (`ops/kv_cache.init_ring_pool`) and the
         block of positions b overwrites that of b - ring_blocks. No
         table, no allocator, nothing to release; a slot never holds
         more than window + one block of rows, however long it runs,
         and a decode step reads no more.

Both kinds are read by the one core of the decode read
(`ops/kv_cache.grouped_paged_attention` over `_ragged_attention`): a
ring through the table of its window (`ring_window`), with the lower
bound of visibility. Prefill attends the prompt's own keys and values,
a block of queries at a time (a full layer's scores over the whole
extent are never held), a sliding layer's over the keys its window
reaches; it writes a full layer's rows whole and a sliding layer's last
window only. The prompt always starts at position 0: this model
refuses the prefix cache (`check_serving_options`), the one source of
another start.

Precision: as `LatentMoELM`: weights in the dtype they are given in,
matmul operands in that dtype with float32 accumulation, the residual
stream, norm statistics, RoPE, softmax and the router in float32.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.models.latent_moe import (_mm, require_source_values,
                                         rms_norm)
from bigdl_tpu.nn.module import Module
from bigdl_tpu.ops.kv_cache import (attended_blocks,
                                    grouped_paged_attention,
                                    init_ring_pool, init_row_pool,
                                    ring_window, ring_write_blocks,
                                    write_decode_rows, write_prompt_ring,
                                    write_prompt_rows)
from bigdl_tpu.parallel.moe import DroplessMoE, ExpertsReport, gated_ffn
from bigdl_tpu.serving.protocol import ServedModel

ATTENTION_KINDS = ("sliding_attention", "full_attention")
FFN_KINDS = ("dense", "moe")
_NEG_INF = -1e30
# queries a block of the prefill's attention: a full layer's float32
# scores are (Hq, 256, bucket) at a time, 200 MB at a 6,144 bucket
_QUERY_BLOCK = 256


@dataclass(frozen=True)
class WindowMoEConfig:
    """`layers` is the model: (attention kind, feed-forward kind) per
    layer. The rest are the source's widths under the source's names."""
    layers: Tuple[Tuple[str, str], ...]
    vocab_size: int
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    sliding_window: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_shared_experts: int = 1
    route_scale: float = 1.0
    route_norm: bool = True
    mup_enabled: bool = False
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 4096

    def __post_init__(self):
        bad = [k for k in self.layers if len(k) != 2
               or k[0] not in ATTENTION_KINDS or k[1] not in FFN_KINDS]
        if bad or not self.layers:
            raise ValueError(
                f"layers {self.layers!r}: each one of {ATTENTION_KINDS} "
                f"with one of {FFN_KINDS}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide "
                f"over {self.num_key_value_heads} key-value heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (RoPE pairs)")

    @property
    def max_len(self) -> int:
        """No positional table: RoPE reaches as far as the source says."""
        return self.max_position_embeddings

    @classmethod
    def from_source(cls, cfg: dict) -> "WindowMoEConfig":
        """From a `config.json` of the family (its keys as they are)."""
        only = {"rope_scaling": None, "n_group": 1, "topk_group": 1,
                "num_expert_groups": 1, "num_limited_groups": 1,
                "score_func": "sigmoid", "hidden_act": "silu",
                "tie_word_embeddings": False}
        require_source_values(cfg, only)
        kinds = cfg["layer_types"]
        if len(kinds) != cfg["num_hidden_layers"]:
            raise ValueError(
                f"{len(kinds)} layer_types for num_hidden_layers="
                f"{cfg['num_hidden_layers']}")
        layers = tuple(
            (kind, "dense" if i < cfg["num_dense_layers"] else "moe")
            for i, kind in enumerate(kinds))
        names = [f for f in cls.__dataclass_fields__ if f != "layers"]
        return cls(layers=layers, **{k: cfg[k] for k in names if k in cfg})


def rope_half_split(x, pos, theta):
    """Rotate the pairs (i, i + d/2) of the last axis by
    pos * theta^(-2i/d) (`rotate_half`): x (T, H, d), pos (T,), float32
    out. Not `latent_moe.rope_interleaved`'s pairs (2i, 2i + 1)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (pos.astype(jnp.float32)[:, None] * inv)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def grouped_prompt_attention(q, k, v, kv_heads: int, sm_scale: float,
                             window=None):
    """Causal (and, with a `window`, windowed) attention of one
    sequence over its own keys, grouped-query heads: q (S, Hq, D), k
    and v (S, G, D) in the compute dtype → (S, Hq * D) float32. A block
    of queries at a time, against all S keys without a window and
    against the `window + block` keys that end at the block's last
    query with one; the mask is on positions either way."""
    s, hq, dh = q.shape
    g = kv_heads
    # every prefill bucket is whole blocks; another length (a test's
    # `apply`) is one block
    qb = s if s % _QUERY_BLOCK else _QUERY_BLOCK
    sliding = window is not None
    extent = min(s, window + qb) if sliding else s
    q = q.reshape(s // qb, qb, g, hq // g, dh)

    def block(args):
        i, qi = args                            # qi (qb, G, R, D)
        lo = jnp.clip(i * qb + qb - extent, 0, s - extent)
        ks = jax.lax.dynamic_slice_in_dim(k, lo, extent)
        vs = jax.lax.dynamic_slice_in_dim(v, lo, extent)
        sc = jnp.einsum("qgrd,kgd->grqk", qi, ks,
                        preferred_element_type=jnp.float32) * sm_scale
        iq = (i * qb + jnp.arange(qb))[:, None]
        jk = (lo + jnp.arange(extent))[None, :]
        visible = jk <= iq
        if sliding:
            visible &= iq - jk < window
        sc = jnp.where(visible, sc, _NEG_INF)
        p = jnp.exp(sc - jnp.max(sc, -1, keepdims=True))
        p = p / jnp.sum(p, -1, keepdims=True)
        o = jnp.einsum("grqk,kgd->qgrd", p.astype(vs.dtype), vs,
                       preferred_element_type=jnp.float32)
        return o.reshape(qb, hq * dh)

    out = jax.lax.map(block, (jnp.arange(s // qb), q))
    return out.reshape(s, hq * dh)


class WindowMoELM(ExpertsReport, Module, ServedModel):
    """See the module docstring. Parameters are per layer from the
    start, as `LatentMoELM`'s: `{"embed" (V, d), "head" (d, V), "norm"
    (d,), "layers": (dict,) * L}`, every matrix (in, out)."""

    def __init__(self, config: WindowMoEConfig, name=None):
        super().__init__(name=name)
        c = self.cfg = config
        self.moe = DroplessMoE(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok,
            shared_hidden=c.num_shared_experts * c.moe_intermediate_size,
            scale=c.route_scale, normalize=c.route_norm)
        # a token's key (or value) row: the G heads side by side
        self.row_width = c.num_key_value_heads * c.head_dim
        self.sm_scale = c.head_dim ** -0.5
        self.embed_scale = c.hidden_size ** 0.5 if c.mup_enabled else 1.0

    # ------------------------------------------------------------ weights

    def init_params(self, rng, std: float = 0.02, dtype=jnp.float32):
        c = self.cfg
        d, hq, dh = c.hidden_size, c.num_attention_heads, c.head_dim
        keys = iter(jax.random.split(rng, 16 * len(c.layers) + 2))

        def w(*shape):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        def ones(n):
            return jnp.ones((n,), jnp.float32)

        def layer(ffn):
            lp = {"ln_in": ones(d), "wq": w(d, hq * dh),
                  "wk": w(d, self.row_width), "wv": w(d, self.row_width),
                  "wg": w(d, hq * dh), "q_norm": ones(dh),
                  "k_norm": ones(dh), "wo": w(hq * dh, d),
                  "ln_post_attn": ones(d), "ln_pre_mlp": ones(d),
                  "ln_post_mlp": ones(d)}
            if ffn == "dense":
                f = c.intermediate_size
                lp.update(w_gate=w(d, f), w_up=w(d, f), w_down=w(f, d))
            else:
                e, f = c.num_experts, c.moe_intermediate_size
                fs = c.num_shared_experts * f
                lp["moe"] = {
                    "router": w(d, e).astype(jnp.float32),
                    "router_bias": w(e).astype(jnp.float32),
                    "w_gate": w(e, d, f), "w_up": w(e, d, f),
                    "w_down": w(e, f, d), "ws_gate": w(d, fs),
                    "ws_up": w(d, fs), "ws_down": w(fs, d)}
            return lp

        return {"embed": w(c.vocab_size, d), "head": w(d, c.vocab_size),
                "norm": ones(d),
                "layers": tuple(layer(ffn) for _, ffn in c.layers)}

    # ------------------------------------------------------- layer pieces

    def _embed(self, p, tokens):
        return p["embed"][tokens].astype(jnp.float32) * self.embed_scale

    def _projections(self, lp, x, pos, kind):
        """x (T, d) float32 residual, pos (T,) → q (T, Hq, D), k and v
        (T, G, D) float32, normed and (sliding layers) rotated, and the
        gate's pre-activation (T, Hq * D) float32."""
        c = self.cfg
        t = x.shape[0]
        h = rms_norm(x, lp["ln_in"], c.rms_norm_eps).astype(lp["wq"].dtype)
        q = rms_norm(_mm(h, lp["wq"]).reshape(t, -1, c.head_dim),
                     lp["q_norm"], c.rms_norm_eps)
        k = rms_norm(_mm(h, lp["wk"]).reshape(t, -1, c.head_dim),
                     lp["k_norm"], c.rms_norm_eps)
        v = _mm(h, lp["wv"]).reshape(t, -1, c.head_dim)
        if kind == "sliding_attention":
            q = rope_half_split(q, pos, c.rope_theta)
            k = rope_half_split(k, pos, c.rope_theta)
        return q, k, v, _mm(h, lp["wg"])

    def _after_attention(self, lp, x, o, gate):
        """o (T, Hq * D) float32 attention output → the residual after
        the gate, W_o and the post-attention norm."""
        c = self.cfg
        o = (o * jax.nn.sigmoid(gate)).astype(lp["wo"].dtype)
        return x + rms_norm(_mm(o, lp["wo"]), lp["ln_post_attn"],
                            c.rms_norm_eps)

    def _ffn(self, lp, ffn, x):
        """x (T, d) float32 residual → (the residual after the layer's
        feed-forward and its norm, the tokens each expert got or
        None)."""
        c = self.cfg
        h32 = rms_norm(x, lp["ln_pre_mlp"], c.rms_norm_eps)
        if ffn == "dense":
            f, n = gated_ffn(h32.astype(lp["w_gate"].dtype), lp["w_gate"],
                             lp["w_up"], lp["w_down"]), None
        else:
            f, n = self.moe.forward(
                lp["moe"], h32.astype(lp["moe"]["w_gate"].dtype), h32)
        return x + rms_norm(f, lp["ln_post_mlp"], c.rms_norm_eps), n

    def _prompt_attention(self, q, k, v, kind):
        c = self.cfg
        return grouped_prompt_attention(
            q, k, v, c.num_key_value_heads, self.sm_scale,
            c.sliding_window if kind == "sliding_attention" else None)

    # ------------------------------------------------------- full forward

    def apply(self, variables, tokens, training=False, rng=None):
        """(B, S) tokens → (B, S, V) float32 logits: every sequence on
        its own, no cache."""
        p = variables["params"]
        c = self.cfg
        pos = jnp.arange(tokens.shape[1])

        def one(toks):
            x = self._embed(p, toks)
            for lp, (kind, ffn) in zip(p["layers"], c.layers):
                dt = lp["wq"].dtype
                q, k, v, gate = self._projections(lp, x, pos, kind)
                o = self._prompt_attention(q.astype(dt), k.astype(dt),
                                           v.astype(dt), kind)
                x = self._after_attention(lp, x, o, gate)
                x = self._ffn(lp, ffn, x)[0]
            h = rms_norm(x, p["norm"], c.rms_norm_eps)
            return _mm(h.astype(p["head"].dtype), p["head"])

        return jax.lax.map(one, tokens), variables.get("state", {})

    # ------------------------------------------------------ the paged trio

    def cache_kinds(self) -> Tuple[str, ...]:
        """What the engine asks instead of a model's name: for each
        entry of `init_block_pool`'s tuple, "table" (rows in blocks the
        slot's table names) or "ring" (rows in the slot's own ring)."""
        return tuple("ring" if kind == "sliding_attention" else "table"
                     for kind, _ in self.cfg.layers)

    def ring_blocks(self, block_size: int) -> int:
        """Blocks of a slot's ring: the window and one block more, so
        that the newest position's window is held whole while its
        block fills."""
        if self.cfg.sliding_window % block_size:
            raise ValueError(
                f"sliding_window {self.cfg.sliding_window} is no multiple "
                f"of block_size {block_size}")
        return self.cfg.sliding_window // block_size + 1

    def init_block_pool(self, num_blocks: int, block_size: int,
                        dtype=jnp.float32, slots: int = 1):
        """Per-layer pools: a TUPLE of L dicts {'k', 'v'}, each leaf
        (blocks, block_size, G * D): whole 128-lane tiles at D = 128,
        block-major (ops/kv_cache.init_block_pool), block 0 scratch. A
        "table" layer's leaves have `num_blocks` blocks; a "ring"
        layer's `1 + slots * ring_blocks` (init_ring_pool), whatever
        `num_blocks` is."""
        ring = self.ring_blocks(block_size)

        def leaf(kind):
            if kind == "ring":
                return init_ring_pool(slots, ring, block_size,
                                      self.row_width, dtype)
            return init_row_pool(num_blocks, block_size, self.row_width,
                                 dtype)

        return tuple({"k": leaf(kind), "v": leaf(kind)}
                     for kind in self.cache_kinds())

    def prefill_paged(self, variables, tokens, pools, table, block_ids,
                      start):
        """ONE request's prompt (1, bucket), padded, at positions
        [0, bucket): attended over its own keys and values, written
        into the pools. `block_ids` says where, by cache kind:
        {"table": (bucket / bs,) the slot's fresh blocks, "ring":
        {"slot": the slot, "sources": (ring_blocks,) the prompt's block
        each ring block takes, ops/kv_cache.ring_prompt_sources}}.
        `table` and `start` are the trio's and not read: the prompt
        starts at 0 (module docstring) and attends nothing but itself.
        Returns the pools; the engine re-decodes the last prompt
        token."""
        p = variables["params"] if "params" in variables else variables
        c = self.cfg
        if tokens.shape[0] != 1:
            raise ValueError("prefill_paged fills one request (batch 1), "
                             f"got batch {tokens.shape[0]}")
        ring = block_ids["ring"]
        pos = jnp.arange(tokens.shape[1])
        x = self._embed(p, tokens[0])
        new_pools = []
        for lp, (kind, ffn), pl in zip(p["layers"], c.layers, pools):
            dt = lp["wq"].dtype
            q, k, v, gate = self._projections(lp, x, pos, kind)
            q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
            rows = {"k": k.reshape(-1, self.row_width),
                    "v": v.reshape(-1, self.row_width)}
            if kind == "sliding_attention":
                new_pools.append({
                    n: write_prompt_ring(pl[n], rows[n], ring["slot"],
                                         ring["sources"]) for n in rows})
            else:
                new_pools.append({
                    n: write_prompt_rows(pl[n], rows[n],
                                         block_ids["table"])
                    for n in rows})
            x = self._after_attention(
                lp, x, self._prompt_attention(q, k, v, kind), gate)
            x = self._ffn(lp, ffn, x)[0]
        return tuple(new_pools)

    def decode_step_paged(self, variables, tokens, pos, pools, table):
        """As `TransformerLM.decode_step_paged`: tokens/pos (B,), table
        (B, max_blocks), ROW b OF THE BATCH IS SLOT b (as the engine
        calls it: a ring is found by its slot). Writes each row's key
        and value at (table[pos // bs], pos % bs) of a full layer and
        at its ring block of a sliding one, attends each slot's own
        live rows. Returns (logits (B, V) float32, pools, aux): `aux`
        is int32 (MoE layers, E), the tokens each expert got."""
        p = variables["params"] if "params" in variables else variables
        c = self.cfg
        b = tokens.shape[0]
        bs = pools[0]["k"].shape[1]
        ring = self.ring_blocks(bs)
        seated = table[:, 0] != 0
        offsets = pos % bs
        ids = {"full_attention": table[jnp.arange(b), pos // bs],
               "sliding_attention": ring_write_blocks(pos, seated, bs,
                                                      ring)}
        win_table, win_pos, win_lo = ring_window(pos, seated, bs, ring,
                                                 c.sliding_window)
        x = self._embed(p, tokens)
        new_pools, counts = [], []
        for lp, (kind, ffn), pl in zip(p["layers"], c.layers, pools):
            q, k, v, gate = self._projections(lp, x, pos, kind)
            kp = write_decode_rows(pl["k"], k.reshape(b, -1), ids[kind],
                                   offsets)
            vp = write_decode_rows(pl["v"], v.reshape(b, -1), ids[kind],
                                   offsets)
            new_pools.append({"k": kp, "v": vp})
            if kind == "sliding_attention":
                o = grouped_paged_attention(
                    q, kp, vp, win_table, win_pos, c.num_key_value_heads,
                    self.sm_scale, lo=win_lo)
            else:
                o = grouped_paged_attention(
                    q, kp, vp, table, pos, c.num_key_value_heads,
                    self.sm_scale)
            x = self._after_attention(lp, x, o.reshape(b, -1), gate)
            x, n = self._ffn(lp, ffn, x)
            if n is not None:
                counts.append(n)
        h = rms_norm(x, p["norm"], c.rms_norm_eps)
        logits = _mm(h.astype(p["head"].dtype), p["head"])
        return logits, tuple(new_pools), jnp.stack(counts)

    # ------------------------------------------------- what the spans say

    def decode_read_report(self, pos, table, block_size: int) -> dict:
        """What a decode step at these clocks (host, NumPy: `pos` (B,),
        `table` (B, max_blocks) with an unseated slot's row zero) reads
        of the cache, for the engine's `decode_step` span:
        `window_rows` and `full_rows`, the rows the mask lets the
        step's queries see, summed over the seated slots, ONE layer of
        each kind; `attended_rows`, the rows the program gathers,
        summed over the layers, from the roundings the program itself
        uses (ops/kv_cache.attended_blocks over `ring_window`)."""
        pos, table = np.asarray(pos), np.asarray(table)
        seated = table[:, 0] != 0
        kinds = self.cache_kinds()
        win_table, win_pos, _ = ring_window(
            pos, seated, block_size, self.ring_blocks(block_size),
            self.cfg.sliding_window)
        read = {"ring": attended_blocks(win_pos, win_table, block_size),
                "table": attended_blocks(pos, table, block_size)}
        return {
            "window_rows": int(np.minimum(
                pos + 1, self.cfg.sliding_window)[seated].sum()),
            "full_rows": int((pos + 1)[seated].sum()),
            "attended_rows": int(block_size * sum(
                read[kind] for kind in kinds))}
