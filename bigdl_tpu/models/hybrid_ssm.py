"""A decoder language model given as a LIST OF LAYER KINDS whose layers
are a Mamba-2 state-space mixer or grouped-query attention with NO
position signal, each followed by one gated MLP: the `granitemoehybrid`
family at `num_local_experts` 0 (IBM's Granite 4.0-H; Mamba-2 is Dao &
Gu, arXiv:2405.21060), whose `config.json` keys the configuration below
keeps under their own names.

No reference counterpart (the reference has no language model with a
cache). The serving side only, through the same paged trio as the other
list models (`init_block_pool`, `prefill_paged`, `decode_step_paged`);
`apply` is the plain full-sequence forward. Training (the scan's
backward) is ROADMAP B-I.

With d = hidden_size, t the position and every row before position 0
zero:

  the stream, float32:
    x = embedding_multiplier * Emb[tok]; for every layer
      x <- x + residual_multiplier * Mixer(RMSNorm(x))
      x <- x + residual_multiplier * W_out(silu(g) * v),
           [g ; v] = W_in RMSNorm(x), each shared_intermediate_size
    logits = RMSNorm(x) Emb^T / logits_scaling (a TIED head)

  `attention` on u_t: q = u W_q in Hq heads of D = d / Hq, k and v in G
    heads (query head h reads key-value head h // (Hq / G)); no
    rotation, no learned position;
    y = W_o softmax_causal(attention_multiplier * q k^T) v: the scale
    is the multiplier, not 1 / sqrt(D).

  `mamba` on u_t, H heads of P, a state of N a head number, K taps:
    [z ; xBC ; dt] = u W_inproj            (H P ; H P + 2 N ; H)
    xBC_t <- silu(sum_j w[j] * xBC_{t-(K-1)+j} + b)      depthwise
    [x ; B ; C] = xBC;  Delta_t = softplus(dt_t + dt_bias)
    h_t = exp(Delta_t A) h_{t-1} + Delta_t * x_t (x) B_t, A = -exp(A_log)
    y_t = h_t C_t + D * x_t;  y <- RMSNorm(y * silu(z)) * w_norm (the
    gate first, one group);  out = y W_outproj
    (`ops/ssm.py`: a prompt in chunks of mamba_chunk_size, a decode
    step one token for every slot.)

WHAT A TOKEN LEAVES IN THE CACHE: in an attention layer its key and
value rows, G * D lanes each, in blocks the slot's table names (a
"table" entry of `cache_kinds()`, read by
`ops/kv_cache.grouped_paged_attention`); in a mamba layer NOTHING.
WHAT A SLOT KEEPS, a mamba layer (a "state" entry: no position axis,
row b is slot b): `h` (slots, H, P, N) float32, the recurrence's
state, and `taps` (slots, K - 1, H P + 2 N) in the cache's dtype, the
last K - 1 rows before the convolution. A layer has ONE entry, of its
kind, in layer order. Prefill scans the prompt from a ZERO state (not
from what the slot's last tenant left) and writes the slot's whole row
of both leaves as they stand after the prompt's last-but-one position
(the engine re-decodes the last prompt token); decode reads the seated
slots' rows and rewrites them every step, and leaves the others' bits
alone; a released slot's row stays until the next prefill (the engine
scrubs a poisoned request's). A prompt always starts at position 0:
this model refuses the prefix cache (`check_serving_options`).

Precision: weights in the dtype they are given in, matmul operands in
that dtype with float32 accumulation (the scan's too); the stream, the
convolution, Delta, the decays, the recurrence's state `h`, norms and
softmax in float32. `h` is float32 WHATEVER the cache's dtype: the
recurrence adds increments Delta * x * B, Delta near 1e-2, to a state
it carries for every token of a session.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.models.latent_moe import (_mm, require_source_values,
                                         rms_norm)
from bigdl_tpu.models.window_moe import grouped_prompt_attention
from bigdl_tpu.nn.module import Module
from bigdl_tpu.ops.kv_cache import (attended_blocks, grouped_paged_attention,
                                    init_row_pool, write_decode_rows,
                                    write_prompt_rows)
from bigdl_tpu.ops.ssm import causal_conv, conv_taps, ssd_chunked, ssm_step
from bigdl_tpu.serving.protocol import ServedModel

LAYER_KINDS = ("mamba", "attention")


@dataclass(frozen=True)
class HybridSSMConfig:
    """`layers` is the model: one kind per layer. The rest are the
    source's widths and multipliers under the source's names."""
    layers: Tuple[str, ...]
    vocab_size: int
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    shared_intermediate_size: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 1.0
    logits_scaling: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 4096

    def __post_init__(self):
        bad = [k for k in self.layers if k not in LAYER_KINDS]
        if bad or not self.layers:
            raise ValueError(f"layers {self.layers!r}: each one of "
                             f"{LAYER_KINDS}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"{self.num_attention_heads} heads do not divide the "
                f"hidden size {self.hidden_size}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide "
                f"over {self.num_key_value_heads} key-value heads")
        if self.mamba_d_conv < 2:
            raise ValueError(f"mamba_d_conv {self.mamba_d_conv}: the slot "
                             "keeps the K - 1 rows before a token")

    @property
    def max_len(self) -> int:
        """No positional table and no rotation: as far as the source
        says."""
        return self.max_position_embeddings

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def from_source(cls, cfg: dict) -> "HybridSSMConfig":
        """From a `config.json` of the family (its keys as they are);
        what this model does not build is refused by name."""
        only = {"num_local_experts": 0, "num_experts_per_tok": 0,
                "mamba_n_groups": 1, "rope_scaling": None,
                "position_embedding_type": "nope",
                "tie_word_embeddings": True, "attention_bias": False,
                "mamba_proj_bias": False, "mamba_conv_bias": True,
                "hidden_act": "silu", "normalization_function": "rmsnorm"}
        require_source_values(cfg, only)
        kinds = tuple(cfg["layer_types"])
        if len(kinds) != cfg["num_hidden_layers"]:
            raise ValueError(
                f"{len(kinds)} layer_types for num_hidden_layers="
                f"{cfg['num_hidden_layers']}")
        inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
        if inner != cfg.get("mamba_expand", 2) * cfg["hidden_size"]:
            raise ValueError(
                f"mamba_n_heads x mamba_d_head = {inner} is not "
                "mamba_expand x hidden_size")
        names = [f for f in cls.__dataclass_fields__ if f != "layers"]
        return cls(layers=kinds, **{k: cfg[k] for k in names if k in cfg})


class HybridSSMLM(Module, ServedModel):
    """See the module docstring. Parameters are per layer from the
    start: `{"embed" (V, d), "norm" (d,), "layers": (dict,) * L}`, every
    matrix (in, out); a layer has `ln_mixer`, `ln_mlp`, `w_in`
    (d, 2 F), `w_out` (F, d) and its mixer's: `in_proj`, `conv_w`
    (K, C), `conv_b`, `A_log`, `D`, `dt_bias`, `gate_norm`, `out_proj`
    (mamba) or `wq`, `wk`, `wv`, `wo` (attention)."""

    def __init__(self, config: HybridSSMConfig, name=None):
        super().__init__(name=name)
        c = self.cfg = config
        # a token's key (or value) row: the G heads side by side
        self.row_width = c.num_key_value_heads * c.head_dim
        self.inner = c.mamba_n_heads * c.mamba_d_head
        # the channels the convolution mixes: x, B and C
        self.conv_width = self.inner + 2 * c.mamba_d_state
        self.state_shape = (c.mamba_n_heads, c.mamba_d_head,
                            c.mamba_d_state)
        self.n_mamba = sum(k == "mamba" for k in c.layers)

    # ------------------------------------------------------------ weights

    def init_params(self, rng, std: float = 0.02, dtype=jnp.float32):
        c = self.cfg
        d, f, h = c.hidden_size, c.shared_intermediate_size, c.mamba_n_heads
        keys = iter(jax.random.split(rng, 16 * len(c.layers) + 4))

        def w(*shape):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        def uniform(lo, hi, *shape):
            return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

        def ones(n):
            return jnp.ones((n,), jnp.float32)

        def layer(kind):
            lp = {"ln_mixer": ones(d), "ln_mlp": ones(d),
                  "w_in": w(d, 2 * f), "w_out": w(f, d)}
            if kind == "mamba":
                k = c.mamba_d_conv
                dt = jnp.exp(uniform(np.log(1e-3), np.log(1e-1), h))
                lp.update(
                    in_proj=w(d, self.inner + self.conv_width + h),
                    conv_w=uniform(-k ** -0.5, k ** -0.5, k,
                                   self.conv_width),
                    conv_b=uniform(-k ** -0.5, k ** -0.5, self.conv_width),
                    A_log=jnp.log(uniform(1.0, 16.0, h)),
                    dt_bias=dt + jnp.log(-jnp.expm1(-dt)), D=ones(h),
                    gate_norm=ones(self.inner), out_proj=w(self.inner, d))
            else:
                lp.update(wq=w(d, d), wk=w(d, self.row_width),
                          wv=w(d, self.row_width), wo=w(d, d))
            return lp

        return {"embed": w(c.vocab_size, d), "norm": ones(d),
                "layers": tuple(layer(k) for k in c.layers)}

    # ------------------------------------------------------- layer pieces

    def _in_proj(self, lp, u):
        """u (T, d) float32 → z (T, H P), xBC (T, C) before the
        convolution, Delta (T, H), float32."""
        zxbcdt = _mm(u.astype(lp["in_proj"].dtype), lp["in_proj"])
        split = self.inner + self.conv_width
        return (zxbcdt[:, :self.inner], zxbcdt[:, self.inner:split],
                jax.nn.softplus(zxbcdt[:, split:] + lp["dt_bias"]))

    def _out_proj(self, lp, y, z):
        """The gated norm (the gate first) and the projection back."""
        y = rms_norm(y.reshape(z.shape) * jax.nn.silu(z), lp["gate_norm"],
                     self.cfg.rms_norm_eps)
        return _mm(y.astype(lp["out_proj"].dtype), lp["out_proj"])

    def _mamba_sequence(self, lp, u, keep):
        """The mixer over ONE sequence u (T, d) from position 0 and a
        zero state → (its output (T, d), the state after position
        `keep`: h (H, P, N) float32 and the K - 1 rows before the
        convolution that end there)."""
        c = self.cfg
        t = u.shape[0]
        with jax.named_scope("ssm_conv"):
            z, xbc, dt = self._in_proj(lp, u)
            taps = conv_taps(xbc, keep, c.mamba_d_conv - 1)
            conv = causal_conv(xbc, lp["conv_w"], lp["conv_b"])
        with jax.named_scope("ssm"):
            n = c.mamba_d_state
            x = conv[:, :self.inner].reshape(t, *self.state_shape[:2])
            b, cc = conv[:, self.inner:self.inner + n], \
                conv[:, self.inner + n:]
            # a length that is no whole chunks (a test's `apply`): padded
            # behind, where Delta is 0 and nothing looks back at it
            pad = -t % c.mamba_chunk_size
            if pad:
                x, b, cc, dt = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (
                    a.ndim - 1)) for a in (x, b, cc, dt))
            y, h = ssd_chunked(x, dt, -jnp.exp(lp["A_log"]), b, cc, lp["D"],
                               keep, c.mamba_chunk_size,
                               dtype=lp["in_proj"].dtype)
            return self._out_proj(lp, y[:t], z), (h, taps)

    def _mamba_step(self, lp, u, state, seated):
        """One token for every slot: u (B, d), state {"h", "taps"} →
        (the mixer's output (B, d), the new state)."""
        with jax.named_scope("ssm_conv"):
            z, xbc, dt = self._in_proj(lp, u)
        with jax.named_scope("ssm"):
            y, h, taps = ssm_step(
                state["h"], state["taps"], xbc, dt, lp["conv_w"],
                lp["conv_b"], -jnp.exp(lp["A_log"]), lp["D"], seated)
            return self._out_proj(lp, y, z), {"h": h, "taps": taps}

    def _qkv(self, lp, u):
        """u (T, d) float32 → q (T, Hq, D), k and v (T, G, D) float32:
        no rotation, no position."""
        c = self.cfg
        t = u.shape[0]
        ub = u.astype(lp["wq"].dtype)
        return (_mm(ub, lp["wq"]).reshape(t, c.num_attention_heads, -1),
                _mm(ub, lp["wk"]).reshape(t, c.num_key_value_heads, -1),
                _mm(ub, lp["wv"]).reshape(t, c.num_key_value_heads, -1))

    def _layer(self, lp, x, mixer):
        """One layer on the stream x (T, d) float32: `mixer(lp, u)` is
        the caller's (what it keeps, and the mixer's output (T, d));
        then the gated MLP. The scopes name the parts' operations in a
        device trace."""
        c = self.cfg
        rm = c.residual_multiplier
        x = x + rm * mixer(lp, rms_norm(x, lp["ln_mixer"], c.rms_norm_eps))
        with jax.named_scope("mlp"):
            u = rms_norm(x, lp["ln_mlp"], c.rms_norm_eps)
            gv = _mm(u.astype(lp["w_in"].dtype), lp["w_in"])
            f = c.shared_intermediate_size
            y = _mm((jax.nn.silu(gv[:, :f]) * gv[:, f:]).astype(
                lp["w_out"].dtype), lp["w_out"])
        return x + rm * y

    def _embed(self, p, toks):
        return self.cfg.embedding_multiplier \
            * p["embed"][toks].astype(jnp.float32)

    def _logits(self, p, x):
        c = self.cfg
        u = rms_norm(x, p["norm"], c.rms_norm_eps)
        # u Emb^T: the embedding is contracted on its own second axis
        return jax.lax.dot_general(
            u.astype(p["embed"].dtype), p["embed"],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / c.logits_scaling

    def _sequence(self, p, toks, keep):
        """One sequence (S,) from position 0 → the stream after the
        last layer and, a layer, what a prefill keeps: (k, v) (S, G, D)
        float32 of an attention layer, (h, taps) after position `keep`
        of a mamba layer."""
        c = self.cfg
        kept = []

        def attend(lp, u):
            with jax.named_scope("attention"):
                q, k, v = self._qkv(lp, u)
                kept.append((k, v))
                dt = lp["wq"].dtype
                o = grouped_prompt_attention(
                    q.astype(dt), k.astype(dt), v.astype(dt),
                    c.num_key_value_heads, c.attention_multiplier)
                return _mm(o.astype(dt), lp["wo"])

        def scan(lp, u):
            y, state = self._mamba_sequence(lp, u, keep)
            kept.append(state)
            return y

        x = self._embed(p, toks)
        for kind, lp in zip(c.layers, p["layers"]):
            x = self._layer(lp, x, scan if kind == "mamba" else attend)
        return x, kept

    # ------------------------------------------------------- full forward

    def apply(self, variables, tokens, training=False, rng=None):
        """(B, S) tokens → (B, S, V) float32 logits: every sequence on
        its own, no cache, no state kept."""
        p = variables["params"]
        last = jnp.int32(tokens.shape[1] - 1)
        return (jax.lax.map(
            lambda t: self._logits(p, self._sequence(p, t, last)[0]),
            tokens), variables.get("state", {}))

    # ------------------------------------------------------ the paged trio

    def cache_kinds(self) -> Tuple[str, ...]:
        """For each entry of `init_block_pool`'s tuple, ONE a layer in
        layer order: "state" for a mamba layer, "table" for an
        attention layer."""
        return tuple("state" if k == "mamba" else "table"
                     for k in self.cfg.layers)

    def init_block_pool(self, num_blocks: int, block_size: int,
                        dtype=jnp.float32, slots: int = 1):
        """One entry a layer. Attention: {'k', 'v'}, each (num_blocks,
        block_size, G * D) block-major with block 0 scratch
        (ops/kv_cache.init_row_pool). Mamba: {'h'} (slots, H, P, N)
        float32 whatever `dtype` is, and {'taps'} (slots, K - 1, C) in
        `dtype`: row b is slot b's."""
        c = self.cfg

        def entry(kind):
            if kind == "mamba":
                return {"h": jnp.zeros((slots,) + self.state_shape,
                                       jnp.float32),
                        "taps": jnp.zeros((slots, c.mamba_d_conv - 1,
                                           self.conv_width), dtype)}
            return {n: init_row_pool(num_blocks, block_size,
                                     self.row_width, dtype)
                    for n in ("k", "v")}

        return tuple(entry(k) for k in c.layers)

    def prefill_paged(self, variables, tokens, pools, table, block_ids,
                      start):
        """ONE request's prompt (1, bucket), padded, at positions
        [0, bucket): an attention layer attends its own keys and values
        and writes their rows into its pool, a mamba layer scans from a
        zero state and sets the slot's row of both leaves. `block_ids`
        says where, by cache kind: {"table": (bucket / bs,) the slot's
        fresh blocks, "state": {"slot": the slot, "keep": the position
        after which the slot's state is taken, the prompt's last but
        one (-1, a prompt of one token: zeros)}}. `table` and `start`
        are the trio's and not read: the prompt starts at 0 (module
        docstring). Returns the pools; the engine re-decodes the last
        prompt token."""
        p = variables["params"] if "params" in variables else variables
        if tokens.shape[0] != 1:
            raise ValueError("prefill_paged fills one request (batch 1), "
                             f"got batch {tokens.shape[0]}")
        slot, keep = block_ids["state"]["slot"], block_ids["state"]["keep"]
        new_pools = []
        for kind, kept, entry in zip(
                self.cfg.layers, self._sequence(p, tokens[0], keep)[1],
                pools):
            if kind == "mamba":
                new_pools.append({
                    n: jax.lax.dynamic_update_slice_in_dim(
                        entry[n], row[None].astype(entry[n].dtype), slot, 0)
                    for n, row in zip(("h", "taps"), kept)})
            else:
                new_pools.append({
                    n: write_prompt_rows(
                        entry[n], x.reshape(-1, self.row_width),
                        block_ids["table"])
                    for n, x in zip(("k", "v"), kept)})
        return tuple(new_pools)

    def decode_step_paged(self, variables, tokens, pos, pools, table):
        """As `TransformerLM.decode_step_paged`: tokens/pos (B,), table
        (B, max_blocks), ROW b OF THE BATCH IS SLOT b (as the engine
        calls it: a state row is found by its slot). An attention layer
        writes each row's key and value at (table[pos // bs], pos % bs)
        and attends each slot's own live rows; a mamba layer moves the
        seated slots' state one token on and leaves the others' as it
        was. Returns (logits (B, V) float32, pools)."""
        p = variables["params"] if "params" in variables else variables
        c = self.cfg
        b = tokens.shape[0]
        seated = table[:, 0] != 0
        new_pools = []

        def attend(lp, u, entry):
            with jax.named_scope("attention"):
                bs = entry["k"].shape[1]
                ids, offsets = table[jnp.arange(b), pos // bs], pos % bs
                q, k, v = self._qkv(lp, u)
                kp = write_decode_rows(entry["k"], k.reshape(b, -1), ids,
                                       offsets)
                vp = write_decode_rows(entry["v"], v.reshape(b, -1), ids,
                                       offsets)
                new_pools.append({"k": kp, "v": vp})
                o = grouped_paged_attention(
                    q, kp, vp, table, pos, c.num_key_value_heads,
                    c.attention_multiplier).reshape(b, -1)
                return _mm(o.astype(lp["wo"].dtype), lp["wo"])

        def step(lp, u, entry):
            y, state = self._mamba_step(lp, u, entry, seated)
            new_pools.append(state)
            return y

        x = self._embed(p, tokens)
        for kind, lp, entry in zip(c.layers, p["layers"], pools):
            x = self._layer(lp, x, functools.partial(
                step if kind == "mamba" else attend, entry=entry))
        return self._logits(p, x), tuple(new_pools)

    # ------------------------------------------------- what the spans say

    def decode_read_report(self, pos, table, block_size: int) -> dict:
        """What a decode step at these clocks (host, NumPy: `pos` (B,),
        `table` (B, max_blocks) with an unseated slot's row zero) reads
        of its ROWS, for the engine's `decode_step` span, under
        `WindowMoELM`'s names: `full_rows`, the rows the mask lets the
        step's queries see, summed over the seated slots, in ONE
        attention layer (`window_rows` is 0: no layer has a window);
        `attended_rows`, the rows the program gathers, summed over the
        attention layers, by the program's own roundings. What it reads
        of the slots' state the engine adds (`state_bytes`)."""
        pos, table = np.asarray(pos), np.asarray(table)
        seated = table[:, 0] != 0
        return {"window_rows": 0,
                "full_rows": int((pos + 1)[seated].sum()),
                "attended_rows": int(
                    block_size * (len(self.cfg.layers) - self.n_mamba)
                    * attended_blocks(pos, table, block_size))}

    def prefill_span_args(self, bucket: int) -> dict:
        """Chunks of the scan a prefill of this bucket runs, a layer
        (also the engine's `serving_prefill_scan_chunks_total`)."""
        return {"scan_chunks": -(-bucket // self.cfg.mamba_chunk_size)}

    def slot_state_bytes(self, cache_dtype) -> int:
        """What ONE seated slot keeps in the "state" entries, all mamba
        layers: the engine's `serving_slot_state_bytes` gauge. `h` is
        float32, the taps in `cache_dtype` (the engine's)."""
        c = self.cfg
        return self.n_mamba * (
            4 * int(np.prod(self.state_shape)) + jnp.dtype(
                cache_dtype).itemsize * (c.mamba_d_conv - 1)
            * self.conv_width)
