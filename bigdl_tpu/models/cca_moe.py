"""A decoder language model given as a LIST OF LAYER KINDS whose layers
are a compressed convolutional attention (CCA) sublayer and an expert
sublayer with ONE expert a token, chosen by a router MLP that carries
its state from layer to layer: the `zaya` family (Zyphra's ZAYA1;
Compressed Convolutional Attention, arXiv:2510.04476; the ZAYA1
technical report, arXiv:2511.17127), whose `config.json` keys the
configuration below keeps under their own names. What the config does
not carry (the two convolutions' form, the q-k mean, the norm and
temperature of q and k, the value shift, the router MLP, its depth
averaging and its 17th choice, the scaled residual merges) is the
published descriptions'.

No reference counterpart (the reference has no language model with a
cache). The serving side only, through the same paged trio as the other
list models (`init_block_pool`, `prefill_paged`, `decode_step_paged`);
`apply` is the plain full-sequence forward. Training is ROADMAP B-I.

With d = hidden_size, Hq query heads over G key-value heads of D (query
head h reads key-value head h // (Hq/G)), t the position and every
row before position 0 zero:

  the stream, float32: every sublayer j gets the residual r and the
  last sublayer's output y (the first: y = Emb[tok], r absent)
    r <- (r + b_r) * s_r + (y + b_y) * s_y;  u = RMSNorm_j(r)
    y <- sublayer_j(u)
  after the last sublayer one more merge, the final RMSNorm, and
  logits = u Emb^T (a TIED head).

  CCA on u_t:
    z_t = [W_q u_t ; W_k u_t]                 Hq + G heads of D
    a_t = w0[:, 0] z_{t-1} + w0[:, 1] z_t + beta0        depthwise
    c_t[h] = a_{t-1}[h] W1[h, 0] + a_t[h] W1[h, 1] + beta1[h]
                                              one (D, D) group a head
    q = c[:Hq] + (q~[h] + k~[h // R]) / 2     q~, k~: z's two parts
    k = c[Hq:] + (mean_{h in g} q~[h] + k~[g]) / 2
    q <- sqrt(D) q / |q|;  k <- sqrt(D) exp(tau_g) k / |k|
    RoPE on the first `partial_rotary_factor` of a head's D numbers
    (half-split pairs), the rest pass
    v_t = [W_v1 u_t ; W_v2 u_{t-1}]           the value shift
    y = W_o (causal softmax(q k^T / sqrt(D)) v)

  experts on u_t, layer l:
    rho_l = W_d u_t + b_d (+ gamma_l * rho_{l-1}, l > 0), alive inside
    one step only; p = softmax(W_3 gelu(W_2 gelu(W_1 RMSNorm(rho) +
    b_1) + b_2)) over num_experts + 1; e = argmax(p + beta);
    e < num_experts: y = p_e E_e(u), `parallel/moe.DroplessMoE` under
    this routing; e = num_experts: y = 0, no expert is read.

WHAT A TOKEN LEAVES IN THE CACHE, a layer: `k` (normed, scaled,
rotated) and `v`, G * D lanes each, in blocks the slot's table names
(a "table" entry, read by `ops/kv_cache.grouped_paged_attention`).
WHAT THE SLOT KEEPS BESIDE THEM: the previous token's z, a and
W_v2 u, float32, `state_width` numbers a layer with NO position axis
(a "state" entry of `cache_kinds()`: `(slots, state_width)`, row b is
slot b). Prefill writes the slot's row from the prompt's last-but-one
position (the engine re-decodes the last prompt token), decode reads
it and rewrites it every step for the seated slots; a released slot's
row stays until the next prefill rewrites the whole of it (the engine
scrubs a poisoned request's). A prompt always starts at position 0:
this model refuses the prefix cache (`check_serving_options`).

Precision: weights in the dtype they are given in, matmul operands in
that dtype with float32 accumulation; the stream, the state, the
depthwise convolution, norms, RoPE, softmax and the whole router
(matmuls at the highest precision) in float32.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.models.latent_moe import (_mm, require_source_values,
                                         rms_norm)
from bigdl_tpu.models.window_moe import (grouped_prompt_attention,
                                         rope_half_split)
from bigdl_tpu.nn.module import Module
from bigdl_tpu.ops.kv_cache import (attended_blocks, grouped_paged_attention,
                                    init_row_pool, write_decode_rows,
                                    write_prompt_rows)
from bigdl_tpu.parallel.moe import DroplessMoE, ExpertsReport
from bigdl_tpu.serving.protocol import ServedModel

LAYER_KINDS = ("hybrid",)
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class CCAMoEConfig:
    """`layers` is the model: one kind per layer. The rest are the
    source's widths under the source's names."""
    layers: Tuple[str, ...]
    vocab_size: int
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int
    router_hidden_size: int
    partial_rotary_factor: float = 0.5
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 4096

    def __post_init__(self):
        bad = [k for k in self.layers if k not in LAYER_KINDS]
        if bad or not self.layers:
            raise ValueError(f"layers {self.layers!r}: each one of "
                             f"{LAYER_KINDS}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide "
                f"over {self.num_key_value_heads} key-value heads")
        if self.num_key_value_heads % 2:
            raise ValueError("the value shift halves the key-value heads: "
                             f"{self.num_key_value_heads} is odd")
        if self.rotary_dim % 2:
            raise ValueError(f"{self.rotary_dim} rotated numbers a head: "
                             "RoPE rotates pairs")

    @property
    def max_len(self) -> int:
        """No positional table: RoPE reaches as far as the source says."""
        return self.max_position_embeddings

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @classmethod
    def from_source(cls, cfg: dict) -> "CCAMoEConfig":
        """From a `config.json` of the family (its keys as they are)."""
        only = {"cca_time0": 2, "cca_time1": 2, "num_experts_per_tok": 1,
                "tie_word_embeddings": True, "attention_bias": False,
                "lm_head_bias": False, "hidden_act": "silu",
                "sliding_window": None}
        require_source_values(cfg, only)
        kinds = tuple(cfg["layer_types"])
        if len(kinds) != cfg["num_hidden_layers"]:
            raise ValueError(
                f"{len(kinds)} layer_types for num_hidden_layers="
                f"{cfg['num_hidden_layers']}")
        rope = cfg["rope_parameters"]["hybrid"]
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError(f"rope_type {rope['rope_type']!r}")
        names = [f for f in cls.__dataclass_fields__ if f != "layers"]
        return cls(layers=kinds, **{
            **{k: cfg[k] for k in names if k in cfg},
            "rope_theta": rope["rope_theta"],
            "partial_rotary_factor": rope["partial_rotary_factor"]})


def _merge(res, r, y):
    """The scaled residual merge before a sublayer's norm; `r` is None
    before the first sublayer."""
    out = (y + res["b_y"]) * res["s_y"]
    return out if r is None else (r + res["b_r"]) * res["s_r"] + out


def _shift(x):
    """Row t of the result is row t - 1 of x (T, W); row 0 is zero."""
    return jnp.pad(x, ((1, 0), (0, 0)))[:-1]


class CCAMoELM(ExpertsReport, Module, ServedModel):
    """See the module docstring. Parameters are per layer from the
    start: `{"embed" (V, d), "res_out", "norm" (d,), "layers":
    (dict,) * L}`, every matrix (in, out); a merge's `res_*` is
    `{"s_r", "b_r", "s_y", "b_y"}` (d,) each, the first sublayer's
    without the `_r` pair; layer 0's router has no `gamma`."""

    # a layer's experts are under "experts", and the step's aux has a
    # seventeenth column: the rows the router sent to no expert
    experts_key = "experts"
    aux_skip_column = True

    def __init__(self, config: CCAMoEConfig, name=None):
        super().__init__(name=name)
        c = self.cfg = config
        self.moe = DroplessMoE(c.hidden_size, c.moe_intermediate_size,
                               c.num_experts, 1)
        # a token's key (or value) row: the G heads side by side
        self.row_width = c.num_key_value_heads * c.head_dim
        self.q_width = c.num_attention_heads * c.head_dim
        # the channels both convolutions mix: q's and k's heads
        self.conv_width = self.q_width + self.row_width
        # z_{t-1}, a_{t-1} and the shifted half of the value row
        self.state_width = 2 * self.conv_width + self.row_width // 2
        self.sm_scale = c.head_dim ** -0.5

    # ------------------------------------------------------------ weights

    def init_params(self, rng, std: float = 0.02, dtype=jnp.float32):
        c = self.cfg
        d, dh, f = c.hidden_size, c.head_dim, c.moe_intermediate_size
        e, rh = c.num_experts, c.router_hidden_size
        heads = c.num_attention_heads + c.num_key_value_heads
        keys = iter(jax.random.split(rng, 32 * len(c.layers) + 8))

        def w(*shape, dtype=dtype):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        def f32(*shape):
            return w(*shape, dtype=jnp.float32)

        def res(first=False):
            out = {"s_y": 1.0 + f32(d), "b_y": f32(d)}
            if not first:
                out.update(s_r=1.0 + f32(d), b_r=f32(d))
            return out

        def layer(i):
            router = {"w_down": f32(d, rh), "b_down": f32(rh),
                      "norm": jnp.ones((rh,), jnp.float32),
                      "w1": f32(rh, rh), "b1": f32(rh), "w2": f32(rh, rh),
                      "b2": f32(rh), "w3": f32(rh, e + 1),
                      "beta": f32(e + 1)}
            if i:
                router["gamma"] = f32(rh)
            return {
                "res_attn": res(first=i == 0),
                "ln_attn": jnp.ones((d,), jnp.float32),
                "wq": w(d, self.q_width), "wk": w(d, self.row_width),
                "wv1": w(d, self.row_width // 2),
                "wv2": w(d, self.row_width // 2),
                "wo": w(self.q_width, d),
                "conv0_w": f32(self.conv_width, 2),
                "conv0_b": f32(self.conv_width),
                "conv1_w": w(heads, 2, dh, dh),
                "conv1_b": f32(self.conv_width),
                "tau": f32(c.num_key_value_heads),
                "res_moe": res(), "ln_moe": jnp.ones((d,), jnp.float32),
                "router": router,
                "experts": {"w_gate": w(e, d, f), "w_up": w(e, d, f),
                            "w_down": w(e, f, d)}}

        return {"embed": w(c.vocab_size, d), "res_out": res(),
                "norm": jnp.ones((d,), jnp.float32),
                "layers": tuple(layer(i) for i in range(len(c.layers)))}

    # ------------------------------------------------------- layer pieces

    def _rope(self, x, pos):
        """x (T, H, D): the first `rotary_dim` numbers of every head
        rotated, the rest as they are."""
        rot = self.cfg.rotary_dim
        return jnp.concatenate(
            [rope_half_split(x[..., :rot], pos, self.cfg.rope_theta),
             x[..., rot:]], -1)

    def _cca_qkv(self, lp, u, pos, prev=None):
        """u (T, d) float32, the sublayer's normed input at positions
        pos (T,). `prev` is the PREVIOUS token's (z (T, conv_width), a
        (T, conv_width), W_v2 u (T, row_width / 2)) float32 of every
        row (a decode step: the slots' state); None says the rows are
        ONE sequence from position 0, whose previous rows are its own,
        shifted, zero before the first. → q (T, Hq, D), k and v
        (T, G, D) float32, and these tokens' (z, a, W_v2 u)."""
        c = self.cfg
        t, dh = u.shape[0], c.head_dim
        hq, g = c.num_attention_heads, c.num_key_value_heads
        dt = lp["wq"].dtype
        ub = u.astype(dt)

        def before(x, n):
            return _shift(x) if prev is None else prev[n]

        z = jnp.concatenate([_mm(ub, lp["wq"]), _mm(ub, lp["wk"])], -1)
        w0 = lp["conv0_w"]
        a = w0[:, 0] * before(z, 0) + w0[:, 1] * z + lp["conv0_b"]

        def tap(x, n):      # one group a head: (H, T, D) @ (H, D, D),
            # heads first (the CPU's batched bfloat16 matmul wants them so)
            x = x.reshape(t, hq + g, dh).astype(dt).swapaxes(0, 1)
            return jnp.einsum(
                "htd,hde->hte", x, lp["conv1_w"][:, n],
                preferred_element_type=jnp.float32).swapaxes(0, 1)

        conv = tap(before(a, 1), 0) + tap(a, 1) \
            + lp["conv1_b"].reshape(hq + g, dh)
        qt = z[:, :self.q_width].reshape(t, g, hq // g, dh)
        kt = z[:, self.q_width:].reshape(t, g, 1, dh)
        q = conv[:, :hq] + ((qt + kt) / 2).reshape(t, hq, dh)
        k = conv[:, hq:] + (jnp.mean(qt, 2) + kt[:, :, 0]) / 2

        def unit(x):        # a head's numbers at length sqrt(D)
            return x * (dh ** 0.5 * jax.lax.rsqrt(
                jnp.sum(x * x, -1, keepdims=True)))

        q = self._rope(unit(q), pos)
        k = self._rope(unit(k) * jnp.exp(lp["tau"])[None, :, None], pos)
        v2 = _mm(ub, lp["wv2"])
        v = jnp.concatenate([_mm(ub, lp["wv1"]), before(v2, 2)], -1)
        return q, k, v.reshape(t, g, dh), (z, a, v2)

    def _route(self, rt, u, rho_prev):
        """u (T, d) float32 → the layer's router state rho (T, rh), the
        chosen output (T, 1) int32 (`num_experts`: no expert) and its
        softmax weight (T, 1) float32."""
        def dense(x, w):
            return jnp.dot(x, w, precision=_HIGHEST)

        rho = dense(u, rt["w_down"]) + rt["b_down"]
        if rho_prev is not None:
            rho = rho + rt["gamma"] * rho_prev
        h = rms_norm(rho, rt["norm"], self.cfg.rms_norm_eps)
        h = jax.nn.gelu(dense(h, rt["w1"]) + rt["b1"], approximate=False)
        h = jax.nn.gelu(dense(h, rt["w2"]) + rt["b2"], approximate=False)
        p = jax.nn.softmax(dense(h, rt["w3"]), axis=-1)
        e = jnp.argmax(p + rt["beta"], axis=-1)[:, None]
        return rho, e.astype(jnp.int32), jnp.take_along_axis(p, e, -1)

    def _experts(self, lp, u, rho_prev):
        """u (T, d) float32 → (the expert sublayer's output (T, d)
        float32, rho, the rows each expert got with the skipped rows
        last, int32 (E + 1,))."""
        with jax.named_scope("router"):
            rho, e, w = self._route(lp["router"], u, rho_prev)
        y, n = self.moe.forward(
            lp["experts"], u.astype(lp["experts"]["w_gate"].dtype),
            routing=(e, w))
        return y, rho, n

    def _logits(self, p, r, y):
        c = self.cfg
        u = rms_norm(_merge(p["res_out"], r, y), p["norm"], c.rms_norm_eps)
        # u Emb^T: the embedding is contracted on its own second axis
        return jax.lax.dot_general(
            u.astype(p["embed"].dtype), p["embed"],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    def _layer(self, lp, stream, pos, attend, prev=None):
        """One layer on the stream (r, y, rho) of the rows at positions
        pos (T,): both sublayers with their merges and norms.
        `attend(q, k, v, state)` is the caller's: what it keeps of the
        CCA's rows and the attention (T, Hq * D) float32 it returns;
        `prev` as `_cca_qkv`'s. → ((r, y, rho), the rows each expert
        got)."""
        c = self.cfg
        r, y, rho = stream
        # the scopes name the sublayers' operations in a device trace
        # (the CCA and router chains are most of a decode step: PERF.md)
        with jax.named_scope("cca"):
            r = _merge(lp["res_attn"], r, y)
            u = rms_norm(r, lp["ln_attn"], c.rms_norm_eps)
            o = attend(*self._cca_qkv(lp, u, pos, prev))
            y = _mm(o.astype(lp["wo"].dtype), lp["wo"])
        with jax.named_scope("routed_experts"):
            r = _merge(lp["res_moe"], r, y)
            u = rms_norm(r, lp["ln_moe"], c.rms_norm_eps)
            y, rho, n = self._experts(lp, u, rho)
        return (r, y, rho), n

    def _sequence(self, p, toks):
        """One sequence (S,) from position 0 → the stream (r, y) after
        the last sublayer and, a layer, what a prefill keeps: the keys
        and values (S, G, D) float32 and the three (S, .) rows a slot's
        state is cut from."""
        c = self.cfg
        kept = []

        def attend(q, k, v, state):
            kept.append((k, v, state))
            dt = p["embed"].dtype
            return grouped_prompt_attention(
                q.astype(dt), k.astype(dt), v.astype(dt),
                c.num_key_value_heads, self.sm_scale)

        stream = (None, p["embed"][toks].astype(jnp.float32), None)
        for lp in p["layers"]:
            stream, _ = self._layer(lp, stream, jnp.arange(toks.shape[0]),
                                    attend)
        return stream[0], stream[1], kept

    # ------------------------------------------------------- full forward

    def apply(self, variables, tokens, training=False, rng=None):
        """(B, S) tokens → (B, S, V) float32 logits: every sequence on
        its own, no cache."""
        p = variables["params"]
        return (jax.lax.map(
            lambda t: self._logits(p, *self._sequence(p, t)[:2]), tokens),
            variables.get("state", {}))

    # ------------------------------------------------------ the paged trio

    def cache_kinds(self) -> Tuple[str, ...]:
        """For each entry of `init_block_pool`'s tuple: a layer's
        "table" entry (its key and value rows) and then its "state"
        entry (the slot's row)."""
        return ("table", "state") * len(self.cfg.layers)

    def init_block_pool(self, num_blocks: int, block_size: int,
                        dtype=jnp.float32, slots: int = 1):
        """Two entries a layer: {'k', 'v'}, each (num_blocks,
        block_size, G * D) block-major with block 0 scratch
        (ops/kv_cache.init_row_pool), then {'s'}, (slots, state_width)
        float32 whatever `dtype` is: row b is slot b's."""
        def rows():
            return init_row_pool(num_blocks, block_size, self.row_width,
                                 dtype)

        return tuple(
            entry for _ in self.cfg.layers for entry in (
                {"k": rows(), "v": rows()},
                {"s": jnp.zeros((slots, self.state_width), jnp.float32)}))

    def _split_state(self, s):
        w = self.conv_width
        return s[:, :w], s[:, w:2 * w], s[:, 2 * w:]

    def prefill_paged(self, variables, tokens, pools, table, block_ids,
                      start):
        """ONE request's prompt (1, bucket), padded, at positions
        [0, bucket): attended over its own keys and values, its rows
        written into the pools and the slot's state set. `block_ids`
        says where, by cache kind: {"table": (bucket / bs,) the slot's
        fresh blocks, "state": {"slot": the slot, "keep": the position
        whose rows the slot keeps, the prompt's last but one (-1, a
        prompt of one token: zeros)}}. `table` and `start` are the
        trio's and not read: the prompt starts at 0 (module docstring).
        Returns the pools; the engine re-decodes the last prompt
        token."""
        p = variables["params"] if "params" in variables else variables
        if tokens.shape[0] != 1:
            raise ValueError("prefill_paged fills one request (batch 1), "
                             f"got batch {tokens.shape[0]}")
        slot, keep = block_ids["state"]["slot"], block_ids["state"]["keep"]
        new_pools = []
        for (k, v, state), rows, st in zip(
                self._sequence(p, tokens[0])[2], pools[::2], pools[1::2]):
            new_pools.append({
                n: write_prompt_rows(rows[n], x.reshape(-1, self.row_width),
                                     block_ids["table"])
                for n, x in (("k", k), ("v", v))})
            row = jax.lax.dynamic_slice_in_dim(
                jnp.concatenate(state, -1), jnp.maximum(keep, 0), 1)
            new_pools.append({"s": jax.lax.dynamic_update_slice_in_dim(
                st["s"], jnp.where(keep >= 0, row, 0.0), slot, 0)})
        return tuple(new_pools)

    def decode_step_paged(self, variables, tokens, pos, pools, table):
        """As `TransformerLM.decode_step_paged`: tokens/pos (B,), table
        (B, max_blocks), ROW b OF THE BATCH IS SLOT b (as the engine
        calls it: a state row is found by its slot). Writes each row's
        key and value at (table[pos // bs], pos % bs), attends each
        slot's own live rows, and rewrites the state of the seated
        slots. Returns (logits (B, V) float32, pools, aux): `aux` is
        int32 (layers, E + 1), the rows each expert got and, last, the
        rows the router sent to none."""
        p = variables["params"] if "params" in variables else variables
        c = self.cfg
        b = tokens.shape[0]
        bs = pools[0]["k"].shape[1]
        seated = (table[:, 0] != 0)[:, None]
        ids, offsets = table[jnp.arange(b), pos // bs], pos % bs
        stream = (None, p["embed"][tokens].astype(jnp.float32), None)
        new_pools, counts = [], []
        for lp, rows, st in zip(p["layers"], pools[::2], pools[1::2]):
            def attend(q, k, v, state, rows=rows, st=st["s"]):
                kp = write_decode_rows(rows["k"], k.reshape(b, -1), ids,
                                       offsets)
                vp = write_decode_rows(rows["v"], v.reshape(b, -1), ids,
                                       offsets)
                new_pools.extend([{"k": kp, "v": vp}, {"s": jnp.where(
                    seated, jnp.concatenate(state, -1), st)}])
                return grouped_paged_attention(
                    q, kp, vp, table, pos, c.num_key_value_heads,
                    self.sm_scale).reshape(b, -1)

            stream, n = self._layer(lp, stream, pos, attend,
                                    self._split_state(st["s"]))
            counts.append(n)
        return (self._logits(p, *stream[:2]), tuple(new_pools),
                jnp.stack(counts))

    # ------------------------------------------------- what the spans say

    def decode_read_report(self, pos, table, block_size: int) -> dict:
        """What a decode step at these clocks (host, NumPy: `pos` (B,),
        `table` (B, max_blocks) with an unseated slot's row zero) reads
        of the cache, for the engine's `decode_step` span, under
        `WindowMoELM`'s names: `full_rows`, the rows the mask lets the
        step's queries see, summed over the seated slots, in ONE layer
        (every layer here keeps all of a slot's rows: `window_rows` is
        0); `attended_rows`, the rows the program gathers, summed over
        the layers, by the program's own roundings."""
        pos, table = np.asarray(pos), np.asarray(table)
        seated = table[:, 0] != 0
        return {"window_rows": 0,
                "full_rows": int((pos + 1)[seated].sum()),
                "attended_rows": int(
                    block_size * len(self.cfg.layers)
                    * attended_blocks(pos, table, block_size))}

    def slot_state_bytes(self, cache_dtype=None) -> int:
        """What ONE seated slot keeps in the "state" entries, all
        layers: the engine's `serving_slot_state_bytes` gauge. Float32
        whatever `cache_dtype` (the engine's) is."""
        return 4 * self.state_width * len(self.cfg.layers)
