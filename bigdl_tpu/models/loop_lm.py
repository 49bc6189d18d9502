"""A decoder language model whose stack of layers is RUN SEVERAL TIMES
over ONE set of weights: the `ouro` family (ByteDance Seed's looped
language models, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741), whose `config.json` keys the configuration below
keeps under their own names; what the config does not carry (the four
norms a layer, no bias, the final norm between the passes, the exit
gate, a pass's own rows) is the family's public modeling code's.

No reference counterpart (the reference has no language model with a
cache). The serving side only, through the same paged trio as the other
list models (`init_block_pool`, `prefill_paged`, `decode_step_paged`);
`apply` is the plain full-sequence forward. Training (a backward pass
over the loop) is ROADMAP B-I.

With d = hidden_size, Hq query heads over G key-value heads of D (query
head h reads key-value head h // (Hq/G)), L layers, T =
`total_ut_steps` passes:

  h = Emb[tok]                                            (no scale)
  for pass t in 0..T-1:
    for layer l in 0..L-1, the SAME weights in every pass:
      a = RMSNorm_1(h);  q, k, v = a W_q, a W_k, a W_v    (no bias)
      q, k <- RoPE(q, k): the pairs (i, i + D/2), all D numbers, at the
              token's position, the same in every pass
      o = softmax_causal(q . k / sqrt(D)) @ v over the keys and values
          of THIS pass and this layer: a pass never reads another's
      h <- h + RMSNorm_2(o W_o)
      h <- h + RMSNorm_4((silu(m W_g) * m W_u) W_d),  m = RMSNorm_3(h)
    h <- RMSNorm_final(h): the normed h is what pass t + 1 takes in
    lambda_t = sigmoid(w_exit . h + b_exit): the exit gate, one vector
               and one bias for every pass
  logits = h W_head over the last pass's normed state, untied.

Generation leaves the loop at the first pass whose cumulated exit
probability reaches `early_exit_threshold`; at the published 1.0 that
is always the last, and `from_source` refuses a lower one (slots that
leave at different passes are a scheduler's change). So EVERY TOKEN
RUNS T PASSES, the serving programs leave `lambda_t` uncomputed
(nothing reads it) and `forward` returns it beside the logits.

THE LOOP IN THE PROGRAM is one `lax.scan` over the pass whose body holds
the L layers: the compiled program has L layer bodies whatever T is, and
`params` has no axis and no copy per pass.

WHAT A TOKEN LEAVES IN THE CACHE: its key (after RoPE) and value in
every layer OF EVERY PASS, T * L row sets of G * D lanes each, four
times as deep as the weights at T = 4. Every row set lives in table
blocks (`cache_kinds()` is all "table": the prefix cache, the spill
tier and `SpeculativeEngine` serve it). The pool has ONE ENTRY A LAYER,
{'k', 'v'}, each leaf (num_blocks, T, block_size, G * D): blocks are
axis 0 as everywhere (ops/kv_cache.init_block_pool, "THE CONTRACT"),
and a block holds its 16 positions' rows of pass 0, then of pass 1, and
so on. The scan's body sees a leaf as `(num_blocks * T, block_size,
G * D)`, a reshape that moves nothing, in which the rows of block b in
pass t are block `b * T + t` (`_pass_blocks`): every read and write of
ops/kv_cache.py then works on a pass as on a layer of its own, in the
leaves the scan carries, and one body serves every pass. (The passes
side by side in a row's LANES, (num_blocks, block_size, T * G * D),
would keep a leaf's second axis the block size, but the TPU compiler
gathers a window of lanes one block at a time in a loop of its own, or
copies a whole pass's slab before it: PERF.md, PR 49.) One block table
a slot, shared by all entries and all passes; `cache_entries` = T * L
says how many row sets a step reads. What reads the block size off a
leaf's second axis does not apply: `InferenceEngine.import_handoff`
does, so the handoff roles are `unserved`.

Decode reads a pass's rows in the grouped rows form
(`ops/kv_cache.grouped_paged_attention`: each slot's own live chunks);
prefill attends the suffix's queries over the slot's whole table
through `start` (the prefix's rows included, full extent with a mask:
the bit-identity contract of ops/kv_cache.py).

Precision: as the other list models: weights in the dtype they are
given in, matmul operands in that dtype with float32 accumulation, the
residual stream, norm statistics, RoPE and softmax in float32.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.models.latent_moe import (_mm, require_source_values,
                                         rms_norm)
from bigdl_tpu.models.window_moe import (grouped_prompt_attention,
                                         rope_half_split)
from bigdl_tpu.nn.module import Module
from bigdl_tpu.ops.kv_cache import (attended_blocks, gather_block_rows,
                                    grouped_paged_attention,
                                    write_decode_rows, write_prompt_rows)
from bigdl_tpu.parallel.moe import gated_ffn
from bigdl_tpu.serving.protocol import ServedModel

_NEG_INF = -1e30
# queries a block of the prefill's attention over the table's extent
_QUERY_BLOCK = 256


@dataclass(frozen=True)
class LoopLMConfig:
    """The source's widths and counts under the source's names."""
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    total_ut_steps: int
    early_exit_threshold: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    max_position_embeddings: int = 4096

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_key_value_heads {self.num_key_value_heads} does not "
                f"divide the {self.num_attention_heads} query heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (RoPE pairs)")
        if self.total_ut_steps < 1 or self.num_hidden_layers < 1:
            raise ValueError(
                f"total_ut_steps {self.total_ut_steps} passes over "
                f"{self.num_hidden_layers} layers: at least one of each")
        if self.early_exit_threshold < 1:
            raise NotImplementedError(
                f"early_exit_threshold={self.early_exit_threshold!r}: this "
                "model runs every pass for every token (slots that leave "
                "the loop at different passes are a scheduler's change)")

    @property
    def max_len(self) -> int:
        """No positional table: RoPE reaches as far as the source says."""
        return self.max_position_embeddings

    @classmethod
    def from_source(cls, cfg: dict) -> "LoopLMConfig":
        """From a `config.json` of the family (its keys as they are);
        what this model does not build is refused by name."""
        require_source_values(cfg, {
            "rope_scaling": None, "use_sliding_window": False,
            "tie_word_embeddings": False, "hidden_act": "silu",
            "attention_bias": False})
        kinds = cfg["layer_types"]
        if len(kinds) != cfg["num_hidden_layers"]:
            raise ValueError(
                f"{len(kinds)} layer_types for num_hidden_layers="
                f"{cfg['num_hidden_layers']}")
        other = sorted(set(kinds) - {"full_attention"})
        if other:
            raise NotImplementedError(
                f"layer_types has {other}: this model does layer_types="
                "'full_attention' only")
        return cls(**{k: cfg[k] for k in cls.__dataclass_fields__
                      if k in cfg})


def table_prompt_attention(q, k, v, start, kv_heads: int, sm_scale: float):
    """Causal attention of a prompt's SUFFIX over its slot's whole
    table: q (S, Hq, D), the queries at positions `start + i`; k and v
    (E, G, D), the table's rows, position j at row j, the suffix's own
    already written among them; compute dtype in, (S, Hq * D) float32
    out. Every query reduces over the FULL extent E under a mask, so a
    row's bits do not depend on where its prefix was computed
    (ops/kv_cache.py, the bit-identity contract); value rows past the
    suffix's end are zeroed (0 * NaN). A block of queries at a time."""
    s, hq, dh = q.shape
    e, g = k.shape[0], kv_heads
    qb = s if s % _QUERY_BLOCK else _QUERY_BLOCK
    jk = jnp.arange(e)
    v = jnp.where((jk < start + s)[:, None, None], v,
                  jnp.zeros((), v.dtype))
    q = q.reshape(s // qb, qb, g, hq // g, dh)

    def block(args):
        i, qi = args                            # qi (qb, G, R, D)
        sc = jnp.einsum("qgrd,kgd->grqk", qi, k,
                        preferred_element_type=jnp.float32) * sm_scale
        iq = start + i * qb + jnp.arange(qb)
        sc = jnp.where(jk[None, :] <= iq[:, None], sc, _NEG_INF)
        p = jnp.exp(sc - jnp.max(sc, -1, keepdims=True))
        p = p / jnp.sum(p, -1, keepdims=True)
        o = jnp.einsum("grqk,kgd->qgrd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(qb, hq * dh)

    return jax.lax.map(block, (jnp.arange(s // qb), q)).reshape(s, hq * dh)


class LoopLM(Module, ServedModel):
    """See the module docstring. Parameters are per layer, ONE set for
    every pass: `{"embed" (V, d), "head" (d, V), "norm" (d,), "exit_w"
    (d,), "exit_b" (), "layers": (dict,) * L}`, every matrix (in, out);
    a layer has `ln_in`, `wq`, `wk`, `wv`, `wo`, `ln_post_attn`,
    `ln_pre_mlp`, `w_gate`, `w_up`, `w_down`, `ln_post_mlp`."""

    # option of `check_serving_options` -> why this model does not serve
    # with it (serving/protocol.py)
    unserved = {"role": "import_handoff reads a package's block size off "
                        "its leaves' second axis (serving/engine.py), "
                        "which in this model's leaves is the pass: a "
                        "block's rows lie pass after pass"}

    def __init__(self, config: LoopLMConfig, name=None):
        super().__init__(name=name)
        c = self.cfg = config
        # a token's key (or value) row of ONE pass: G heads side by side
        self.row_width = c.num_key_value_heads * c.head_dim
        self.sm_scale = c.head_dim ** -0.5
        self.cache_entries = c.total_ut_steps * c.num_hidden_layers
        # bytes of the weights one decode step has to read, and the
        # head's part of them, by the weights the last `serving_params`
        # was given (0: none yet)
        self.weight_bytes_streamed = self._head_bytes = 0

    # ------------------------------------------------------------ weights

    def init_params(self, rng, std: float = 0.02, dtype=jnp.float32):
        c = self.cfg
        d, f = c.hidden_size, c.intermediate_size
        hq = c.num_attention_heads * c.head_dim
        keys = iter(jax.random.split(rng, 8 * c.num_hidden_layers + 2))

        def w(*shape):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        def ones(n):
            return jnp.ones((n,), jnp.float32)

        def layer():
            return {"ln_in": ones(d), "wq": w(d, hq),
                    "wk": w(d, self.row_width), "wv": w(d, self.row_width),
                    "wo": w(hq, d), "ln_post_attn": ones(d),
                    "ln_pre_mlp": ones(d), "w_gate": w(d, f),
                    "w_up": w(d, f), "w_down": w(f, d),
                    "ln_post_mlp": ones(d)}

        return {"embed": w(c.vocab_size, d), "head": w(d, c.vocab_size),
                "norm": ones(d), "exit_w": jnp.zeros((d,), jnp.float32),
                "exit_b": jnp.zeros((), jnp.float32),
                "layers": tuple(layer()
                                for _ in range(c.num_hidden_layers))}

    def serving_params(self, variables):
        """The tree as it is given (per layer already: no second copy),
        and `weight_bytes_streamed` of it: what ONE decode step must
        read of the weights, the layers' and the final norm's bytes
        once a pass and the head once (the embedding's rows are
        gathered)."""
        p = variables["params"]

        def nbytes(tree):
            return sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree_util.tree_leaves(tree))

        self._head_bytes = int(nbytes(p["head"]))
        self.weight_bytes_streamed = self._head_bytes + int(
            self.cfg.total_ut_steps * nbytes((p["layers"], p["norm"])))
        return p

    # ------------------------------------------------------- layer pieces

    def _qkv(self, lp, x, pos):
        """x (T, d) float32 stream, pos (T,) → q (T, Hq, D), k and v
        (T, G, D) float32, q and k rotated."""
        c = self.cfg
        t = x.shape[0]
        a = rms_norm(x, lp["ln_in"], c.rms_norm_eps).astype(lp["wq"].dtype)
        q = _mm(a, lp["wq"]).reshape(t, -1, c.head_dim)
        k = _mm(a, lp["wk"]).reshape(t, -1, c.head_dim)
        v = _mm(a, lp["wv"]).reshape(t, -1, c.head_dim)
        return (rope_half_split(q, pos, c.rope_theta),
                rope_half_split(k, pos, c.rope_theta), v)

    def _after_attention(self, lp, x, o):
        """o (T, Hq * D) float32 attention output → the stream after
        W_o, its norm and the residual add."""
        return x + rms_norm(_mm(o.astype(lp["wo"].dtype), lp["wo"]),
                            lp["ln_post_attn"], self.cfg.rms_norm_eps)

    def _mlp(self, lp, x):
        """The gated MLP between its two norms, on the stream."""
        c = self.cfg
        m = rms_norm(x, lp["ln_pre_mlp"], c.rms_norm_eps)
        f = gated_ffn(m.astype(lp["w_gate"].dtype), lp["w_gate"],
                      lp["w_up"], lp["w_down"])
        return x + rms_norm(f, lp["ln_post_mlp"], c.rms_norm_eps)

    def _loop(self, p, x, pos, carry, attend, gates=False):
        """The passes, one `lax.scan` whose body holds the layers:
        `attend(t, l, carry, q, k, v)` → (the attention's output
        (T, Hq * D) float32, the carry) is the caller's (what it keeps
        of k and v), t the traced pass and l the layer's number.
        Returns the last pass's NORMED stream, the carry and, with
        `gates`, the exit gate of every pass (T, rows). The scopes name
        the parts' operations in a device trace."""
        c = self.cfg

        def one_pass(state, t):
            x, carry = state
            with jax.named_scope("loop_body"):
                for l, lp in enumerate(p["layers"]):
                    with jax.named_scope("attention"):
                        o, carry = attend(t, l, carry,
                                          *self._qkv(lp, x, pos))
                        x = self._after_attention(lp, x, o)
                    with jax.named_scope("mlp"):
                        x = self._mlp(lp, x)
                with jax.named_scope("loop_norm"):
                    x = rms_norm(x, p["norm"], c.rms_norm_eps)
                gate = jax.nn.sigmoid(x @ p["exit_w"] + p["exit_b"]) \
                    if gates else None
            return (x, carry), gate

        (x, carry), lam = jax.lax.scan(one_pass, (x, carry),
                                       jnp.arange(c.total_ut_steps))
        return x, carry, lam

    def _embed(self, p, tokens):
        return p["embed"][tokens].astype(jnp.float32)

    def _head(self, p, x):
        return _mm(x.astype(p["head"].dtype), p["head"])

    # ------------------------------------------------------- full forward

    def forward(self, params, tokens):
        """(B, S) tokens → ((B, S, V) float32 logits, (B, T, S) the exit
        gate `lambda_t` of every pass): every sequence on its own, no
        cache."""
        c = self.cfg
        pos = jnp.arange(tokens.shape[1])

        def attend(t, l, carry, q, k, v):
            dt = params["layers"][l]["wq"].dtype
            return grouped_prompt_attention(
                q.astype(dt), k.astype(dt), v.astype(dt),
                c.num_key_value_heads, self.sm_scale), carry

        def one(toks):
            x, _, lam = self._loop(params, self._embed(params, toks), pos,
                                   (), attend, gates=True)
            return self._head(params, x), lam

        return jax.lax.map(one, tokens)

    def apply(self, variables, tokens, training=False, rng=None):
        """(B, S) tokens → (B, S, V) float32 logits."""
        return (self.forward(variables["params"], tokens)[0],
                variables.get("state", {}))

    # ------------------------------------------------------ the paged trio

    def init_block_pool(self, num_blocks: int, block_size: int,
                        dtype=jnp.float32, slots: int = 1):
        """ONE entry a layer, {'k', 'v'}, each leaf (num_blocks, T,
        block_size, G * D): a block's rows pass after pass (module
        docstring), block-major, block 0 scratch."""
        c = self.cfg
        shape = (num_blocks, c.total_ut_steps, block_size, self.row_width)
        return tuple({n: jnp.zeros(shape, dtype) for n in ("k", "v")}
                     for _ in range(c.num_hidden_layers))

    def _pass_blocks(self, blocks, t):
        """Table blocks → the same blocks' rows of pass t in a leaf seen
        as (num_blocks * T, block_size, G * D). The scratch block stays
        block 0 (pass 0's part of it), so that a row whose first entry
        is 0 still reads as not seated."""
        return jnp.where(blocks == 0, 0,
                         blocks * self.cfg.total_ut_steps + t)

    def _written(self, pools, l, t, write):
        """The pools with layer l's leaves after `write(view, name)`,
        the leaf seen a pass at a time (`_pass_blocks`), and that view
        of both for the read that follows."""
        views = {n: write(leaf.reshape((-1,) + leaf.shape[2:]), n)
                 for n, leaf in pools[l].items()}
        entry = {n: v.reshape(pools[l][n].shape) for n, v in views.items()}
        return pools[:l] + (entry,) + pools[l + 1:], views

    def prefill_paged(self, variables, tokens, pools, table, block_ids,
                      start):
        """ONE request's SUFFIX (1, bucket), padded, at positions
        [start, start + bucket): `table` (1, max_blocks) is the slot's
        whole table (the blocks of a cached prefix, then the fresh
        `block_ids` (bucket / bs,) this call writes), `start` a traced
        scalar, the block-aligned length of the prefix (0: a cold
        prefill, the same program). In every pass a layer writes the
        suffix's rows into its pass's part of the fresh blocks and
        attends the table. Returns the pools; the engine re-decodes the
        last prompt token."""
        p = variables["params"] if "params" in variables else variables
        c = self.cfg
        if tokens.shape[0] != 1:
            raise ValueError("prefill_paged fills one request (batch 1), "
                             f"got batch {tokens.shape[0]}")
        w, g = self.row_width, c.num_key_value_heads
        start = jnp.asarray(start, jnp.int32)
        pos = start + jnp.arange(tokens.shape[1])

        def attend(t, l, pools, q, k, v):
            dt = p["layers"][l]["wq"].dtype
            rows = {"k": k.astype(dt).reshape(-1, w),
                    "v": v.astype(dt).reshape(-1, w)}
            pools, views = self._written(
                pools, l, t, lambda view, n: write_prompt_rows(
                    view, rows[n], self._pass_blocks(block_ids, t)))
            kc, vc = (gather_block_rows(
                views[n], self._pass_blocks(table, t))[0].reshape(
                    -1, g, c.head_dim) for n in ("k", "v"))
            return table_prompt_attention(q.astype(kc.dtype), kc, vc, start,
                                          g, self.sm_scale), pools

        return self._loop(p, self._embed(p, tokens[0]), pos, tuple(pools),
                          attend)[1]

    def decode_step_paged(self, variables, tokens, pos, pools, table):
        """As `TransformerLM.decode_step_paged`: tokens/pos (B,), table
        (B, max_blocks). In every pass a layer writes each row's key
        and value at (table[pos // bs], pos % bs) of its pass and
        attends each slot's own live rows of that pass. Returns
        (logits (B, V) float32, pools)."""
        p = variables["params"] if "params" in variables else variables
        c = self.cfg
        b, w = tokens.shape[0], self.row_width
        bs = pools[0]["k"].shape[2]
        ids, offsets = table[jnp.arange(b), pos // bs], pos % bs

        def attend(t, l, pools, q, k, v):
            rows = {"k": k.reshape(b, w), "v": v.reshape(b, w)}
            # an unseated row writes where the scratch block's pass t
            # lies: block t of the view
            pools, views = self._written(
                pools, l, t, lambda view, n: write_decode_rows(
                    view, rows[n], ids * c.total_ut_steps + t, offsets))
            o = grouped_paged_attention(
                q, views["k"], views["v"], self._pass_blocks(table, t), pos,
                c.num_key_value_heads, self.sm_scale)
            return o.reshape(b, -1), pools

        x, pools, _ = self._loop(p, self._embed(p, tokens), pos,
                                 tuple(pools), attend)
        return self._head(p, x), pools

    # ------------------------------------------------- what the spans say

    def health_report(self) -> dict:
        """What `health()` says of the loop."""
        return {"ut_steps": self.cfg.total_ut_steps,
                "cache_entries": self.cache_entries}

    def decode_read_report(self, pos, table, block_size: int) -> dict:
        """What a decode step at these clocks (host, NumPy: `pos` (B,),
        `table` (B, max_blocks) with an unseated slot's row zero) reads,
        for the engine's `decode_step` span, under `WindowMoELM`'s
        names: `full_rows`, the rows the mask lets the step's queries
        see, summed over the seated slots, in ONE of the `cache_entries`
        row sets (`window_rows` is 0: no layer has a window);
        `attended_rows`, the rows the program gathers, summed over the
        row sets, by the program's own roundings; and of the loop:
        `ut_steps`, `cache_entries`, `weight_bytes_streamed` (the
        layers' bytes once a pass, the head once)."""
        pos, table = np.asarray(pos), np.asarray(table)
        seated = table[:, 0] != 0
        return {"window_rows": 0,
                "full_rows": int((pos + 1)[seated].sum()),
                "attended_rows": int(
                    block_size * self.cache_entries
                    * attended_blocks(pos, table, block_size)),
                "weight_bytes_streamed": self.weight_bytes_streamed,
                **self.health_report()}

    def prefill_span_args(self, bucket: int) -> dict:
        """A prefill streams the layers once a pass too, and no head
        (it leaves rows, no logits)."""
        return {"ut_steps": self.cfg.total_ut_steps,
                "weight_bytes_streamed":
                    self.weight_bytes_streamed - self._head_bytes}
