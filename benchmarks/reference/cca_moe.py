"""Plain reference of the `zaya` family (Zyphra's ZAYA1 models): a decoder
whose every layer is a compressed convolutional attention (CCA) sublayer and
an expert sublayer with ONE expert a token, chosen by a router MLP whose
state is handed from layer to layer, the two joined by scaled residual
merges. Read off huggingface.co/Zyphra/ZAYA1-8B's `config.json`
(`model_type: zaya`) and, for what the config does not carry, off the
published descriptions (Compressed Convolutional Attention, arXiv:2510.04476;
the ZAYA1 technical report, arXiv:2511.17127): each such point is marked
(paper) below and listed under `assumed` in the configuration's file.

The yardstick of every cell of the family. It imports nothing of the
program: weights come from `init(seed, cfg)` here, and the family file
(benchmarks/families/cca_moe.py) hands the SAME arrays to the program.
float32 `jax.numpy`; callers wrap calls in
`jax.default_matmul_precision("highest")`. No kernels, no cache, no state,
no batching: one sequence at a time, the previous token's rows by shifting
the whole sequence one position, the full (S, S) score matrix under the
causal mask (a block of query rows at a time, so that 3,072 positions fit
one chip), and a loop over ALL experts, each weighted by the routing (zero
where a token did not choose it).

With S positions t = 0..S-1, Hq query heads over G key-value heads of D
(query head h reads key-value head h // (Hq/G), R = Hq/G), no bias in a
projection, RMSNorm's eps `rms_norm_eps`, and x_{-1} = 0 for every row
"before the first":

  stream   every sublayer j (attention, experts, attention, ...) gets the
           residual r and the last sublayer's output y; the first gets
           y = Emb[tok] and no r.
           r' = (r + b_r) * s_r + (y + b_y) * s_y  (first: (y + b_y) * s_y)
           u = RMSNorm_j(r');  y' = sublayer_j(u)                    (paper)
           after the last sublayer one more merge, the final RMSNorm,
           logits = u Emb^T                        (`tie_word_embeddings`)
  CCA      q~ = u W_q (Hq heads), k~ = u W_k (G heads); z = [q~ ; k~]
           a_t = w0[:, 0] z_{t-1} + w0[:, 1] z_t + beta0: depthwise,
               causal, `cca_time0` = 2 taps                 (config + paper)
           c_t[h] = a_{t-1}[h] W1[h, 0] + a_t[h] W1[h, 1] + beta1[h]: one
               (D, D) group a head, `cca_time1` = 2 taps    (config + paper)
           the q-k mean: mq[h] = (q~[h] + k~[h // R]) / 2;
               mk[g] = (mean_{h in g} q~[h] + k~[g]) / 2;
               q = c[:Hq] + mq, k = c[Hq:] + mk                     (paper)
           q <- sqrt(D) q / |q|, k <- sqrt(D) exp(tau_g) k / |k|    (paper)
           RoPE on the first `partial_rotary_factor` * D numbers of a head,
               half-split pairs, theta `rope_parameters.hybrid.rope_theta`;
               the rest pass
           v_t = [u_t W_v1 ; u_{t-1} W_v2]: the value shift, the first half
               of the key-value heads the token's own value, the second the
               previous token's                                      (paper)
           y = softmax(q k^T / sqrt(D), causal) v W_o
  experts  rho_l = u W_d + b_d (+ gamma_l * rho_{l-1} for l > 0, rho_{l-1}
               the last layer's after its own sum: depth averaging) (paper)
           p = softmax(gelu(gelu(RMSNorm(rho) W_1 + b_1) W_2 + b_2) W_3)
               over `num_experts` + 1, gelu exact                    (paper)
           e = argmax(p + beta): beta selects and does not weigh     (paper)
           e < `num_experts`: y = p_e E_e(u), E(x) = (silu(x W_g) * x W_u)
               W_dn; e = `num_experts`: y = 0, the token skips the layer's
               experts                                               (paper)

Which layers: `layer_types` gives every published layer's kind (all
`hybrid`); `kept_layers`, where the file has it, the published layers that
are run, in order (the cut in depth).

Departures from the source, each for a reason:
  - Weights are seeded random and bfloat16-VALUED (the precision the
    configuration states), held as bfloat16 and upcast to float32 one
    matrix, and one expert, at a time. ASSUMED: every matrix
    normal(0, `initializer_range` or 0.02), the family's convention and
    1/sqrt(fan-in) at the hidden size, EXCEPT those whose fan-in is small
    (the depthwise taps: 2; the grouped taps: 2 D; the router MLP's W_1,
    W_2, W_3: `router_hidden_size`), normal(0, 1/sqrt(fan-in)): at 0.02
    the convolutions would add a hundredth of what the q-k mean adds and
    the 17 router outputs would differ by 0.01, so that every token chose
    by beta alone. Norm gains 1.
  - The terms a trained checkpoint learns around a fixed point are drawn
    with a spread, so that a term left out shows: the merges' scales
    1 + normal(0, `residual_scale_std`), their biases and the conv and
    router biases normal(0, `bias_std`), tau and gamma normal(0,
    `tau_std`), normal(0, `gamma_std`).
  - The selection bias beta is NOT drawn: a checkpoint learns it to keep
    the experts' loads even, and a random router MLP prefers some of its
    outputs whatever it is fed (drawn, or evened out over normal rows, it
    left 3 to 12 of 16 experts with a row, by the seed: the file has the
    chip readings). So each layer's beta is evened out over the rows THAT
    LAYER'S ROUTER IS FED (`router_balance`): `sequences` of `positions`
    tokens from the seed, a prompt of `prompt` drawn tokens and an answer
    in which sequence k repeats its last token with probability REPEAT
    * k / (sequences - 1) and draws a fresh one otherwise (from distinct
    tokens, as sampled requests write them at these weights, to long runs
    of one, as greedy ones do), go through the layers as they are made,
    and at every layer each output's bias is set, in turn and ROUNDS
    times over, to the value at which 1 / (E + 1) of the answer rows
    choose it (`_evened`). MANY sequences, because a row's choice goes
    mostly by what stands before it and little by its own token: eight
    sequences of 1,024 evened out eight contexts and left a served step
    13.4 of 16 experts a layer, sixty-four of 512 left it 14.9 (real
    widths, depth 20, on the CPU).
  - The router, its weights and everything from rho to e are float32 in
    every precision.
  - Every matrix is (in, out): y = x W, where the source stores (out, in).

`precision` selects the arithmetic of the *control*, never of the
reference: None is float32; "fp8" rounds both operands of every matmul but
the router's to float8_e4m3 under per-tensor scales (the nearest precision
below bfloat16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.gpt2 import _mm  # matmul, or its fp8 control

QUERY_ROWS = 512        # query rows a block of the score matrix
# the balancing rows' answers: sequence k repeats its last token with
# probability REPEAT * k / (sequences - 1); each output's bias is set
# ROUNDS times over
REPEAT, ROUNDS = 0.9, 8


def kept_layers(cfg: dict) -> list:
    """The published layers that are run, in order."""
    kept = list(cfg.get("kept_layers", range(len(cfg["layer_types"]))))
    if len(kept) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(kept)} layers kept, num_hidden_layers "
                         f"{cfg['num_hidden_layers']}")
    if any(cfg["layer_types"][i] != "hybrid" for i in kept):
        raise ValueError("a layer type other than 'hybrid'")
    return kept


def _draws(seed, part, cfg: dict):
    """The two ways a part of the tree (0: the top; n + 1: layer n) draws a
    leaf: `normal` in bfloat16 at the initialiser's scale, `f32` in float32
    at a scale given. `part` may be traced."""
    std = cfg.get("initializer_range", 0.02)
    key = jax.random.fold_in(
        jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32)), part)
    count = iter(range(10 ** 6))

    def normal(*shape, scale=std, dtype=jnp.bfloat16):
        return (jax.random.normal(jax.random.fold_in(key, next(count)),
                                  shape, jnp.float32) * scale).astype(dtype)

    def f32(*shape, scale):
        return normal(*shape, scale=scale, dtype=jnp.float32)

    return normal, f32


def _merge_init(f32, cfg, first=False):
    d = cfg["hidden_size"]
    scale, bias = (cfg.get("residual_scale_std", 0.1),
                   cfg.get("bias_std", 0.02))
    m = {"s_y": 1.0 + f32(d, scale=scale), "b_y": f32(d, scale=bias)}
    if not first:
        m.update(s_r=1.0 + f32(d, scale=scale), b_r=f32(d, scale=bias))
    return m


def init_top(seed, cfg: dict) -> dict:
    """The embedding (which is the head), the last merge, the final norm."""
    normal, f32 = _draws(seed, 0, cfg)
    d = cfg["hidden_size"]
    return {"embed": normal(cfg["vocab_size"], d),
            "out_merge": _merge_init(f32, cfg),
            "norm": jnp.ones((d,), jnp.float32)}


def init_layer(seed, cfg: dict, n, first: bool, stream):
    """Layer n of those that are run (n may be traced: one program makes
    every layer but the first, which has no residual to scale before its
    attention and no router state below it), drawn, and its selection
    bias evened out over the balancing rows, whose `stream` (r, y, rho)
    comes from the layer below (`balance_stream` before the first).
    -> (the layer, the stream after it)."""
    normal, f32 = _draws(seed, n + 1, cfg)
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    hq, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    rh = cfg["router_hidden_size"]
    bias = cfg.get("bias_std", 0.02)
    lp = {
        "attn_merge": _merge_init(f32, cfg, first),
        "attn_norm": jnp.ones((d,), jnp.float32),
        "w_q": normal(d, hq * dh), "w_k": normal(d, g * dh),
        "w_v1": normal(d, g * dh // 2), "w_v2": normal(d, g * dh // 2),
        "w_o": normal(hq * dh, d),
        "conv0": f32(2, (hq + g) * dh, scale=2 ** -0.5),
        "conv0_bias": f32((hq + g) * dh, scale=bias),
        "conv1": normal(2, hq + g, dh, dh, scale=(2 * dh) ** -0.5),
        "conv1_bias": f32(hq + g, dh, scale=bias),
        "tau": f32(g, scale=cfg.get("tau_std", 0.3)),
        "moe_merge": _merge_init(f32, cfg),
        "moe_norm": jnp.ones((d,), jnp.float32),
        "r_down": f32(d, rh, scale=cfg.get("initializer_range", 0.02)),
        "r_down_bias": f32(rh, scale=bias),
        "r_norm": jnp.ones((rh,), jnp.float32),
        "r_1": f32(rh, rh, scale=rh ** -0.5), "r_1_bias": f32(rh, scale=bias),
        "r_2": f32(rh, rh, scale=rh ** -0.5), "r_2_bias": f32(rh, scale=bias),
        "r_3": f32(rh, e + 1, scale=rh ** -0.5),
        "e_g": normal(e, d, f), "e_u": normal(e, d, f),
        "e_d": normal(e, f, d),
    }
    if not first:
        lp["r_gamma"] = f32(rh, scale=cfg.get("gamma_std", 0.3))
    return _evened(lp, stream, cfg)


def balance_tokens(seed, cfg: dict):
    """(sequences, positions) int32: the tokens the selection biases are
    evened out over: a prompt of `prompt` tokens drawn alike over
    the vocabulary, then an answer in which sequence k repeats its last
    token with probability REPEAT * k / (sequences - 1) and draws a
    fresh one otherwise."""
    b = cfg["router_balance"]
    k, s = b["sequences"], b["positions"]
    key = jax.random.fold_in(
        jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32)), 2 ** 20)
    fresh = jax.random.randint(jax.random.fold_in(key, 0), (k, s), 0,
                               cfg["vocab_size"])
    share = REPEAT * jnp.arange(k) / max(k - 1, 1)
    again = (jax.random.uniform(jax.random.fold_in(key, 1), (k, s))
             < share[:, None]) & (jnp.arange(s) >= b["prompt"])[None, :]
    # a position's token is the last fresh one at or before it
    last = lax.cummax(jnp.where(again, 0, jnp.arange(s)[None, :]), axis=1)
    return jnp.take_along_axis(fresh, last, axis=1)


def balance_stream(top: dict, tokens):
    """The stream (r, y, rho) of the balancing rows before the first
    layer."""
    return None, embed(top, tokens), None


def _even_bias(p):
    """p (N, outputs) -> the bias (outputs,) under which argmax(p + bias)
    gives every output N // outputs rows, as nearly as the rows allow:
    one output at a time, its bias becomes a value that just so many
    rows' margins over the others fall under."""
    n, outs = p.shape

    def one(i, bias):
        e = i % outs
        others = jnp.where(jnp.arange(outs) == e, -jnp.inf, p + bias)
        margin = jnp.sort(jnp.max(others, -1) - jnp.take(p, e, axis=1))
        # half way between the last row that chooses it and the first
        # that does not: no row is left at a tie
        return bias.at[e].set(
            (margin[n // outs - 1] + margin[n // outs]) / 2)

    return lax.fori_loop(0, ROUNDS * outs, one,
                         jnp.zeros((outs,), jnp.float32))


def _evened(lp, stream, cfg: dict):
    """The layer with its selection bias evened out over the balancing
    rows' ANSWER positions (what a decode step routes), and those rows'
    stream after the layer."""
    b = cfg["router_balance"]
    r, y, rho = stream
    eps = cfg["rms_norm_eps"]
    r = _merged(lp["attn_merge"], r, y)
    y = jax.vmap(lambda u: attention(lp, u, cfg))(
        _rms(r, lp["attn_norm"], eps))
    r = _merged(lp["moe_merge"], r, y)
    u = _rms(r, lp["moe_norm"], eps).reshape(-1, r.shape[-1])
    before = None if rho is None else rho.reshape(-1, rho.shape[-1])
    with jax.default_matmul_precision("highest"):
        _, p = router(lp, u, before, cfg)
    answers = p.reshape(*r.shape[:2], -1)[:, b["prompt"]:]
    lp = dict(lp, r_select_bias=_even_bias(
        answers.reshape(-1, p.shape[-1])))
    y, rho = experts(lp, u, before, cfg)
    return lp, (r, y.reshape(r.shape), rho.reshape(*r.shape[:-1], -1))


def init(seed, cfg: dict) -> dict:
    """The whole tree as one traceable function of the seed (a uint32
    scalar). The family file makes the same tree a part at a time
    (`init_top`, `init_layer` with the seed, the layer AND the balancing
    rows as arguments), so that three small programs, the same for every
    seed, make a model of any depth."""
    top = init_top(seed, cfg)
    stream, layers = balance_stream(top, balance_tokens(seed, cfg)), []
    for n in range(len(kept_layers(cfg))):
        lp, stream = init_layer(seed, cfg, n, n == 0, stream)
        layers.append(lp)
    return {**top, "layers": layers}


def _f32(w):
    return w.astype(jnp.float32)


def _rms(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _previous(x):
    """x (S, ...) -> the row before each: row 0 becomes zero."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], 0)


def _partial_rope(x, pos, theta, rot):
    """x (S, H, D): the first `rot` numbers of each head rotated at
    positions pos (S,), the pairs (i, i + rot/2); the rest pass."""
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = (pos.astype(jnp.float32)[:, None] * inv)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [a * cos - b * sin, a * sin + b * cos, x[..., rot:]], -1)


def _merged(m, r, y):
    new = (y + m["b_y"]) * m["s_y"]
    if r is not None:
        new = (r + m["b_r"]) * m["s_r"] + new
    return new


def attention(lp, u, cfg, precision=None):
    """u (S, hidden) -> the CCA sublayer's output (S, hidden)."""
    s = u.shape[0]
    hq, g, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    rope = cfg["rope_parameters"]["hybrid"]
    pos = jnp.arange(s)
    q_lat = _mm(u, _f32(lp["w_q"]), precision).reshape(s, hq, dh)
    k_lat = _mm(u, _f32(lp["w_k"]), precision).reshape(s, g, dh)
    z = jnp.concatenate([q_lat, k_lat], 1)              # (S, Hq + G, D)
    w0 = lp["conv0"].reshape(2, hq + g, dh)
    a = w0[0] * _previous(z) + w0[1] * z \
        + lp["conv0_bias"].reshape(hq + g, dh)
    w1 = _f32(lp["conv1"])                              # (2, H, D, D)
    c = (_mm(_previous(a).transpose(1, 0, 2), w1[0], precision)
         + _mm(a.transpose(1, 0, 2), w1[1], precision)).transpose(1, 0, 2) \
        + lp["conv1_bias"]
    k_of_q = jnp.repeat(k_lat, hq // g, axis=1)         # head h: k~[h // R]
    q = c[:, :hq] + (q_lat + k_of_q) / 2
    q_of_k = jnp.mean(q_lat.reshape(s, g, hq // g, dh), 2)
    k = c[:, hq:] + (q_of_k + k_lat) / 2
    q = dh ** 0.5 * q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = dh ** 0.5 * jnp.exp(lp["tau"])[None, :, None] * k \
        / jnp.linalg.norm(k, axis=-1, keepdims=True)
    rot = int(dh * rope["partial_rotary_factor"])
    q = _partial_rope(q, pos, rope["rope_theta"], rot)
    k = _partial_rope(k, pos, rope["rope_theta"], rot)
    v = jnp.concatenate(
        [_mm(u, _f32(lp["w_v1"]), precision),
         _previous(_mm(u, _f32(lp["w_v2"]), precision))], -1
    ).reshape(s, g, dh)
    # every query head beside its key-value head: repeat each of the G
    k = jnp.repeat(k, hq // g, axis=1).transpose(1, 2, 0)   # (Hq, D, S)
    v = jnp.repeat(v, hq // g, axis=1).transpose(1, 0, 2)   # (Hq, S, D)
    rows = QUERY_ROWS if s % QUERY_ROWS == 0 else s

    def block(args):
        i, qi = args                                        # (rows, Hq, D)
        score = _mm(qi.transpose(1, 0, 2), k, precision) * dh ** -0.5
        iq = (i * rows + jnp.arange(rows))[:, None]
        p = jax.nn.softmax(
            jnp.where(pos[None, :] <= iq, score, -1e30), axis=-1)
        return _mm(p, v, precision).transpose(1, 0, 2).reshape(rows, -1)

    o = lax.map(block, (jnp.arange(s // rows),
                        q.reshape(s // rows, rows, hq, dh))).reshape(s, -1)
    return _mm(o, _f32(lp["w_o"]), precision)


def router(lp, u, rho_before, cfg):
    """-> (rho (S, router_hidden), p (S, E + 1) float32: the router MLP's
    softmax over the experts and, last, the choice of none)."""
    rho = jnp.matmul(u, lp["r_down"]) + lp["r_down_bias"]
    if rho_before is not None:
        rho = rho + lp["r_gamma"] * rho_before
    h = _rms(rho, lp["r_norm"], cfg["rms_norm_eps"])
    h = jax.nn.gelu(jnp.matmul(h, lp["r_1"]) + lp["r_1_bias"],
                    approximate=False)
    h = jax.nn.gelu(jnp.matmul(h, lp["r_2"]) + lp["r_2_bias"],
                    approximate=False)
    return rho, jax.nn.softmax(jnp.matmul(h, lp["r_3"]), axis=-1)


def routing(lp, u, rho_before, cfg):
    """-> (rho, (S, E) float32: p_e where token t chose expert e, else 0;
    a token that chose output E has a row of zeros)."""
    rho, p = router(lp, u, rho_before, cfg)
    chosen = jnp.argmax(p + lp["r_select_bias"], axis=-1)
    e = cfg["num_experts"]
    return rho, jnp.where(jnp.arange(e)[None, :] == chosen[:, None],
                          p[:, :e], 0.0)


def experts(lp, u, rho_before, cfg, precision=None):
    """u (S, hidden) -> (the expert sublayer's output, rho)."""
    rho, weights = routing(lp, u, rho_before, cfg)

    def one_expert(acc, xs):
        w_g, w_u, w_d, column = xs
        h = jax.nn.silu(_mm(u, _f32(w_g), precision)) \
            * _mm(u, _f32(w_u), precision)
        return acc + column[:, None] * _mm(h, _f32(w_d), precision), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(u),
                    (lp["e_g"], lp["e_u"], lp["e_d"], weights.T))
    return y, rho


def layer(lp, r, y, rho, cfg: dict, precision=None):
    """One layer on the stream of ONE sequence: the residual r and the last
    sublayer's output y (S, hidden), the router state rho of the layer
    below; r and rho are None before the first layer. -> (r, y, rho)."""
    eps = cfg["rms_norm_eps"]
    r = _merged(lp["attn_merge"], r, y)
    y = attention(lp, _rms(r, lp["attn_norm"], eps), cfg, precision)
    r = _merged(lp["moe_merge"], r, y)
    y, rho = experts(lp, _rms(r, lp["moe_norm"], eps), rho, cfg, precision)
    return r, y, rho


def embed(params, tokens):
    return _f32(params["embed"][tokens])


def final(params, r, y, cfg: dict):
    """The stream after the last layer -> final-norm hidden states."""
    return _rms(_merged(params["out_merge"], r, y), params["norm"],
                cfg["rms_norm_eps"])


def hidden(params: dict, tokens, cfg: dict, precision=None):
    """(S,) tokens of ONE sequence -> (S, hidden) final-norm hidden
    states."""
    if precision not in (None, "fp8"):
        raise ValueError(f"precision {precision!r}: None (the reference) or "
                         "'fp8' (its control)")
    r, rho = None, None
    y = embed(params, tokens)
    for lp in params["layers"]:
        r, y, rho = layer(lp, r, y, rho, cfg, precision)
    return final(params, r, y, cfg)


def head(params, hid, precision=None):
    """(N, hidden) final-norm hidden states -> (N, V) float32 logits: the
    embedding, transposed."""
    return _mm(hid, _f32(params["embed"]).T, precision)


def logits(params, tokens, cfg, precision=None):
    """(B, S) tokens -> (B, S, V) float32 logits, a sequence at a time."""
    return lax.map(lambda t: head(params, hidden(params, t, cfg, precision),
                                  precision), tokens)
