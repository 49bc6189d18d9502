"""Operations and bytes of the `granite_hybrid` family, from shapes alone.
The benchmark's own count: a PR may change the program, not the yardstick.

Decode is bound by memory traffic, so what is counted is BYTES, and only
those that any implementation of the step has to move through HBM once:
every weight (the tied embedding once, as the head), the cache rows of the
positions the seated slots hold in the attention layers, and the seated
slots' recurrent state READ AND WRITTEN. The write is counted, against the
other families' rule (their written rows are a token's: thousands of times
less than what is read): no implementation of the recurrence can leave the
new state unwritten, and it is as large as what was read. Activations, the
sampler, the written key and value rows, gathered copies, the embedding's
rows and anything moved twice are left out, so a share of the roofline
from these counts can only read low, never above 100%.

Prefill is bound by the matrix unit, so what is counted there is FLOPs,
and only those a prefill has to do: a prompt's prefill leaves rows and
states and no logits (the engine re-decodes the last prompt token), so
nothing reads the last layer's output: its mixer's output projection, its
MLP, the final norm and the head are not counted (and the compiler drops
them from the program)."""

from __future__ import annotations


def layer_plan(cfg: dict) -> list:
    """[(mixer kind, FFN kind)] of the layers that are run: the source's
    `layer_types`, each followed by the one gated MLP."""
    return [(kind, "mlp") for kind in cfg["layer_types"]]


def _mamba(cfg: dict):
    """(H, P, N, K, the inner width H P, the convolved width)."""
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    return h, p, n, cfg["mamba_d_conv"], h * p, h * p + 2 * n


def mamba_matrix_params(cfg: dict) -> int:
    """`in_proj` and `out_proj`: held in the weights' dtype."""
    h, _, _, _, inner, conv = _mamba(cfg)
    d = cfg["hidden_size"]
    return d * (inner + conv + h) + inner * d


def mamba_float32_params(cfg: dict) -> int:
    """The depthwise taps and their bias, A_log, D, dt_bias and the gated
    norm's gain."""
    h, _, _, k, inner, conv = _mamba(cfg)
    return (k + 1) * conv + 3 * h + inner


def attention_matrix_params(cfg: dict) -> int:
    d, hq, g = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    return 2 * d * d + 2 * d * g * (d // hq)


def mlp_params(cfg: dict) -> int:
    """`input_linear` (gate and value side by side) and `output_linear`."""
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def matrix_params(cfg: dict) -> int:
    """Every matrix of the layers, the embedding (= the head) once."""
    kinds = [kind for kind, _ in layer_plan(cfg)]
    n_mamba = kinds.count("mamba")
    return (cfg["hidden_size"] * cfg["vocab_size"]
            + len(kinds) * mlp_params(cfg)
            + n_mamba * mamba_matrix_params(cfg)
            + (len(kinds) - n_mamba) * attention_matrix_params(cfg))


def float32_params(cfg: dict) -> int:
    """Two norms a layer, the final norm, and the mamba layers' small
    leaves."""
    kinds = [kind for kind, _ in layer_plan(cfg)]
    return ((2 * len(kinds) + 1) * cfg["hidden_size"]
            + kinds.count("mamba") * mamba_float32_params(cfg))


def params_held(cfg: dict) -> int:
    return matrix_params(cfg) + float32_params(cfg)


def cache_row_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """A token's key and value rows in ONE attention layer."""
    d, hq, g = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    return 2 * g * (d // hq) * cache_bytes


def slot_state_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """What one slot keeps beside its rows, all mamba layers: the
    recurrence's state (H, P, N) in float32 and the K - 1 rows before the
    convolution in the cache's dtype."""
    h, p, n, k, _, conv = _mamba(cfg)
    n_mamba = [kind for kind, _ in layer_plan(cfg)].count("mamba")
    return n_mamba * (4 * h * p * n + cache_bytes * (k - 1) * conv)


def decode_bytes_per_step(cfg: dict, cached_tokens: float, active: float,
                          weight_bytes: int = 2, cache_bytes: int = 2
                          ) -> float:
    """Least HBM traffic of ONE decode step: every weight once, the state
    of the `active` seated slots read and written, and the rows of the
    `cached_tokens` positions those slots hold (summed over the slots) in
    each attention layer."""
    n_attn = [kind for kind, _ in layer_plan(cfg)].count("attention")
    return float(weight_bytes * matrix_params(cfg) + 4 * float32_params(cfg)
                 + 2 * active * slot_state_bytes(cfg, cache_bytes)
                 + n_attn * cached_tokens * cache_row_bytes(cfg, cache_bytes))


def prefill_flops(cfg: dict, bucket: int) -> float:
    """FLOPs a prefill of `bucket` positions (the padded length: what the
    program computes) has to do: 2 a matrix parameter a position, the
    attention's causal scores and sums, the convolution and the recurrence
    itself (a state's decay, its increment and its read: 5 H P N a
    position, the sequential form's; a chunked form does more and is held
    to this). Left out: what nothing reads (module docstring)."""
    kinds = [kind for kind, _ in layer_plan(cfg)]
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    h, p, n, k, inner, conv = _mamba(cfg)
    matrices = 0
    for i, kind in enumerate(kinds):
        last = i == len(kinds) - 1
        if kind == "mamba":
            matrices += d * (inner + conv + h) + (0 if last else inner * d)
        else:
            matrices += attention_matrix_params(cfg) - (2 * d * d if last
                                                        else 0)
        matrices += 0 if last else mlp_params(cfg)
    n_mamba = kinds.count("mamba")
    # an attention layer that is the last keeps its rows and attends none
    scored = len(kinds) - n_mamba - (kinds[-1] == "attention")
    pairs = bucket * (bucket + 1) / 2       # (query, key) a causal layer
    return float(2 * matrices * bucket
                 + scored * 4 * hq * (d // hq) * pairs
                 + n_mamba * bucket * (5 * h * p * n + 2 * k * conv))
