"""Operations and bytes of the `loop_lm` family, from shapes alone. The
benchmark's own count: a PR may change the program, not the yardstick.

Decode is bound by memory traffic, so what is counted is BYTES, and only
those that any implementation of the step has to move through HBM: the
layers' weights and the final norm's gain ONCE A PASS (`total_ut_steps`
times: a pass needs the whole stack's output before the next begins, and
5 GB of layers do not stay on the chip between passes), the head once, and
the cache rows of the positions the seated slots HOLD, keys and values, in
every row set (a pass of a layer each): not what a read rounds up to.
Activations, the sampler, the written key and value rows, gathered copies,
the embedding's rows and anything moved twice are left out, so a share of
the roofline from these counts can only read low, never above 100%.

A prefill is bound by whichever of the two roofs is higher at its bucket:
its FLOPs (2 a matrix parameter a position a pass, the attention's causal
scores and sums) or its bytes (the same weights once a pass; the rows it
writes). A prompt's prefill leaves rows and no logits (the engine re-decodes
the last prompt token), so nothing reads the last pass's last layer past
its keys and values: its attention, its output projection, its MLP, the
final norm and the head are not counted there."""

from __future__ import annotations


def layer_plan(cfg: dict) -> list:
    """[(attention kind, FFN kind)] of the ROW SETS a step reads: the
    source's `layer_types` once a pass, pass-major (`total_ut_steps` x
    `num_hidden_layers` entries: what `attn_rows_read_over_visible`
    multiplies a row set's visible rows by)."""
    return [(kind, "dense") for kind in cfg["layer_types"]] \
        * cfg["total_ut_steps"]


def attention_matrix_params(cfg: dict) -> int:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    hq, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * hq * dh + 2 * d * g * dh


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_matrix_params(cfg: dict) -> int:
    return attention_matrix_params(cfg) + mlp_params(cfg)


def float32_params(cfg: dict) -> int:
    """Four norm gains a layer, the final norm, the exit gate's vector and
    bias."""
    d = cfg["hidden_size"]
    return 4 * d * cfg["num_hidden_layers"] + 2 * d + 1


def matrix_params(cfg: dict) -> int:
    """Every matrix of ONE set of layers, the embedding and the head."""
    return (cfg["num_hidden_layers"] * layer_matrix_params(cfg)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def params_held(cfg: dict) -> int:
    """One set, whatever `total_ut_steps` is."""
    return matrix_params(cfg) + float32_params(cfg)


def cache_row_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """A token's key and value rows in ONE row set (a pass of a layer)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * cache_bytes


def weight_bytes_per_step(cfg: dict, weight_bytes: int = 2) -> int:
    """What one step streams of the weights: the layers' matrices and
    gains and the final norm's once a pass, the head once."""
    d = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"] * (
        weight_bytes * layer_matrix_params(cfg) + 4 * 4 * d)
    return (cfg["total_ut_steps"] * (layers + 4 * d)
            + weight_bytes * d * cfg["vocab_size"])


def decode_bytes_per_step(cfg: dict, cached_tokens: float, active: float,
                          weight_bytes: int = 2, cache_bytes: int = 2
                          ) -> float:
    """Least HBM traffic of ONE decode step: `weight_bytes_per_step`, and
    the rows of the `cached_tokens` positions the seated slots hold (summed
    over the slots) in each of the `total_ut_steps` x `num_hidden_layers`
    row sets. `active` does not enter (no slot keeps a state)."""
    return float(weight_bytes_per_step(cfg, weight_bytes)
                 + len(layer_plan(cfg)) * cached_tokens
                 * cache_row_bytes(cfg, cache_bytes))


def prefill_bytes(cfg: dict, bucket: int, weight_bytes: int = 2,
                  cache_bytes: int = 2) -> float:
    """Least HBM traffic of a prefill of `bucket` positions: the layers'
    weights once a pass (no head: a prefill leaves no logits) and the rows
    it writes in every row set."""
    d = cfg["hidden_size"]
    head = weight_bytes * d * cfg["vocab_size"]
    return float(weight_bytes_per_step(cfg, weight_bytes) - head
                 + len(layer_plan(cfg)) * bucket
                 * cache_row_bytes(cfg, cache_bytes))


def prefill_flops(cfg: dict, bucket: int) -> float:
    """FLOPs a prefill of `bucket` positions (the padded length: what the
    program computes) has to do: 2 a matrix parameter a position in every
    row set, and the causal scores and sums of its own positions. Left
    out: what nothing reads (module docstring)."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    hq, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sets = len(layer_plan(cfg))
    unread = layer_matrix_params(cfg) - 2 * d * g * dh   # the last set's
    pairs = bucket * (bucket + 1) / 2       # (query, key) a causal layer
    return float(2 * (sets * layer_matrix_params(cfg) - unread) * bucket
                 + (sets - 1) * 4 * hq * dh * pairs)
