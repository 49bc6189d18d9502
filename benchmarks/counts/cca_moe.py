"""Operations and bytes of the `zaya` family, from shapes alone. The
benchmark's own count: a PR may change the program, not the yardstick.

Decode is bound by memory traffic, so what is counted is BYTES, and only
those that any implementation of the step has to read from HBM once:
weights that the step's tokens use (an expert only if a token chose it; the
tied embedding once, as the head), the cache rows the step's attention sees
and the slots' state. Activations, the sampler, written cache rows and
state, gathered copies, the embedding's rows and anything read twice are
left out, so a share of the roofline from these counts can only read low,
never above 100%."""

from __future__ import annotations


def layer_plan(cfg: dict) -> list:
    """[(attention kind, FFN kind)] of the layers that are run: every one
    keeps all of a slot's rows (no window) and routes to experts."""
    return [("full_attention", "moe")] * cfg["num_hidden_layers"]


def _heads(cfg: dict):
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def attention_matrix_params(cfg: dict) -> int:
    """W_q, W_k, W_v1, W_v2, W_o and the grouped convolution's two taps:
    held in the weights' dtype."""
    d = cfg["hidden_size"]
    hq, g, dh = _heads(cfg)
    return (2 * d * hq * dh + 2 * d * g * dh
            + cfg["cca_time1"] * (hq + g) * dh * dh)


def attention_float32_params(cfg: dict) -> int:
    """The depthwise taps, both convolutions' biases and tau."""
    hq, g, dh = _heads(cfg)
    return (cfg["cca_time0"] + 2) * (hq + g) * dh + g


def router_params(cfg: dict, layer: int) -> int:
    """W_d, W_1, W_2 with their biases, W_3, the norm's gain, the selection
    bias and (every layer but the first) gamma: float32."""
    d, rh, e = cfg["hidden_size"], cfg["router_hidden_size"], \
        cfg["num_experts"]
    return (d * rh + rh + 2 * (rh * rh + rh) + rh * (e + 1) + rh + (e + 1)
            + (rh if layer else 0))


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def merge_and_norm_params(cfg: dict, layer: int) -> int:
    """A layer's two norms and two merges (four vectors each; the first
    sublayer of the model has no residual to scale: two)."""
    return (2 + 8 - (0 if layer else 2)) * cfg["hidden_size"]


def float32_params_outside_experts(cfg: dict) -> int:
    """Every float32 parameter of the layers that are run, with the last
    merge and the final norm."""
    return 5 * cfg["hidden_size"] + sum(
        attention_float32_params(cfg) + router_params(cfg, n)
        + merge_and_norm_params(cfg, n)
        for n in range(cfg["num_hidden_layers"]))


def params_held(cfg: dict) -> int:
    """Every parameter the configuration holds on the chip (the embedding
    once: it is the head)."""
    layers = cfg["num_hidden_layers"]
    return (cfg["hidden_size"] * cfg["vocab_size"]
            + float32_params_outside_experts(cfg)
            + layers * (attention_matrix_params(cfg)
                        + cfg["num_experts"] * expert_params(cfg)))


def cache_row_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """A token's key and value rows in one layer."""
    _, g, dh = _heads(cfg)
    return 2 * g * dh * cache_bytes


def slot_state_bytes(cfg: dict) -> int:
    """What one slot keeps beside its rows, all layers: the previous
    token's z and a (Hq + G heads each) and half a value row, float32."""
    hq, g, dh = _heads(cfg)
    return 4 * (2 * (hq + g) * dh + g * dh // 2) * cfg["num_hidden_layers"]


def decode_bytes_per_step(cfg: dict, experts_touched: float,
                          cached_tokens: float, slots: float,
                          weight_bytes: int = 2, cache_bytes: int = 2
                          ) -> float:
    """Least HBM reads of ONE decode step: every layer's weights outside
    its experts, the `experts_touched` experts (summed over the layers)
    that got a token, the head (the embedding, once), the cache rows of
    the `cached_tokens` positions the seated slots hold (summed over the
    slots) in every layer, and the state of the `slots` seated slots."""
    layers = cfg["num_hidden_layers"]
    matrices = (layers * attention_matrix_params(cfg)
                + experts_touched * expert_params(cfg)
                + cfg["hidden_size"] * cfg["vocab_size"])
    return float(weight_bytes * matrices
                 + 4 * float32_params_outside_experts(cfg)
                 + layers * cached_tokens * cache_row_bytes(cfg, cache_bytes)
                 + slots * slot_state_bytes(cfg))
