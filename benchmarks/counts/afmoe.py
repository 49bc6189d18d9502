"""Operations and bytes of the `afmoe` family, from shapes alone. The
benchmark's own count: a PR may change the program, not the yardstick.

Decode is bound by memory traffic, so what is counted is BYTES, and only
those that any implementation of the step has to read from HBM once:
weights that the step's tokens use, and the cache rows that the step's
attention is allowed to see: a sliding layer's window, a full layer's whole
context. Activations, the sampler, written cache rows, gathered copies,
rows read and masked, and anything read twice are left out, so a share of
the roofline from these counts can only read low, never above 100%."""

from __future__ import annotations


def layer_plan(cfg: dict) -> list:
    """[(attention kind, FFN kind)] of the layers that are run (the
    reference's reading of `layer_types`, `kept_layers` and
    `num_dense_layers`, counted here again)."""
    kept = cfg.get("kept_layers", range(len(cfg["layer_types"])))
    return [(cfg["layer_types"][i],
             "dense" if n < cfg["num_dense_layers"] else "moe")
            for n, i in enumerate(kept)]


def attention_params(cfg: dict) -> int:
    """W_q, W_k, W_v, the output gate, W_o and the two per-head norms."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    hq, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 3 * d * hq * dh + 2 * d * g * dh + 2 * dh


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params_outside_experts(cfg: dict, ffn: str) -> int:
    """Attention, the four layer norms, and the dense FFN or (MoE layer)
    the shared expert, the router and its bias."""
    d = cfg["hidden_size"]
    n = attention_params(cfg) + 4 * d
    if ffn == "dense":
        return n + 3 * d * cfg["intermediate_size"]
    e = cfg["num_experts"]
    return n + cfg["num_shared_experts"] * expert_params(cfg) + d * e + e


def params_held(cfg: dict) -> int:
    """Every parameter the configuration holds on the chip."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    ffns = [ffn for _, ffn in layer_plan(cfg)]
    return (2 * d * v + d
            + sum(layer_params_outside_experts(cfg, f) for f in ffns)
            + ffns.count("moe") * cfg["num_experts"] * expert_params(cfg))


def cache_row_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """A token's key and value rows in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * cache_bytes


def decode_bytes_per_step(cfg: dict, experts_touched: float,
                          window_rows: float, full_rows: float,
                          weight_bytes: int = 2, cache_bytes: int = 2
                          ) -> float:
    """Least HBM reads of ONE decode step: every layer's weights outside
    its routed experts, the `experts_touched` experts (summed over the MoE
    layers) that got a token, the final norm and the head, and the cache
    rows the step's queries may see: `window_rows` (summed over the slots,
    ONE sliding layer) in each sliding layer and `full_rows` (likewise) in
    each full one, each read once. The embedding's rows (one a slot) are
    left out."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    plan = layer_plan(cfg)
    weights = (sum(layer_params_outside_experts(cfg, f) for _, f in plan)
               + experts_touched * expert_params(cfg) + d + d * v)
    sliding = sum(kind == "sliding_attention" for kind, _ in plan)
    rows = sliding * window_rows + (len(plan) - sliding) * full_rows
    return float(weight_bytes * weights
                 + rows * cache_row_bytes(cfg, cache_bytes))
