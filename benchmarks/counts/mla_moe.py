"""Operations and bytes of the `mla_moe` family, from shapes alone. The
benchmark's own count: a PR may change the program, not the yardstick.

Decode is bound by memory traffic, so what is counted is BYTES, and only
those that any implementation of the step has to read from HBM once:
weights that the step's tokens use, and the cache rows the step's attention
sees. Activations, the sampler, written cache rows, gathered copies and
anything read twice are left out, so a share of the roofline from these
counts can only read low, never above 100%."""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """W_DQ, W_UQ, W_DKV, W_UKV, W_O and the two inner norms."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (d * rq + rq * h * (nope + rope) + d * (rkv + rope)
            + rkv * h * (nope + v) + h * v * d + rq + rkv)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params_outside_experts(cfg: dict, kind: str) -> int:
    """Attention, the two layer norms, and the dense FFN or (MoE layer) the
    shared expert, the router and its bias."""
    d = cfg["hidden_size"]
    n = attention_params(cfg) + 2 * d
    if kind == "dense":
        return n + 3 * d * cfg["intermediate_size"]
    e = cfg["n_routed_experts"]
    return n + cfg["n_shared_experts"] * expert_params(cfg) + d * e + e


def layer_kinds(cfg: dict) -> list:
    k = cfg["first_k_dense_replace"]
    return ["dense" if i < k else "moe"
            for i in range(cfg["num_hidden_layers"])]


def params_held(cfg: dict) -> int:
    """Every parameter the configuration holds on the chip."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    kinds = layer_kinds(cfg)
    return (2 * d * v + d
            + sum(layer_params_outside_experts(cfg, k) for k in kinds)
            + kinds.count("moe") * cfg["n_routed_experts"]
            * expert_params(cfg))


def decode_bytes_per_step(cfg: dict, experts_touched: int,
                          cached_tokens: int, weight_bytes: int = 2,
                          cache_bytes: int = 2) -> float:
    """Least HBM reads of ONE decode step: every layer's weights outside
    its routed experts, the `experts_touched` experts (summed over the MoE
    layers) that got a token, the final norm and the head, and the
    `cached_tokens` latent rows (summed over the slots) of every layer,
    each read once. The embedding's rows (one a slot) are left out."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    kinds = layer_kinds(cfg)
    weights = (sum(layer_params_outside_experts(cfg, k) for k in kinds)
               + experts_touched * expert_params(cfg) + d + d * v)
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return float(weight_bytes * weights
                 + cache_bytes * cached_tokens * len(kinds) * row)
