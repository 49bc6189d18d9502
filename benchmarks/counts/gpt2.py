"""Operations and bytes of the `gpt2` family, from shapes alone. The
benchmark's own count: a PR may change the program, not the yardstick.

Conventions: a multiply-add is 2 operations; the backward pass of a matmul
is twice its forward; recomputed operations (remat, the flash backward's
second QK^T) are NOT counted, so a utilization from these counts can only
read low, never above 100%. Causal attention counts the lower triangle
only: S*(S+1)/2 of the S*S score entries."""

from __future__ import annotations


def _dims(cfg):
    e, l, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    return e, l, v, cfg.get("n_inner") or 4 * e


def matmul_flops_per_token_fwd(cfg: dict) -> float:
    """q, k, v, o projections, the two MLP matmuls, and the tied head."""
    e, l, v, f = _dims(cfg)
    return 2.0 * (l * (4 * e * e + 2 * e * f) + e * v)


def attention_flops_per_seq_fwd(cfg: dict, seq: int) -> float:
    """QK^T and PV over the causal triangle, all heads, all layers."""
    e, l, _, _ = _dims(cfg)
    return l * 2 * 2.0 * e * seq * (seq + 1) / 2


def train_flops_per_record(cfg: dict, traffic: dict) -> float:
    """Forward + backward of one sequence (backward = 2x forward)."""
    s = traffic["seq_len"]
    return 3.0 * (s * matmul_flops_per_token_fwd(cfg)
                  + attention_flops_per_seq_fwd(cfg, s))


def attention_kernel_flops_per_step(cfg: dict, traffic: dict) -> float:
    """What the attention kernels of ONE train step must compute: forward
    (2 matmuls) and backward (4 matmuls) over the causal triangle."""
    return 3.0 * traffic["batch"] * attention_flops_per_seq_fwd(
        cfg, traffic["seq_len"])


def attention_kernel_bytes_per_step(cfg: dict, traffic: dict,
                                    itemsize: int = 2) -> float:
    """Least HBM traffic of one step's attention kernels in the compute
    dtype: forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv (the log-sum-exp rows are 1/head_dim of that and
    are left out, which keeps the count low)."""
    e, l, _, _ = _dims(cfg)
    tensor = traffic["batch"] * traffic["seq_len"] * e * itemsize
    return l * (4 + 8) * float(tensor)
