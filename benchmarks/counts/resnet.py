"""Operations of the `resnet` family from layer shapes (multiply-add = 2;
backward = 2x forward; BatchNorm, ReLU and pooling are left out, so a
utilization from this count reads low rather than high)."""

from __future__ import annotations

from benchmarks.reference.resnet import blocks


def forward_flops_per_image(cfg: dict) -> float:
    size = cfg["image_size"]
    hw = size // 2                      # conv1, stride 2
    total = 2.0 * 7 * 7 * 3 * 64 * hw * hw
    hw //= 2                            # 3x3 max pool, stride 2
    n_out = 64
    for _, n_in, planes, n_out, stride, kind in blocks(cfg):
        out_hw = hw // stride
        if kind == "bottleneck":
            total += 2.0 * n_in * planes * hw * hw
            total += 2.0 * 9 * planes * planes * out_hw * out_hw
            total += 2.0 * planes * n_out * out_hw * out_hw
        else:
            total += 2.0 * 9 * n_in * planes * out_hw * out_hw
            total += 2.0 * 9 * planes * n_out * out_hw * out_hw
        if n_in != n_out or stride != 1:
            total += 2.0 * n_in * n_out * out_hw * out_hw
        hw = out_hw
    return total + 2.0 * n_out * cfg["num_classes"]


def train_flops_per_record(cfg: dict, traffic: dict) -> float:
    return 3.0 * forward_flops_per_image(cfg)
