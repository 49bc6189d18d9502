"""Roofline share of the train step's attention kernels (all tpu_custom_call time of the step)."""

from benchmarks.harness.readers import attention_kernel_roofline


def read(ctx):
    return attention_kernel_roofline(ctx)
