"""Model FLOP/s utilization of the training cell, from the benchmark's own count."""

from benchmarks.harness.readers import train_mfu


def read(ctx):
    return train_mfu(ctx)
