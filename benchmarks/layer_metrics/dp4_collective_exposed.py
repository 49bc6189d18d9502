"""Collective time during which no compute op runs on that device, over the traced window."""

from benchmarks.harness.readers import collective_exposed_share


def read(ctx):
    return collective_exposed_share(ctx)
