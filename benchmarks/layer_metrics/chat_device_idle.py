"""1 - union of device op intervals over the traced window, in percent."""

from benchmarks.harness.readers import device_idle_share


def read(ctx):
    return device_idle_share(ctx)
