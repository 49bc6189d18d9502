"""tpu_custom_call device time over device busy time in the traced window."""

from benchmarks.harness.readers import custom_call_share_of_busy


def read(ctx):
    return custom_call_share_of_busy(ctx)
