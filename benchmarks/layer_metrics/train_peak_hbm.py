"""peak_bytes_in_use of the fullest device after the window, in GB."""

from benchmarks.harness.readers import peak_hbm_gb


def read(ctx):
    return peak_hbm_gb(ctx)
