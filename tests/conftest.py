"""Test configuration.

Mirrors the reference's test strategy (SURVEY.md §4): the reference tests
distributed code paths on Spark `local[N]` without a cluster; we test
multi-chip code paths on a virtual 8-device CPU mesh via
`--xla_force_host_platform_device_count` — the real sharding/collective
code runs unchanged.

Tests run CPU-only: the platform is pinned to cpu here, before any
backend is materialized, so a test run never reaches for (or holds) an
accelerator.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)
