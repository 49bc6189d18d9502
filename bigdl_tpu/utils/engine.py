"""Engine — runtime/topology discovery and global configuration.

Reference parity: utils/Engine.scala (Engine.init, coreNumber, nodeNumber,
Engine.model/Engine.default thread pools) and utils/ThreadPool.scala.

TPU-first redesign: the reference's Engine discovers Spark executor/core
topology and builds OpenMP-pinned thread pools; here Engine discovers the
JAX device/process topology (PJRT) and builds the default
`jax.sharding.Mesh`. Thread pools are unnecessary — intra-op parallelism
belongs to XLA — so `core_number` reports host CPUs for the *input
pipeline* only.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np


# Published per-chip peaks, keyed by `jax.devices()[0].device_kind`.
# One table for every script that turns a time into a utilization; a
# device that is not in it is an error, never a default.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
    # 1,600 Gbit/s of chip-to-chip interconnect per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 200e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def device_peaks(device_kind: Optional[str] = None) -> dict:
    """Peaks of the device a measurement ran on (default: this
    process's first device). Raises on a `device_kind` the table does
    not hold — a utilization against another chip's peak is a wrong
    number, not an approximate one."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)} — add the chip to "
            "bigdl_tpu.utils.engine.DEVICE_PEAKS with its source")
    return DEVICE_PEAKS[device_kind]


def bf16_utilization(flops_per_s: float) -> Optional[float]:
    """`flops_per_s` as a share of this process's device's bf16 peak.
    None off-TPU: a CPU run has no device utilization to report."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    return flops_per_s / device_peaks(dev.device_kind)["bf16_flops_per_s"]


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache; call before the first
    compile. `JAX_COMPILATION_CACHE_DIR` set → JAX already reads it,
    nothing is set in code. Unset → `<checkout>/.jax_cache`, derived
    from this package's location: the directory is part of the cache
    key's world, so it must be the same path on every run (never a
    tempdir, a pid or the clock). Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class Engine:
    """Process-wide runtime info. All methods are class-level, mirroring the
    reference's singleton `Engine` object."""

    _initialized = False
    _node_number: int = 1
    _core_number: int = 1

    @classmethod
    def init(cls) -> None:
        """Discover topology. Safe to call repeatedly.

        Reference parity: utils/Engine.scala#Engine.init — there it
        validates spark conf / executor cores; here it reads the PJRT
        process group (multi-host via jax.distributed) and host cores.
        """
        cls._node_number = jax.process_count()
        cls._core_number = os.cpu_count() or 1
        cls._initialized = True

    @classmethod
    def init_distributed(
        cls,
        coordinator_address: Optional[str] = None,
        num_processes: Optional[int] = None,
        process_id: Optional[int] = None,
    ) -> None:
        """Multi-host bring-up: one process per TPU host (the reference ran
        one Spark executor per node; utils/Engine.scala#Engine.init).

        Wraps `jax.distributed.initialize`, which wires the PJRT process
        group over DCN; collectives inside `jit` then span all hosts' chips.
        Off-cloud, scripts/launch_pod.sh exports BIGDL_COORDINATOR /
        BIGDL_NUM_PROCESSES / BIGDL_PROCESS_ID, picked up here; on Cloud
        TPU VMs everything is discovered from the metadata server and
        plain `Engine.init_distributed()` suffices.
        """
        if coordinator_address is None:
            coordinator_address = os.environ.get("BIGDL_COORDINATOR")
            if coordinator_address is not None:
                n = os.environ.get("BIGDL_NUM_PROCESSES")
                pid = os.environ.get("BIGDL_PROCESS_ID")
                if n is None or pid is None:
                    raise ValueError(
                        "BIGDL_COORDINATOR is set but "
                        f"BIGDL_NUM_PROCESSES={n!r} / "
                        f"BIGDL_PROCESS_ID={pid!r}; all three must be set "
                        "together (scripts/launch_pod.sh exports them)")
                num_processes = int(n)
                process_id = int(pid)
        if coordinator_address is not None:
            # (CPU multi-process — the local[N]-style smoke/drill
            # topology, scripts/multihost_smoke.py — needs nothing
            # extra: gloo is the CPU client's default cross-process
            # collective implementation.)
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
        elif os.environ.get("TPU_NAME"):
            # Cloud TPU VM: topology from metadata, no flags needed.
            # IMPORTANT: nothing may touch a jax backend before this call
            # (backend init would make initialize() fail) — so no
            # process_count() precheck here. A failure RAISES: a pod
            # member that carried on single-process would train on its
            # own shard alone and report a healthy run.
            jax.distributed.initialize()
        cls.init()

    @classmethod
    def node_number(cls) -> int:
        if not cls._initialized:
            cls.init()
        return cls._node_number

    @classmethod
    def core_number(cls) -> int:
        if not cls._initialized:
            cls.init()
        return cls._core_number

    @classmethod
    def device_count(cls) -> int:
        return jax.device_count()

    @classmethod
    def local_device_count(cls) -> int:
        return jax.local_device_count()

    @classmethod
    def default_mesh(cls, axis_names: Sequence[str] = ("data",)) -> jax.sharding.Mesh:
        """Build the default mesh over all devices.

        With one axis this is pure data parallelism — the direct analogue of
        the reference's partition-per-executor layout
        (parameters/AllReduceParameter.scala#AllReduceParameter.init).
        """
        devices = np.array(jax.devices())
        if len(axis_names) == 1:
            devices = devices.reshape(-1)
        else:
            raise ValueError(
                "default_mesh builds 1-D meshes; build multi-axis meshes via "
                "bigdl_tpu.parallel.mesh.make_mesh"
            )
        return jax.sharding.Mesh(devices, axis_names)
