#!/usr/bin/env bash
# Multi-host TPU pod launcher (reference parity: scripts/bigdl.sh +
# dist/conf/spark-bigdl.conf — there: OpenMP env + required Spark confs;
# here: the env every TPU pod host needs, then one python process per
# host, exactly as the reference ran one Spark executor per node).
#
# Usage, run ON EACH HOST of the pod slice (or via
# `gcloud compute tpus tpu-vm ssh ... --worker=all --command=...`):
#
#   ./scripts/launch_pod.sh python -m bigdl_tpu.models.train \
#       --model resnet50 --synthetic -b 1024 --mesh data=32
#
# On Cloud TPU VMs, JAX discovers the pod topology from the metadata
# server and `jax.distributed.initialize()` (called by Engine.init_distributed
# with no args) needs no flags. Off-cloud, set:
#   BIGDL_COORDINATOR   host:port of process 0
#   BIGDL_NUM_PROCESSES total process count
#   BIGDL_PROCESS_ID    this process's rank
set -euo pipefail

# --- performance env (counterpart of bigdl.sh's OMP_NUM_THREADS etc.) ---
# Donated-buffer reuse + async dispatch are defaults; these keep the host
# input pipeline from fighting XLA's compilation threads.
# (The persistent compile cache is placed by the program itself —
# bigdl_tpu.utils.engine.setup_compile_cache — not from here.)
export TPU_MEGACORE="${TPU_MEGACORE:-}"

# --- distributed bring-up flags consumed by Engine.init_distributed ---
if [[ -n "${BIGDL_COORDINATOR:-}" ]]; then
  export BIGDL_COORDINATOR BIGDL_NUM_PROCESSES BIGDL_PROCESS_ID
fi

exec "$@"
