"""ZeRO-1 data-parallel training over a chip mesh (reference:
optim/DistriOptimizer + parameters/AllReduceParameter → psum_scatter /
sharded update / all_gather). Run with real chips, or simulate:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python distributed_data_parallel.py
"""

import os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet, Sample
from bigdl_tpu.models import lenet
from bigdl_tpu.optim import Optimizer, SGD, Top1Accuracy, Trigger
from bigdl_tpu.parallel import make_mesh

import jax


def main():
    n_dev = jax.device_count()
    mesh = make_mesh({"data": n_dev})
    rng = np.random.RandomState(0)
    ys = rng.randint(0, 10, 1024).astype(np.int32)
    xs = rng.rand(1024, 28, 28, 1).astype(np.float32)
    samples = [Sample(x, int(y)) for x, y in zip(xs, ys)]

    trained = (
        Optimizer(lenet.build(10), DataSet.array(samples),
                  nn.ClassNLLCriterion(), batch_size=16 * n_dev)
        .set_optim_method(SGD(learningrate=0.05, momentum=0.9))
        .set_end_when(Trigger.max_epoch(1))
        .set_mesh(mesh)
        .optimize()
    )
    print(f"trained with ZeRO-1 DP over {n_dev} devices")
    return trained


if __name__ == "__main__":
    main()
