"""Multi-host training example (reference: the multinode MPI launch,
tests/multinode_helpers/mpi_wrapper1.sh + GASNet transport).

One process per host; every process runs THIS script. On TPU pods the
coordinator is auto-discovered; elsewhere set:

    FF_COORDINATOR_ADDRESS=host0:12345 FF_NUM_PROCESSES=2 FF_PROCESS_ID=<i>

Local 2-process smoke test (the CPU analog, 4 virtual devices per
"host"):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    JAX_PLATFORMS=cpu \
    FF_COORDINATOR_ADDRESS=localhost:12345 FF_NUM_PROCESSES=2 \
    FF_PROCESS_ID=0 python examples/multihost_train.py &
    ... FF_PROCESS_ID=1 python examples/multihost_train.py

Each process feeds ITS OWN slice of the global batch (per-node
dataloader partitions, like the reference's SingleDataLoader); the mesh
puts "data" across hosts over DCN and "model" inside each host on ICI.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from flexflow_tpu import FFConfig, LossType, MetricsType, SGDOptimizer
from flexflow_tpu.model import FFModel
from flexflow_tpu.parallel.strategy import megatron_strategy

GLOBAL_BATCH = 64
HIDDEN = 128


def main():
    config = FFConfig(batch_size=GLOBAL_BATCH, workers_per_node=0)
    model = FFModel(config)
    x = model.create_tensor((GLOBAL_BATCH, HIDDEN), name="x")
    t = model.dense(x, 4 * HIDDEN, activation="relu", name="ff1")
    t = model.dense(t, HIDDEN, name="ff2")

    # compile() joins the multi-process job from the env (FF_* vars) and
    # lays the mesh across hosts; dp spans DCN, tp stays on ICI
    nproc = int(os.environ.get("FF_NUM_PROCESSES", "1"))
    dp = max(nproc, GLOBAL_BATCH // 16)
    model.compile(
        optimizer=SGDOptimizer(lr=0.05),
        loss_type=LossType.MEAN_SQUARED_ERROR,
        metrics=[MetricsType.MEAN_SQUARED_ERROR],
        strategy=megatron_strategy(model.graph, dp=dp, tp=2),
    )
    pid, n = jax.process_index(), jax.process_count()
    print(f"process {pid}/{n}: {jax.local_device_count()} local / "
          f"{jax.device_count()} global devices, mesh="
          f"{dict(zip(model.mesh.axis_names, model.mesh.devices.shape))}")

    # this process's slice of the global batch
    rs = np.random.RandomState(0)
    xg = rs.randn(GLOBAL_BATCH, HIDDEN).astype(np.float32)
    yg = rs.randn(GLOBAL_BATCH, HIDDEN).astype(np.float32)
    lo = pid * (GLOBAL_BATCH // n)
    hi = lo + GLOBAL_BATCH // n
    xl, yl = (xg[lo:hi], yg[lo:hi]) if n > 1 else (xg, yg)

    for step in range(5):
        mets = model.executor.train_batch([xl], yl, jax.random.key(step))
        print(f"process {pid} step {step} loss {float(mets['loss']):.4f}")


if __name__ == "__main__":
    main()
