"""distributed_model_parallel_tpu — a TPU-native distributed training framework.

A ground-up JAX/XLA/pjit/pallas re-design of the capabilities of the reference
repo ``HaoKang-Timmy/distributed_model_parallel`` (see /root/repo/SURVEY.md):

* single-host data parallelism (the ``nn.DataParallel`` capability,
  reference ``data_parallel.py:76-78``) via batch-dimension ``NamedSharding``
  under ``jit``;
* multi-process DDP-equivalent gradient allreduce (reference ``Readme.md:144-157``)
  via ``shard_map`` + ``lax.psum`` over an ICI mesh, with SyncBatchNorm and a
  sparse-embedding gradient path;
* inter-layer model/pipeline parallelism (reference ``distributed_layers.py``,
  ``model_parallel.py``, ``utils.py``) via stage-partitioned models with both a
  naive 1-batch-in-flight schedule (parity) and micro-batched schedules;
* a training harness: SGD + cosine annealing + linear warmup, top-1/5 metrics,
  per-batch timing, checkpoint/resume, text+structured logging
  (reference ``data_parallel.py:89-171``, ``utils.py:34-229``);
* a model zoo (MobileNetV2 ± BatchNorm, ResNet-18/50, a Transformer LM for
  long-context and multi-axis mesh parallelism) and a dataset registry
  (reference ``model/mobilenetv2.py``, ``dataset/dataset_collection.py``).

Everything is SPMD-first: pick a ``Mesh``, annotate shardings, let XLA insert
collectives.
"""

__version__ = "0.1.0"

from distributed_model_parallel_tpu.config import (  # noqa: F401
    DataConfig,
    MeshConfig,
    ModelConfig,
    OptimizerConfig,
    TrainConfig,
)
from distributed_model_parallel_tpu.mesh import (  # noqa: F401
    MeshSpec,
    best_effort_distributed_init,
    make_mesh,
)
