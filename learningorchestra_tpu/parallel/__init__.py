"""Distributed execution: device meshes, sharded training, ring attention,
and the multi-host control plane.

This package replaces the reference's entire distributed stack — Horovod's
C++ ring-allreduce over Gloo, the Ray cluster scheduler, and the
ship-model-as-JSON / return-weights-as-lists serialization (reference:
microservices/binary_executor_image/binary_execution.py:203-292,
training_function/train_function.py:53-139, ray_cluster/Dockerfile:14) —
with the TPU-native equivalents:

- ``mesh``: named device meshes (dp/fsdp/tp/sp axes) over ICI;
- ``sharding``: partition rules mapping model pytrees and batches onto the
  mesh so XLA's SPMD partitioner inserts the collectives (psum over dp for
  gradients — the compiled replacement for Horovod's host-side ring);
- ``distributed``: ``DistributedTrainer``, the mesh-sharded train loop;
- ``ring_attention``: blockwise ring attention over the ``sp`` axis
  (ppermute under shard_map) for long-context sequence parallelism;
- ``coordinator``: multi-host bootstrap (``jax.distributed.initialize``)
  plus the framework's own coordinator/host-agent control plane replacing
  Ray client + GCS (SURVEY §5.8).
"""

from learningorchestra_tpu.parallel.mesh import (  # noqa: F401
    MeshSpec,
    build_mesh,
    default_spec,
)
from learningorchestra_tpu.parallel.sharding import (  # noqa: F401
    batch_sharding,
    param_shardings,
)
from learningorchestra_tpu.parallel.distributed import (  # noqa: F401
    DistributedTrainer,
)
from learningorchestra_tpu.parallel.ring_attention import (  # noqa: F401
    ring_attention,
)
from learningorchestra_tpu.parallel.pipeline import (  # noqa: F401
    PipelinedTransformer,
)
