"""Distributed / multi-chip execution.

Reference equivalents (SURVEY §5.8): the kvstore 'device' GPU reduce and the
ps-lite parameter server are both replaced by XLA collectives over ICI/DCN,
driven by sharding annotations on a ``jax.sharding.Mesh``.  This package
holds the TPU-native machinery:

* :mod:`mesh` — device-mesh construction (dp × tp axes).
* :mod:`trainer` — ``ShardedTrainer``: the Symbol graph fused into ONE
  pjit-compiled train step (forward + backward + optimizer + collectives),
  the performant path that Module's per-call forward/backward approximates.
* :mod:`dist_kvstore` — the ``dist_sync`` KVStore facade over collectives.
* :mod:`multihost` — process-spanning-mesh seams (runtime bootstrap,
  per-process shard staging, checkpoint gather).
* :mod:`sequence` — ring attention (sequence/context parallelism).
* :mod:`pipeline` — GPipe-style microbatch pipeline over a ``pipe`` axis.
* :mod:`reshard` — elastic training: checkpoint resharding across mesh
  shapes, rank join/leave events, ``match_partition_rules`` tables.
"""
from . import multihost
from . import reshard
from .mesh import build_mesh, build_mesh_from_axes, data_parallel_spec
from .moe import make_expert_mesh, switch_moe, topk_moe
from .pipeline import make_pipeline_mesh, pipeline_apply, pipeline_grad
from .trainer import ShardedTrainer
