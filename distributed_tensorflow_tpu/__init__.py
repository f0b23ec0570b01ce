"""distributed_tensorflow_tpu — a TPU-native distributed training framework.

A ground-up JAX/XLA/pjit re-design of the capability surface of the reference
parameter-server trainer (zzy123abc/distributed-tensorflow, ``distributed.py``):

- cluster bring-up & control plane: :mod:`.cluster` (C++ coordination service
  over DCN replaces the gRPC PS runtime; data rides ICI collectives)
- parameter placement: :mod:`.parallel.sharding` (HBM sharding rules replace
  ``replica_device_setter``)
- replica modes: :mod:`.parallel.sync` (AllReduce sync, R<N masking) and
  :mod:`.parallel.async_replicas` (TPU-native async/local-SGD)
- supervision: :mod:`.training.supervisor` (init-or-recover + orbax checkpoints
  replace ``tf.train.Supervisor``)
- models/ops/data: :mod:`.models`, :mod:`.ops`, :mod:`.data`
"""

__version__ = "0.1.0"

from . import config
from .config import app, flags
from .cluster.spec import ClusterSpec, is_chief
from .parallel import mesh
from .parallel.mesh import create_mesh, data_parallel_mesh
from .parallel.sharding import ShardingRules, replicate_tree
from .training.state import TrainState, gradient_descent

__all__ = [
    "app", "flags", "config",
    "ClusterSpec", "is_chief",
    "mesh", "create_mesh", "data_parallel_mesh",
    "ShardingRules", "replicate_tree",
    "TrainState", "gradient_descent",
]
