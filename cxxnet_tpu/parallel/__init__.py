"""Parallelism: device meshes, sharding rules, and collectives.

TPU-native replacement for the reference's entire distribution stack —
mshadow-ps push/pull parameter server + per-GPU worker threads
(SURVEY.md §2.9-§2.10). Strategy mapping:

* single-node multi-GPU data parallelism (dev=gpu:a-b, batch split across
  NeuralNetThreads, PS "local" sync)      -> batch sharded over the mesh
  'data' axis; XLA inserts the gradient all-reduce over ICI
* distributed PS (param_server=dist, update_on_server=1, server-side
  optimizer)                              -> ZeRO-style sharded optimizer
  state (weight-update sharding) over the data axis
* per-tensor async push/pull overlap      -> XLA latency-hiding scheduler
  within the single jitted train step
"""

from .mesh import (create_mesh, ensure_platform,  # noqa: F401
                   parse_device_spec)
from .sharding import (batch_sharding, replicated,  # noqa: F401
                       zero_sharding)
from . import collectives  # noqa: F401
from .ring import attention_reference, ring_attention, ulysses_attention  # noqa: F401
from .tensor import (column_parallel_dense, expert_parallel_ffn,  # noqa: F401
                     fullc_sharding, row_parallel_dense)
from .pipeline import (pipeline_apply, pipeline_apply_stages,  # noqa: F401
                       stage_sharding)
from .multihost import (create_hybrid_mesh, fetch_global,  # noqa: F401
                        init_distributed,
                        virtual_cpu_env, worker_shard_params)
