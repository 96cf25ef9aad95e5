"""swiftsnails_tpu — a TPU-native distributed sparse-training framework.

A ground-up re-design of the capabilities of SwiftSnails (a C++11 ZeroMQ
parameter server: master/server/worker roles, hash-sharded sparse parameter
table, async pull/push SGD) for TPUs:

* the sharded KV parameter table (reference ``src/core/parameter/sparsetable.h``)
  becomes a pjit-sharded dense ``jax.Array`` with hashed-row placement
  (:mod:`swiftsnails_tpu.parallel.store`);
* the ZeroMQ Transfer/Route/Listener RPC stack (reference
  ``src/core/transfer/transfer.h``) becomes XLA collectives over ICI/DCN inside
  a jit'd step (:mod:`swiftsnails_tpu.parallel`);
* master rendezvous / cluster lifecycle (reference ``src/core/system/``)
  becomes ``jax.distributed`` + the coordination service
  (:mod:`swiftsnails_tpu.parallel.cluster`, multi-host runtime);
* pluggable trainers (reference ``BaseAlgorithm``,
  ``src/core/framework/SwiftWorker.h:19-57``) become
  :class:`swiftsnails_tpu.framework.trainer.Trainer` subclasses
  (:mod:`swiftsnails_tpu.models`);
* pluggable update rules (reference ``Pull/PushAccessMethod``,
  ``src/core/parameter/sparse_access_method.h:10-48``) become
  :class:`swiftsnails_tpu.parallel.access.AccessMethod` optimizer defs.
"""

__version__ = "0.1.0"

from swiftsnails_tpu.utils.config import Config, global_config, load_config

__all__ = [
    "Config",
    "global_config",
    "load_config",
    "__version__",
]
