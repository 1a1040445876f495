"""openembedding_tpu — a TPU-native large-scale sparse-embedding training framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of 4paradigm/OpenEmbedding
(reference: /root/reference). The reference is a C++ synchronous parameter server with
TensorFlow custom ops; here the "parameter server" disappears into a single SPMD program:

- embedding tables are `jax.Array`s row-sharded over a `jax.sharding.Mesh` axis,
  resident in HBM (reference: PS shards, `server/EmbeddingStorage.h`);
- pull/push become all_to_all exchanges + sparse gather / scatter-add inside the jitted
  train step (reference: `server/EmbeddingPullOperator.cpp`, `EmbeddingPushOperator.cpp`);
- server-side fused optimizers become sparse slot-update functions applied to the owning
  shard (reference: `variable/EmbeddingOptimizer.h`);
- the Horovod/NCCL dense allreduce becomes `jax.lax.psum` under pjit (reference:
  `examples/criteo_deepctr_network.py:53-62`);
- the batch-version gating protocol (`EmbeddingStoreOperator.cpp`) is obviated: SPMD is
  synchronous by construction.

Public API (the reference's 3-line conversion, `openembedding/tensorflow/exb.py`):

    import openembedding_tpu as embed
    model   = embed.Model(...)              # or any flax module using embed.Embedding
    trainer = embed.Trainer(model, optimizer=embed.Adagrad(learning_rate=0.01))
"""

__version__ = "0.1.0"

from . import meta
from . import config
from . import initializers
from . import optimizers
from .meta import DataType, EmbeddingVariableMeta, ModelVariableMeta, ModelMeta
from .config import Flags, EnvConfig
from .initializers import (
    Initializer,
    Constant,
    Zeros,
    Ones,
    Uniform,
    Normal,
    TruncatedNormal,
    make_initializer,
)
from .optimizers import (
    SparseOptimizer,
    SGD,
    Momentum,
    Adagrad,
    Adadelta,
    Adam,
    Adamax,
    Ftrl,
    RMSprop,
    TestOptimizer,
    make_optimizer,
)
from .embedding import Embedding, EmbeddingTableState, EmbeddingSpec
from .variable import EmbeddingVariable
from .model import EmbeddingModel, Trainer, TrainState
from .utils.metrics import NonFiniteError
from . import checkpoint
from .checkpoint import save_server_model, load_server_model
from . import persist
from .persist import (AsyncPersister, IncrementalPersister, PersistPolicy,
                      persist_server_model, restore_server_model)
# keras_compat (from_keras_model / import-hook inject) is imported lazily:
# it needs keras, whose backend is fixed at first import — see
# openembedding_tpu/keras_compat.py and openembedding_tpu/inject.py
