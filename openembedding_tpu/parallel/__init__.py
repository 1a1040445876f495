from .mesh import make_mesh, table_sharding, replicated, batch_sharding
from .sharded import (sharded_lookup, deinterleave_rows, interleave_rows,
                      exchange_load_stats)
from .trainer import MeshTrainer, SeqMeshTrainer
from .checkpoint import (save_sharded, load_sharded, snapshot_addressable,
                         checkpoint_layout)
from .sequence import ring_attention, ulysses_attention, reference_attention
from . import multihost
