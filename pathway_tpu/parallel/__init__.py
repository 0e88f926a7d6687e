"""SPMD distribution over TPU device meshes.

Parity target: SURVEY.md §2b. The reference's only parallelism is data
parallelism by key-shard over identical dataflow replicas — timely workers
exchanging ``(Key, Value, Timestamp, diff)`` tuples over shared memory or
zero-copy TCP (``external/timely-dataflow/communication/``,
``src/engine/dataflow/shard.rs``).  The TPU-native mapping replaces the
row-tuple exchange with XLA collectives over ICI:

* host rows are sharded by the 16-bit shard field of the 128-bit key,
  exactly like the reference (``src/engine/value.rs:38``);
* dense state (embedding matrices, index shards) stays resident in HBM,
  sharded over the mesh; queries move, vectors do not;
* the compute path (encoder fwd/bwd, top-k retrieval) is pjit-compiled
  SPMD — XLA inserts ``all_gather``/``psum``/``reduce_scatter`` from the
  sharding annotations instead of hand-written NCCL/MPI calls.

Mesh convention: 2-D ``("data", "model")``. Batch/data parallelism rides
the ``data`` axis; tensor parallelism of encoder weights rides ``model``;
the document index is sharded over the *flattened* mesh (every chip holds
one slice of the corpus — the analog of the reference's key-shard space).
Further axes for the decoder family: ``("stage",)`` pipeline meshes
(``pipeline.py``, GPipe over ``ppermute``), ``("data", "expert")`` MoE
meshes (``moe.py``, GShard dispatch lowering to ``all_to_all``), and the
sequence-parallel ring (``ring_attention.py``).
"""

from __future__ import annotations

from pathway_tpu.parallel.mesh import (
    flat_axes,
    get_default_index_mesh,
    initialize_distributed,
    make_mesh,
    mesh_shape_for,
    put_global,
    set_default_index_mesh,
)
from pathway_tpu.parallel.sharding import (
    replicated,
    shard_batch,
    shard_params,
)
from pathway_tpu.parallel.train import (
    TrainState,
    make_causal_lm_train_step,
    make_contrastive_train_step,
    init_train_state,
)
from pathway_tpu.parallel.index import ShardedDeviceIndex, sharded_topk
from pathway_tpu.parallel.ring_attention import ring_encoder_attention
from pathway_tpu.parallel.moe import (
    MoEConfig,
    ep_param_specs,
    init_moe_params,
    make_ep_mesh,
    make_moe_train_step,
    moe_ffn,
    moe_serve,
)
from pathway_tpu.parallel.pipeline import (
    make_pipelined_causal_lm,
    make_pp_mesh,
    make_pp_train_step,
    place_pp_params,
    pp_param_specs,
)
from pathway_tpu.parallel.checkpoint import TrainCheckpointer

__all__ = [
    "initialize_distributed",
    "put_global",
    "make_mesh",
    "mesh_shape_for",
    "flat_axes",
    "set_default_index_mesh",
    "get_default_index_mesh",
    "shard_params",
    "shard_batch",
    "replicated",
    "TrainState",
    "init_train_state",
    "make_causal_lm_train_step",
    "make_contrastive_train_step",
    "ShardedDeviceIndex",
    "sharded_topk",
    "ring_encoder_attention",
    "MoEConfig",
    "init_moe_params",
    "ep_param_specs",
    "make_ep_mesh",
    "make_moe_train_step",
    "moe_ffn",
    "moe_serve",
    "make_pp_mesh",
    "pp_param_specs",
    "place_pp_params",
    "make_pipelined_causal_lm",
    "make_pp_train_step",
    "TrainCheckpointer",
]
