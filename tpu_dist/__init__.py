"""tpu_dist — a TPU-native distributed-training framework.

A ground-up rebuild of the capability surface of seba-1511/dist_tuto.pth
("Writing Distributed Applications with PyTorch", /root/reference/tuto.md),
designed TPU-first on JAX/XLA: SPMD programs compiled over a
`jax.sharding.Mesh`, XLA collectives over ICI/DCN instead of
TCP/Gloo/MPI/NCCL, `lax.ppermute` rings instead of per-tensor send/recv,
and fused `pjit`/`shard_map` train steps instead of per-parameter blocking
all-reduce.

Correspondence to the reference API (kept explicit per SURVEY.md §7):

=====================================  ========================================
reference (`torch.distributed`)        tpu_dist
=====================================  ========================================
``init_process_group(backend, ...)``   ``comm.init(...)`` + ``comm.make_mesh``
``get_rank()`` / ``get_world_size()``  ``comm.rank(axis)`` / ``comm.world_size(axis)``
``send`` / ``recv``                    ``comm.send`` / ``comm.shift`` (ppermute)
``isend`` / ``irecv`` + ``wait()``     XLA async dispatch (compiled overlap)
``all_reduce(t, op, group)``           ``comm.all_reduce(x, op, axis, group=...)``
``reduce`` / ``broadcast``             ``comm.reduce`` / ``comm.broadcast``
``scatter`` / ``gather``               ``comm.scatter`` / ``comm.gather``
``all_gather``                         ``comm.all_gather``
``reduce_op.{SUM,PRODUCT,MAX,MIN}``    ``comm.ReduceOp.{SUM,PRODUCT,MAX,MIN}``
``new_group([ranks])``                 ``comm.new_group([ranks])``
backend strings ('tcp'/'gloo'/'mpi')   platform selection ('tpu'/'cpu')
hand-rolled ring allreduce             ``parallel.ring_all_reduce`` (+ chunked)
``DistributedDataParallel``-by-hand    ``parallel.data_parallel`` train step
=====================================  ========================================
"""

from tpu_dist import (
    comm,
    data,
    export,
    models,
    nn,
    observe,
    ops,
    parallel,
    resilience,
    serve,
    train,
    utils,
)

__version__ = "0.1.0"

# every compilation from here on leaves its stages on the span ring
observe.compile_spans.install()

__all__ = [
    "comm",
    "data",
    "export",
    "models",
    "nn",
    "observe",
    "ops",
    "parallel",
    "resilience",
    "serve",
    "train",
    "utils",
]
