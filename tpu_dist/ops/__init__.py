"""`tpu_dist.ops` — Pallas TPU kernels (the hot-op / native-kernel layer).

- `matmul`: tiled MXU matmul with fused bias+activation epilogue.
- `flash_attention`: blockwise attention, forward and both backward
  passes as kernels.
- `paged_attention_decode`: one query token a slot attends its slot's
  held blocks of a paged KV pool where they lie (serving's decode step
  on the TPU).
- `ring_all_reduce_pallas`: the hand-rolled ring allreduce at the RDMA
  level (the reference's allreduce.py exercise at its native depth).

Every kernel takes ``interpret`` explicitly: ``False`` compiles for the
TPU (and raises anywhere else), ``True`` runs the Pallas interpreter (the
kernels' own tests).  Library code that must run everywhere —
`nn.dot_product_attention`, `serve.paged_kv` — hands `kernel_for_platform`
the compiled kernel and its own plain form, and the program takes the one
it can run where it is lowered.
"""

import jax
from jax import lax

from tpu_dist.ops.flash_attention import (
    flash_attention,
    flash_attention_lse,
    flash_attention_takes,
)
from tpu_dist.ops.matmul import matmul
from tpu_dist.ops.paged_attention import paged_attention_decode
from tpu_dist.ops.pallas_ring import ring_all_reduce_pallas

# bytes of float32 scores one plain attention product may hold before its
# caller walks the keys in parts (`nn.latent_attention`, `serve.paged_kv`)
SCORE_BYTES = 1 << 29


def _partitioned_by_compiler() -> bool:
    """Whether the computation being traced is one that XLA's SPMD
    partitioner splits over devices, by Mosaic's own test (it refuses to
    lower a kernel there): inside a `shard_map`, some axis of the mesh
    is not manual, whatever its size; outside one, the context mesh
    holds more than one device.  `shard_map` sets the context itself; a
    `jax.jit` whose shardings span a mesh knows it only in its builder,
    which traces what the model computes under
    `parallel.partitioned_over(mesh)`."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.manual_axes:
        return set(mesh.manual_axes) != set(mesh.axis_names)
    return not mesh.empty and mesh.size > 1


def kernel_for_platform(kernel, plain, *operands):
    """``kernel(*operands)`` where the program is lowered for a TPU (the
    kernel compiled: it raises what the compiler raises), and
    ``plain(*operands)``, the caller's own form of the same result,
    anywhere else and wherever the compiler partitions the program over
    several devices.  Decided at LOWERING time by the platform the
    program is lowered FOR: unlike ``jax.default_backend()`` this is
    right for a CPU mesh in a process whose default backend is the TPU,
    and for a TPU program lowered from a CPU host.  No platform ever
    interprets a kernel here."""
    if _partitioned_by_compiler():
        return plain(*operands)
    return lax.platform_dependent(*operands, tpu=kernel, default=plain)


__all__ = [
    "flash_attention",
    "flash_attention_lse",
    "flash_attention_takes",
    "kernel_for_platform",
    "matmul",
    "paged_attention_decode",
    "ring_all_reduce_pallas",
]
