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
CPU test path).  Library code that must run on both — `nn.Dense`,
`nn.dot_product_attention` — goes through `kernel_for_platform`, which
makes that choice when the program is lowered, from the platform it is
lowered FOR.  (`serve.paged_kv` makes the same choice the same way, but
its other branch is its own gathered view, not the interpreter.)
"""

import functools

from jax import lax

from tpu_dist.ops.flash_attention import (
    flash_attention,
    flash_attention_lse,
)
from tpu_dist.ops.matmul import matmul, use_pallas_dense
from tpu_dist.ops.paged_attention import paged_attention_decode
from tpu_dist.ops.pallas_ring import ring_all_reduce_pallas


def kernel_for_platform(kernel, *operands, **static):
    """``kernel(*operands, interpret=..., **static)`` with ``interpret``
    decided at LOWERING time by the platform the operands' program is
    compiled for: on a TPU the kernel is compiled (or raises — it is never
    interpreted there); on any other platform it is interpreted.  Unlike
    ``jax.default_backend()`` this is right for a CPU mesh in a process
    whose default backend is the TPU, and for a TPU program lowered from a
    CPU host."""
    return lax.platform_dependent(
        *operands,
        tpu=functools.partial(kernel, interpret=False, **static),
        default=functools.partial(kernel, interpret=True, **static),
    )


__all__ = [
    "flash_attention",
    "flash_attention_lse",
    "kernel_for_platform",
    "matmul",
    "paged_attention_decode",
    "ring_all_reduce_pallas",
    "use_pallas_dense",
]
