"""Hand-rolled ring all-reduce as a Pallas TPU kernel with explicit
inter-chip RDMA — the true native analog of the reference's exercise.

The reference hand-implements DeepSpeech's ring allreduce over p2p
send/recv (allreduce.py:8-34, tuto.md:322-354) on top of THD's C++
transport.  `tpu_dist.parallel.ring_all_reduce` re-expresses that with
XLA-level `ppermute`; THIS module goes one level lower — the level the
reference's Gloo/NCCL kernels live at: a Pallas kernel issuing its own
inter-chip DMAs (`make_async_remote_copy` over ICI), with neighbor
barriers and double-buffered communication slots, per the TPU kernel
playbook (/opt/skills/guides/pallas_guide.md, "Ring Collectives").

COMPILED execution needs ≥2 real TPU chips (``chip_smoke.py`` checks it
against ``lax.psum`` on all chips of the host).  Off-TPU the kernel runs
only when ASKED to, with ``interpret=True``: Pallas's TPU interpret mode
(`pltpu.InterpretParams`) simulates the DMA semaphores and remote copies
across the CPU-sim mesh, so the tests run the real kernel body —
barriers, double buffering, RDMA ordering — and cross-check it against
``lax.psum`` (tests/test_ops.py::TestPallasRing).
"""

from __future__ import annotations

import functools

import jax
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_dist.comm.mesh import DEFAULT_AXIS


def _ring_kernel(x_ref, o_ref, comm_buf, send_sem, recv_sem, *, axis_name):
    """Naive ring: n-1 hops of the full buffer, accumulate on arrival.

    comm_buf: VMEM (2, *x.shape) — slot s holds the buffer being sent
    (s = step % 2) while slot 1-s receives the neighbor's.
    """
    n = lax.axis_size(axis_name)
    my_id = lax.axis_index(axis_name)
    right = lax.rem(my_id + 1, n)
    left = lax.rem(my_id - 1 + n, n)
    barrier = pltpu.get_barrier_semaphore()

    o_ref[:] = x_ref[:]
    comm_buf[0] = x_ref[:]

    def step_body(step, _):
        send_slot = lax.rem(step, 2)
        recv_slot = 1 - send_slot
        # Backpressure: at step s we write the RIGHT neighbor's slot
        # (1 - s%2), the very slot it sends from at step s-1.  A
        # neighborhood barrier at the top of every step guarantees both
        # neighbors have finished their previous step's send+recv+
        # accumulate (and, at step 0, have entered the kernel and
        # allocated comm_buf) before any RDMA lands in their buffers —
        # without it a fast sender could overwrite a slot still being
        # sent from, silently corrupting the sum for n >= 3.
        pltpu.semaphore_signal(barrier, inc=1, device_id=(left,))
        pltpu.semaphore_signal(barrier, inc=1, device_id=(right,))
        pltpu.semaphore_wait(barrier, 2)
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_buf.at[send_slot],
            dst_ref=comm_buf.at[recv_slot],
            send_sem=send_sem.at[send_slot],
            recv_sem=recv_sem.at[recv_slot],
            device_id=right,  # LOGICAL ids are scalars (tuples are MESH coords)
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        o_ref[:] += comm_buf[recv_slot]
        return _

    lax.fori_loop(0, n - 1, step_body, None)


def _pallas_ring(
    x: jax.Array, axis_name: str, collective_id: int, *,
    interpret: bool = False,
) -> jax.Array:
    """``interpret=True`` runs the kernel under Pallas's TPU interpret
    mode (`pltpu.InterpretParams`), which SIMULATES the semaphores and
    inter-chip RDMAs on CPU devices — the same kernel body, exercised
    without hardware."""
    return pl.pallas_call(
        functools.partial(_ring_kernel, axis_name=axis_name),
        name="ring_all_reduce",
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2,) + x.shape, x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x)


def ring_all_reduce_pallas(
    x: jax.Array,
    axis_name: str = DEFAULT_AXIS,
    *,
    collective_id: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Ring all-reduce via explicit inter-chip RDMA.  Call inside
    shard_map over ``axis_name`` (which must be the mesh's only axis for
    LOGICAL device ids to equal ring positions).

    ``interpret=False`` compiles the kernel for the TPU; lowered for any
    other platform Pallas raises — there is no silent substitute
    (`parallel.ring_all_reduce` is the portable ppermute ring, and a
    caller that wants it calls it).
    ``interpret=True`` runs the ACTUAL kernel (semaphores, remote copies)
    under Pallas's TPU interpret simulator on any platform — how the
    kernel is exercised without hardware.
    """
    return _pallas_ring(x, axis_name, collective_id, interpret=interpret)
