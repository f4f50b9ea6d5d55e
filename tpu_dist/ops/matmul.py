"""Pallas tiled matmul with fused epilogue — the framework's hot-op kernel.

The reference's FLOPs all live in external cuDNN/BLAS (torch.nn conv/linear,
train_dist.py:57-60); on TPU the analog is the MXU, normally driven by XLA.
This kernel is the hand-tuned path for the cases XLA's fusion doesn't own:
matmul + bias + activation in ONE VMEM round-trip (the HBM-bandwidth rule:
fuse elementwise ops into the matmul's epilogue rather than re-reading the
output).

Grid is (M/bm, N/bn, K/bk) with a float32 VMEM accumulator carried across
the K dimension ("arbitrary" semantics — K iterations revisit the same
output tile); inputs may be bf16 (MXU-native) while accumulation stays f32.
A library kernel: called directly as `matmul` (no layer routes through
it; XLA's own product carries every model here).  Tested against jnp.dot
in interpret mode on CPU and compiled on real TPU.
"""

from __future__ import annotations

import functools
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_EPILOGUES: dict[str, Callable] = {
    "none": lambda x: x,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
}


def _matmul_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, epilogue: str, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(
        x_ref[:], w_ref[:], preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _finish():
        # b_ref is (1, bn): 1-D operands get Mosaic/XLA layout-mismatched
        # tilings on real TPU (bf16[n] refuses to compile) — rank-2 rows
        # are the native layout, and broadcasting handles the rest.
        out = acc_ref[:] + b_ref[:].astype(jnp.float32)
        o_ref[:] = _EPILOGUES[epilogue](out).astype(o_ref.dtype)


_VMEM_BUDGET = 8 * 1024 * 1024  # ~half of a core's ~16MB VMEM

def _resolve_blocks(
    m: int, n: int, k: int, bm, bn, bk
) -> tuple[int, int, int]:
    """Final block sizes: explicit args win, then the `_auto_blocks`
    heuristic."""
    if bm is None or bn is None or bk is None:
        abm, abn, abk = _auto_blocks(m, n, k)
        bm, bn, bk = bm or abm, bn or abn, bk or abk
    return bm, bn, bk


def _vmem_bytes(bm: int, bn: int, bk: int) -> int:
    """Working set: 2 copies (double buffer) of the input blocks + the
    f32 accumulator + the output block."""
    x_b = bm * bk * 4
    w_b = bk * bn * 4
    acc_b = bm * bn * 4
    return 2 * (x_b + w_b) + 2 * acc_b


def _pick_block(dim: int, target: int) -> int:
    """Largest power-of-two block <= target that divides dim (falls back
    to the full dimension for sizes nothing divides — tiny/odd shapes
    become a single block)."""
    t = target
    while t >= 128:
        if dim % t == 0:
            return t
        t //= 2
    return dim


def _auto_blocks(m: int, n: int, k: int) -> tuple[int, int, int]:
    """Shape-aware default tiling.

    The round-2 hardware run showed 256x256x512 blocks reaching only
    ~40 TF/s at 1024^3 vs XLA's ~116: the working set (~1 MB) leaves
    VMEM (~16 MB/core) idle and re-fetches the operands N/bn + M/bm
    times.  Total HBM traffic is ~ M*K*N/bn + K*N*M/bm, so grow bm/bn
    first (512 each → 4x fewer operand passes than 256), then take bk
    as large as the VMEM budget allows: x(bm,bk) + w(bk,bn) double-
    buffered + f32 acc(bm,bn) + out within ~half of VMEM."""
    bm = _pick_block(m, 512)
    bn = _pick_block(n, 512)
    for bk_target in (2048, 1024, 512, 256, 128):
        bk = _pick_block(k, bk_target)
        if _vmem_bytes(bm, bn, bk) <= _VMEM_BUDGET:
            return bm, bn, bk
    # Nothing fit: only reachable when _pick_block returned a full
    # dimension (nothing >=128 divides it) and that block blows the
    # budget.  Callers pad to 128-multiples before block selection, so
    # this is a guard for explicit odd shapes: shrink the largest block
    # until the working set fits (full-dim blocks cannot shrink — warn).
    bk = _pick_block(k, 128)
    if _vmem_bytes(bm, bn, bk) > _VMEM_BUDGET:
        warnings.warn(
            f"pallas matmul blocks ({bm},{bn},{bk}) for shape "
            f"({m},{n},{k}) exceed the ~{_VMEM_BUDGET >> 20}MB VMEM "
            "budget (no power-of-two >=128 divides the dimensions); "
            "pass bm/bn/bk explicitly or pad the operands",
            stacklevel=3,
        )
    return bm, bn, bk


def _matmul_impl(x, w, b, epilogue, bm, bn, bk, interpret):
    m, k = x.shape
    _, n = w.shape
    # Pad dims that no viable block divides up to the next 128-multiple
    # (k-padding contributes zeros; m/n padding is sliced off) so block
    # selection never degenerates to a full — possibly VMEM-busting —
    # dimension.  A dim's viability is judged against the block the
    # caller actually requested (an explicit bm=500 that divides m=3000
    # must be honored, not padded away); shapes already served by one
    # block (dim <= 256, the pad-unit x2) skip padding: a single small
    # block is cheaper than a copy.
    def _pad_amount(d: int, t: int | None) -> int:
        if d <= 256 or _pick_block(d, t or 512) != d:
            return 0  # a single small block, or a dividing block exists
        padded = d + ((-d) % 128)
        # Pad only when it buys a dividing block: an explicit block that
        # divides neither d nor the 128-multiple (e.g. bm=3000, m=70000)
        # would still degenerate to a full-dim block — after paying for
        # the pad copy.
        return padded - d if _pick_block(padded, t or 512) != padded else 0

    pads = [_pad_amount(d, t) for d, t in zip((m, n, k), (bm, bn, bk))]
    if any(pads):
        pm, pn, pk = pads
        x = jnp.pad(x, ((0, pm), (0, pk)))
        w = jnp.pad(w, ((0, pk), (0, pn)))
        b = jnp.pad(b, ((0, 0), (0, pn)))
        out = _matmul_impl(x, w, b, epilogue, bm, bn, bk, interpret)
        return out[:m, :n]
    bm, bn, bk = _resolve_blocks(m, n, k, bm, bn, bk)
    bm_, bn_, bk_ = _pick_block(m, bm), _pick_block(n, bn), _pick_block(k, bk)
    if not interpret and _vmem_bytes(bm_, bn_, bk_) > _VMEM_BUDGET:
        # explicit blocks bypass _auto_blocks' budget loop (and padding
        # cannot rescue a block that divides nothing) — never silent
        warnings.warn(
            f"pallas matmul blocks ({bm_},{bn_},{bk_}) for shape "
            f"({m},{n},{k}) exceed the ~{_VMEM_BUDGET >> 20}MB VMEM "
            "budget; expect Mosaic failure or HBM spills — pass smaller "
            "bm/bn/bk or pad the operands",
            stacklevel=3,
        )
    nk = k // bk_
    grid = (m // bm_, n // bn_, nk)
    kernel = functools.partial(_matmul_kernel, epilogue=epilogue, nk=nk)
    return pl.pallas_call(
        kernel,
        name="matmul_fused",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn_), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, w, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _matmul_core(x, w, b, epilogue, bm, bn, bk, interpret):
    return _matmul_impl(x, w, b, epilogue, bm, bn, bk, interpret)


def _matmul_fwd(x, w, b, epilogue, bm, bn, bk, interpret):
    out = _matmul_impl(x, w, b, epilogue, bm, bn, bk, interpret)
    return out, (x, w, b)


def _matmul_bwd(epilogue, bm, bn, bk, interpret, res, g):
    # Backward = two plain matmuls + a reduction; XLA owns those (they
    # have no fusable epilogue).  The kernel's value-add — the fused
    # forward epilogue — needs the pre-activation recomputed here for
    # non-trivial epilogues (cheaper than saving an (M, N) residual).
    x, w, b = res
    if epilogue == "none":
        d_pre = g
    else:
        pre = _matmul_impl(x, w, b, "none", bm, bn, bk, interpret)
        _, act_vjp = jax.vjp(_EPILOGUES[epilogue], pre)
        (d_pre,) = act_vjp(g)
    dx = d_pre @ w.T
    dw = x.T @ d_pre
    db = d_pre.sum(0, keepdims=True)  # b is (1, N) inside the core
    return dx.astype(x.dtype), dw.astype(w.dtype), db.astype(b.dtype)


_matmul_core.defvjp(_matmul_fwd, _matmul_bwd)


@functools.partial(
    jax.jit, static_argnames=("epilogue", "bm", "bn", "bk", "interpret")
)
def matmul(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    epilogue: str = "none",
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``epilogue(x @ w + b)`` in one kernel.  x: (M, K), w: (K, N),
    b: (N,) or None.  Block sizes default to a shape-aware pick
    (`_auto_blocks`: fill VMEM, minimize operand re-fetches) and fall
    back to the full dimension when nothing divides evenly (tiny shapes
    just become a single block); pass bm/bn/bk to override.
    Differentiable: a custom VJP computes dx/dw/db with plain XLA matmuls
    (recomputing the pre-activation for fused epilogues), so the kernel is
    safe inside `jax.grad`/train steps."""
    if epilogue not in _EPILOGUES:
        raise ValueError(f"unknown epilogue {epilogue!r}; one of {list(_EPILOGUES)}")
    m, k = x.shape
    k2, n = w.shape
    if k != k2:
        raise ValueError(f"inner dims mismatch: {x.shape} @ {w.shape}")
    if b is None:
        b = jnp.zeros((n,), x.dtype)
    # (1, N) internally — see _matmul_kernel's layout note.
    return _matmul_core(x, w, b.reshape(1, n), epilogue, bm, bn, bk, interpret)

