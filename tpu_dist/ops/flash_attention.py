"""Blockwise (flash-style) attention as a Pallas kernel.

The single-device counterpart of `tpu_dist.parallel.ring_attention`: the
same streaming-softmax recurrence, but blocked over the KEY dimension
inside one chip's VMEM instead of over ring hops between chips — the
(S, S) score matrix is never materialized in HBM.  Grid: one program per
(batch·head, query-block); each program scans key/value blocks with
``lax.fori_loop``.

What it computes in: the MXU takes q, k, v and dO in the dtype they
arrive in (bfloat16 from a bfloat16 model: the configuration's own
arithmetic) and accumulates in float32; everything between a product's
output and the next product's input (scale, mask, running max /
denominator / numerator, ``exp``, ``p * (dp - D)``, the dK / dV / dQ
accumulators, the saved LSE) is float32; ``p`` and ``ds`` are cast to
the input's dtype for the products that take them.  Float32 inputs reach
the MXU as float32, at Mosaic's default precision (on a v5e that is one
bfloat16 pass: there the two read the same to the last digit, in time
and in error, PERF.md section 6, PR 31).  Every product is stated in
`lax.dot_general`'s dimension numbers, so no tile is transposed in the
kernel.

Interpret-mode tested against `tpu_dist.nn.dot_product_attention` on CPU
(values and gradients); compiled on TPU.  Differentiable END TO END in
Pallas: the forward kernel emits per-row LSE, and the custom VJP runs
TWO backward kernels — `_dkv_kernel` (one program per key block, scanning
query blocks for dK/dV on the TRANSPOSED scores ``K·Qᵀ``, so ``pᵀ·dO``
and ``dsᵀ·Q`` are plain products) and `_dq_kernel` (one program per query
block, scanning key blocks for dQ) — so the (S, S) score matrix is never
materialized on either pass and ~2/3 of a train step's attention FLOPs
run through hand-written kernels (benchmarks/kernels.py measures fwd and
fwd+bwd against dense XLA).

Blocks default to 512 where that divides the sequence, else 256: on a
v5e a 256 x 256 tile's loop body is bound by its chain of dependent
steps (the MXU busy a third to a half of it), a 512 x 512 tile's by the
MXU at head size 64 (PERF.md section 6, PR 31).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


_NT = (((1,), (1,)), ((), ()))  # a · bᵀ: contract the last dim of both


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """a · b (or a · bᵀ with `_NT`) on the operands' own dtype, float32 out."""
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _band_mask(q0, k0, shape, causal, window, q_axis=0):
    """The visibility mask of a tile whose first query is ``q0`` and first
    key ``k0``: causal lower-triangle, optionally intersected with the
    sliding-window band ``k > q - window`` (the Mistral-style
    local-attention pattern).  Queries run along ``q_axis`` of ``shape``,
    keys along the other (the dK/dV kernel's tiles are keys x queries)."""
    q_pos = q0 + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = q_pos >= k_pos if causal else None
    if window is not None:
        band = k_pos > q_pos - window
        mask = band if mask is None else (mask & band)
    return mask


def _key_blocks(i, bq, bk, nk, causal, window):
    """[lo, hi): the key blocks query block ``i`` can see."""
    # Skip fully-masked key blocks past the diagonal: query block i only
    # attends to keys < (i+1)*bq — roughly halves causal FLOPs...
    hi = lax.min(nk, ((i + 1) * bq + bk - 1) // bk) if causal else nk
    # ...and key blocks wholly BEFORE the window: the earliest key this
    # query block can see is i*bq - window + 1, so work is O(S·window)
    # instead of O(S²) — the sliding-window payoff.
    lo = lax.max(0, (i * bq - window + 1) // bk) if window is not None else 0
    return lo, hi


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, bk: int,
                  causal: bool, window: int | None):
    q = q_ref[0]  # (bq, d), the input's dtype
    bq, d = q.shape
    S = k_ref.shape[1]
    scale = d**-0.5
    i = pl.program_id(1)
    masked = causal or window is not None
    # The MXU's output tile is 128 lanes wide whatever d is: where d leaves
    # lanes to spare, a block of ones beside V gives the row sums of p in
    # the same product, and the lane reduction leaves the loop (a fifth of
    # the loop's body at d = 64, PERF.md section 6, PR 31).  The denominator
    # then sums exactly the p the numerator was weighted with.
    spare = -d % 128

    def body(j, carry):
        m, l, acc = carry  # (bq, 1), (bq, 1), (bq, d) float32
        k_blk = k_ref[0, pl.ds(j * bk, bk), :]
        v_blk = v_ref[0, pl.ds(j * bk, bk), :]
        # the scale goes on the float32 logits: d**-0.5 is a power of two
        # only for some head sizes, so a scaled bfloat16 q would round
        logits = _dot(q, k_blk, _NT) * scale
        if masked:
            mask = _band_mask(i * bq, j * bk, (bq, bk), causal, window)
            logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
        correction = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        if masked:
            p = jnp.where(mask, p, 0.0)
        if spare:
            ones = jnp.ones((bk, spare), v_blk.dtype)
            pv = _dot(p.astype(v_blk.dtype), jnp.concatenate([v_blk, ones], 1))
            pv, row_sum = pv[:, :d], pv[:, d:d + 1]
        else:
            pv = _dot(p.astype(v_blk.dtype), v_blk)
            row_sum = p.sum(-1, keepdims=True)
        return m_new, l * correction + row_sum, acc * correction + pv

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    lo, hi = _key_blocks(i, bq, bk, S // bk, causal, window)
    m, l, acc = lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # log-sum-exp per query row (saved for the backward pass).  lse is
    # carried as (bh, S, 1) — the trailing singleton makes every block
    # (1, bq, 1), satisfying the TPU rule that a block's last two dims
    # divide (8, 128) or equal the array's ((1, bq) blocks on a (bh, S)
    # array violate it whenever bh > 1 and refuse to lower).
    lse_ref[0] = m + jnp.log(l)


def _blocks(S: int, bq: int | None, bk: int | None) -> tuple[int, int]:
    """The block sizes a call runs with: the caller's, else 512 where that
    divides the sequence and 256 where it does not, clamped to S."""
    default = 512 if S % 512 == 0 else 256
    bq = min(bq or default, S)
    bk = min(bk or default, S)
    if S % bq or S % bk:
        raise ValueError(f"seq {S} not divisible by blocks ({bq}, {bk})")
    return bq, bk


def _flash_forward(q3, k3, v3, causal, bq, bk, interpret, window=None):
    bh, S, d = q3.shape
    kernel = functools.partial(
        _flash_kernel, bk=bk, causal=causal, window=window
    )
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(bh, S // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, S, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, S, 1), jnp.float32),
        ],
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(q3, k3, v3)
    return out, lse


def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    bq: int | None = None,
    bk: int | None = None,
    interpret: bool = False,
    window: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """`flash_attention` that ALSO returns the per-row log-sum-exp
    ``(..., S)`` the kernel already computes for its backward pass.

    The lse is what makes flash blocks composable: partial attentions
    over disjoint key sets recombine exactly via
    ``out = Σ exp(lse_b - m*) out_b / Σ exp(lse_b - m*)`` — the
    ring-attention composition (`parallel.ring_attention_flash`).
    Forward-only (no VJP); compositions define their own backward.
    """
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    *lead, S, d = q.shape
    if q.shape != k.shape or q.shape != v.shape:
        raise ValueError(f"q/k/v shapes differ: {q.shape} {k.shape} {v.shape}")
    bq, bk = _blocks(S, bq, bk)
    bh = 1
    for x in lead:
        bh *= x
    out, lse = _flash_forward(
        q.reshape(bh, S, d), k.reshape(bh, S, d), v.reshape(bh, S, d),
        causal, bq, bk, interpret, window,
    )
    return out.reshape(q.shape), lse[..., 0].reshape(*lead, S)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q3, k3, v3, causal, bq, bk, interpret, window):
    out, _ = _flash_forward(q3, k3, v3, causal, bq, bk, interpret, window)
    return out


def _flash_fwd(q3, k3, v3, causal, bq, bk, interpret, window):
    out, lse = _flash_forward(q3, k3, v3, causal, bq, bk, interpret, window)
    return out, (q3, k3, v3, out, lse)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                *, bq: int, causal: bool, window: int | None):
    """Backward kernel A: one program per (batch·head, KEY block);
    scans query blocks accumulating dK, dV for this key block in f32.
    Its tiles are the TRANSPOSED scores (keys x queries), so both
    accumulations are plain products; lse and D arrive as rows."""
    ks = k_ref[0]  # (bk, d), the input's dtype
    vs = v_ref[0]
    bk_, d = ks.shape
    S = q_ref.shape[1]
    scale = d**-0.5
    j = pl.program_id(1)
    nq = S // bq
    masked = causal or window is not None

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * bq, bq), :]
        do = do_ref[0, pl.ds(qi * bq, bq), :]
        lse = lse_ref[0, qi]  # (1, bq)
        dd = d_ref[0, qi]
        logits = _dot(ks, q, _NT) * scale  # (bk, bq)
        if masked:
            mask = _band_mask(qi * bq, j * bk_, (bk_, bq), causal, window,
                              q_axis=1)
            logits = jnp.where(mask, logits, NEG_INF)
        p = jnp.exp(logits - lse)  # 0.0 where masked: lse is finite
        dv = dv + _dot(p.astype(do.dtype), do)
        dp = _dot(vs, do, _NT)
        ds = p * (dp - dd)
        dk = dk + _dot(ds.astype(q.dtype), q)
        return dk, dv

    if causal:
        # query blocks before this key block's diagonal are fully masked
        lo = (j * bk_) // bq
    else:
        lo = 0
    if window is not None:
        # the LAST query that can see key block j is (j+1)*bk-1+window-1
        hi = lax.min(nq, ((j + 1) * bk_ - 1 + window - 1) // bq + 1)
    else:
        hi = nq
    zeros = jnp.zeros((bk_, d), jnp.float32)
    dk, dv = lax.fori_loop(lo, hi, body, (zeros, zeros))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref,
               *, bk: int, causal: bool, window: int | None):
    """Backward kernel B: one program per (batch·head, QUERY block);
    scans key blocks accumulating dQ in f32."""
    q = q_ref[0]  # (bq, d), the input's dtype
    do = do_ref[0]
    lse = lse_ref[0]  # (bq, 1): the (bh, S, 1) carry, see _flash_kernel
    dd = d_ref[0]
    bq_, d = q.shape
    S = k_ref.shape[1]
    scale = d**-0.5
    i = pl.program_id(1)
    masked = causal or window is not None

    def body(j, dq):
        ks = k_ref[0, pl.ds(j * bk, bk), :]
        vs = v_ref[0, pl.ds(j * bk, bk), :]
        logits = _dot(q, ks, _NT) * scale
        if masked:
            mask = _band_mask(i * bq_, j * bk, (bq_, bk), causal, window)
            logits = jnp.where(mask, logits, NEG_INF)
        p = jnp.exp(logits - lse)  # 0.0 where masked: lse is finite
        dp = _dot(do, vs, _NT)
        ds = p * (dp - dd)
        return dq + _dot(ds.astype(ks.dtype), ks)

    lo, hi = _key_blocks(i, bq_, bk, S // bk, causal, window)
    dq = lax.fori_loop(lo, hi, body, jnp.zeros((bq_, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd(causal, bq, bk, interpret, window, res, g):
    """Backward via two Pallas kernels (dK/dV by key block, dQ by query
    block) — the (S, S) score matrix is never formed on either pass.
    Standard flash recurrence: with P = exp(logits - lse) and
    D = rowsum(dO ∘ O),  dV_j = Pᵀ dO,  dS = P ∘ (dO Vᵀ − D),
    dQ += dS K_j · scale,  dK_j = dSᵀ Q · scale.  The dK/dV kernel works
    on Pᵀ and dSᵀ, so it takes lse and D one ROW a query block."""
    q3, k3, v3, out, lse = res
    bh, S, d = q3.shape
    go = g.astype(q3.dtype)
    D = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )  # (bh, S, 1) f32 — same trailing-singleton carry as lse

    full = pl.BlockSpec((1, S, d), lambda b, i: (b, 0, 0))
    rows = pl.BlockSpec((1, S // bq, 1, bq), lambda b, j: (b, 0, 0, 0))
    params = (
        None
        if interpret
        else pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))
    )
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, bq=bq, causal=causal, window=window),
        name="flash_bwd_dkv",
        grid=(bh, S // bk),
        in_specs=[full, pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
                  pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
                  full, rows, rows],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, S, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, S, d), v3.dtype),
        ],
        compiler_params=params,
        interpret=interpret,
    )(q3, k3, v3, go, lse.reshape(bh, S // bq, 1, bq),
      D.reshape(bh, S // bq, 1, bq))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bk=bk, causal=causal, window=window),
        name="flash_bwd_dq",
        grid=(bh, S // bq),
        in_specs=[pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
                  full, full,
                  pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0))],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, S, d), q3.dtype),
        compiler_params=params,
        interpret=interpret,
    )(q3, k3, v3, go, lse, D)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_takes(q, k, v, *, mask=None, scale=None) -> bool:
    """Whether `flash_attention` at its default blocks is the form to
    compute this attention in: self-attention lengths from 1024 up that
    the 256-block divides, no arbitrary mask, the kernel's own scale.
    Anything else (cross-attention, short or indivisible sequences,
    padding or segment masks, a model's own multiplier) is the dense
    form's.  The floor is where the kernel stops losing: on a v5e,
    forward and backward at 128 heads of 64, causal, bfloat16, it takes
    1.5, 2.2-2.7 and 1.5 times the dense form's time at 128, 256 and 512
    (0.271 | 0.177, 0.411 | 0.189, 0.785 | 0.511 ms) and two thirds of
    it at 1024 (2.17 | 3.42 ms), where the dense form's (S, S) scores
    begin to cost memory as well (PERF.md section 6, PR 31; before it
    2.3-3.3 times below 1024 and par there, PR 30).  Below it some
    lengths do not even compile (causal at 197: the kernel wants a
    multiple of 8)."""
    S = q.shape[-2]
    return (
        q.shape == k.shape == v.shape
        and S >= 1024
        and S % 256 == 0
        and mask is None
        and scale in (None, q.shape[-1] ** -0.5)
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret", "window")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    bq: int | None = None,
    bk: int | None = None,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Attention over (..., heads, S, d) without materializing (S, S).

    Block sizes default to 512 where that divides S, else 256, and clamp
    to the sequence length for small inputs; S must be divisible by the
    (clamped) block sizes.  Differentiable: the custom
    VJP runs the standard flash backward blockwise (peak intermediate
    (S, bk)), using the LSE saved by the forward kernel.

    ``window=w`` adds the LOWER band bound ``k > q - w``; with
    ``causal=True`` that is the sliding-window (Mistral-style)
    autoregressive band ``(q - w, q]``, and forward + both backward
    kernels skip out-of-band blocks — O(S·w) work instead of O(S²).
    Without ``causal`` the bound is one-sided (queries still see all
    FUTURE keys, and the past-side skip is the only saving); for
    symmetric bidirectional local attention use the dense path with
    `nn.sliding_window_mask`.
    """
    *lead, S, d = q.shape
    if q.shape != k.shape or q.shape != v.shape:
        raise ValueError(f"q/k/v shapes differ: {q.shape} {k.shape} {v.shape}")
    bq, bk = _blocks(S, bq, bk)
    bh = 1
    for x in lead:
        bh *= x
    q3 = q.reshape(bh, S, d)
    k3 = k.reshape(bh, S, d)
    v3 = v.reshape(bh, S, d)
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    out = _flash(q3, k3, v3, causal, bq, bk, interpret, window)
    return out.reshape(q.shape)
