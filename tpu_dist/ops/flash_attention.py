"""Blockwise (flash-style) attention as a Pallas kernel.

The single-device counterpart of `tpu_dist.parallel.ring_attention`: the
same streaming-softmax recurrence (running max / denominator / numerator
in f32), but blocked over the KEY dimension inside one chip's VMEM instead
of over ring hops between chips — the (S, S) score matrix is never
materialized in HBM.  Grid: one program per (batch·head, query-block);
each program scans key/value blocks with ``lax.fori_loop``.

Interpret-mode tested against `tpu_dist.nn.dot_product_attention` on CPU
(values and gradients); compiled on TPU.  Differentiable END TO END in
Pallas: the forward kernel emits per-row LSE, and the custom VJP runs
TWO backward kernels — `_dkv_kernel` (one program per key block, scanning
query blocks for dK/dV) and `_dq_kernel` (one program per query block,
scanning key blocks for dQ) — so the (S, S) score matrix is never
materialized on either pass and ~2/3 of a train step's attention FLOPs
run through hand-written kernels (benchmarks/kernels.py measures fwd and
fwd+bwd against dense XLA).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _band_mask(i, j, bq, bk, causal, window):
    """The visibility mask for (query block i, key block j): causal
    lower-triangle, optionally intersected with the sliding-window band
    ``k > q - window`` (the Mistral-style local-attention pattern)."""
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = q_pos >= k_pos if causal else None
    if window is not None:
        band = k_pos > q_pos - window
        mask = band if mask is None else (mask & band)
    return mask


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, bk: int,
                  causal: bool, window: int | None):
    q = q_ref[0].astype(jnp.float32)  # (bq, d)
    bq, d = q.shape
    S = k_ref.shape[1]
    scale = d**-0.5
    qs = q * scale
    i = pl.program_id(1)
    nblocks = S // bk
    masked = causal or window is not None

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        logits = jnp.dot(qs, k_blk.T, preferred_element_type=jnp.float32)
        if masked:
            mask = _band_mask(i, j, bq, bk, causal, window)
            logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1))
        correction = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[:, None])
        if masked:
            p = jnp.where(mask, p, 0.0)
        l_new = l * correction + p.sum(-1)
        acc_new = acc * correction[:, None] + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    if causal:
        # Skip fully-masked key blocks past the diagonal: query block i
        # only attends to keys < (i+1)*bq — roughly halves causal FLOPs.
        hi = lax.min(nblocks, ((i + 1) * bq + bk - 1) // bk)
    else:
        hi = nblocks
    if window is not None:
        # ...and key blocks wholly BEFORE the window: the earliest key
        # this query block can see is i*bq - window + 1, so work is
        # O(S·window) instead of O(S²) — the sliding-window payoff.
        lo = lax.max(0, (i * bq - window + 1) // bk)
    else:
        lo = 0
    m, l, acc = lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
    # log-sum-exp per query row (saved for the backward pass).  lse is
    # carried as (bh, S, 1) — the trailing singleton makes every block
    # (1, bq, 1), satisfying the TPU rule that a block's last two dims
    # divide (8, 128) or equal the array's ((1, bq) blocks on a (bh, S)
    # array violate it whenever bh > 1 and refuse to lower).
    lse_ref[0] = (m + jnp.log(l))[:, None]


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
    bq: int = 256,
    bk: int = 256,
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
    bq = min(bq, S)
    bk = min(bk, S)
    if S % bq or S % bk:
        raise ValueError(f"seq {S} not divisible by blocks ({bq}, {bk})")
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
    scans query blocks accumulating dK, dV for this key block in f32."""
    ks = k_ref[0].astype(jnp.float32)  # (bk, d)
    vs = v_ref[0].astype(jnp.float32)
    bk_, d = ks.shape
    S = q_ref.shape[1]
    scale = d**-0.5
    j = pl.program_id(1)
    nq = S // bq
    masked = causal or window is not None

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * bq, bq), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(qi * bq, bq), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qi * bq, bq), 0]
        dd = d_ref[0, pl.ds(qi * bq, bq), 0]
        logits = jnp.dot(q * scale, ks.T, preferred_element_type=jnp.float32)
        if masked:
            mask = _band_mask(qi, j, bq, bk_, causal, window)
            logits = jnp.where(mask, logits, NEG_INF)
        p = jnp.exp(logits - lse[:, None])  # (bq, bk)
        if masked:
            p = jnp.where(mask, p, 0.0)
        dv = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, vs.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dd[:, None])
        dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32) * scale
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
    dk0 = jnp.zeros((bk_, d), jnp.float32)
    dv0 = jnp.zeros((bk_, d), jnp.float32)
    dk, dv = lax.fori_loop(lo, hi, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref,
               *, bk: int, causal: bool, window: int | None):
    """Backward kernel B: one program per (batch·head, QUERY block);
    scans key blocks accumulating dQ in f32."""
    q = q_ref[0].astype(jnp.float32)  # (bq, d)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, :, 0]  # (bh, S, 1) carry, see _flash_kernel
    dd = d_ref[0, :, 0]
    bq_, d = q.shape
    S = k_ref.shape[1]
    scale = d**-0.5
    i = pl.program_id(1)
    nk = S // bk
    masked = causal or window is not None

    def body(j, dq):
        ks = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        vs = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        logits = jnp.dot(q * scale, ks.T, preferred_element_type=jnp.float32)
        if masked:
            mask = _band_mask(i, j, bq_, bk, causal, window)
            logits = jnp.where(mask, logits, NEG_INF)
        p = jnp.exp(logits - lse[:, None])
        if masked:
            p = jnp.where(mask, p, 0.0)
        dp = jnp.dot(do, vs.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dd[:, None])
        return dq + jnp.dot(ds, ks, preferred_element_type=jnp.float32) * scale

    hi = lax.min(nk, ((i + 1) * bq_ + bk - 1) // bk) if causal else nk
    lo = (
        lax.max(0, (i * bq_ - window + 1) // bk)
        if window is not None
        else 0
    )
    dq = lax.fori_loop(lo, hi, body, jnp.zeros((bq_, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd(causal, bq, bk, interpret, window, res, g):
    """Backward via two Pallas kernels (dK/dV by key block, dQ by query
    block) — the (S, S) score matrix is never formed on either pass.
    Standard flash recurrence: with P = exp(logits - lse) and
    D = rowsum(dO ∘ O),  dV_j = Pᵀ dO,  dS = P ∘ (dO Vᵀ − D),
    dQ += dS K_j · scale,  dK_j = dSᵀ Q · scale."""
    q3, k3, v3, out, lse = res
    bh, S, d = q3.shape
    go = g.astype(q3.dtype)
    D = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )  # (bh, S, 1) f32 — same trailing-singleton carry as lse

    full = pl.BlockSpec((1, S, d), lambda b, i: (b, 0, 0))
    row_full = pl.BlockSpec((1, S, 1), lambda b, i: (b, 0, 0))
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
                  full, row_full, row_full],
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
    )(q3, k3, v3, go, lse, D)
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
    """Whether `flash_attention` at its default blocks (256) is the form
    to compute this attention in: self-attention lengths from 1024 up
    that the block divides, no arbitrary mask, the kernel's own scale.
    Anything else (cross-attention, short or indivisible sequences,
    padding or segment masks, a model's own multiplier) is the dense
    form's.  The floor is where the kernel stops losing: on a v5e,
    forward and backward at 128 heads of 64, causal, it takes 2.3-3.3
    times the dense form's time at 128, 256 and 512 and the same time at
    1024, where the dense form's (S, S) scores begin to cost memory
    (PERF.md section 6, PR 30).  Below it some lengths do not even
    compile (causal at 197: the kernel wants a multiple of 8)."""
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
    bq: int = 256,
    bk: int = 256,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Attention over (..., heads, S, d) without materializing (S, S).

    Block sizes clamp to the sequence length for small inputs; S must be
    divisible by the (clamped) block sizes.  Differentiable: the custom
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
    bq = min(bq, S)
    bk = min(bk, S)
    if S % bq or S % bk:
        raise ValueError(f"seq {S} not divisible by blocks ({bq}, {bk})")
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
