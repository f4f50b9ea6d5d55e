"""The state-space scan of a Mamba-2 mixer (Dao & Gu 2024, "Transformers
are SSMs"), one group: per head ``h`` a state ``S`` of shape
``(head_dim, d_state)`` follows

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t . C_t + D_h * x_t

with ``A_h < 0`` a scalar a head, ``dt_t > 0`` a head and token, and
``B_t`` / ``C_t`` shared by all heads.  `ssm_step` is the recurrence
itself for one token (decode); `ssd_chunked` computes the same for a
run of tokens chunk by chunk (prefill): inside a chunk by the
decay-masked ``C B^T`` product, between chunks by carrying ``S``.
`causal_conv` is the depthwise convolution in front of the scan, with
the window it carries from one call to the next.

A token whose ``mask`` is False is a pad: its ``dt`` is taken as 0 (decay
1, no input), so it leaves ``S`` as it found it, and it does not enter
the convolution's window.  Real tokens are a prefix of every row.  All
state is float32 and every product that reads or makes state runs at
``highest`` precision (on a TPU a float32 product otherwise runs in one
bfloat16 pass); outputs are float32.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_EXACT = lax.Precision.HIGHEST


def causal_conv(x, w, b, window, mask=None):
    """Depthwise causal convolution over time with a carried window.

    ``x``: ``(rows, s, channels)`` new tokens; ``w``: ``(channels, K)``
    (``w[:, K-1]`` multiplies the current token); ``b``: ``(channels,)``;
    ``window``: ``(rows, K-1, channels)`` the last ``K-1`` real tokens
    before ``x`` (zeros at a sequence's start).  Returns ``(y, window')``
    in float32, ``window'`` being the last ``K-1`` real tokens after
    ``x``'s (``mask``: ``(rows, s)``, real tokens first)."""
    s, K = x.shape[1], w.shape[1]
    full = jnp.concatenate([window, x.astype(jnp.float32)], axis=1)
    w = w.astype(jnp.float32)
    y = b.astype(jnp.float32) + sum(full[:, j:j + s] * w[:, j] for j in range(K))
    if mask is None:
        return y, full[:, s:]
    real = mask.sum(axis=1, dtype=jnp.int32)
    at = real[:, None] + jnp.arange(K - 1, dtype=jnp.int32)
    return y, jnp.take_along_axis(full, at[:, :, None], axis=1)


def ssm_step(xs, dt, A, B, C, D, S, mask=None):
    """One token of the recurrence for every row.  ``xs``: ``(rows,
    heads, head_dim)``; ``dt``: ``(rows, heads)``; ``A``, ``D``:
    ``(heads,)``; ``B``, ``C``: ``(rows, d_state)``; ``S``: ``(rows,
    heads, head_dim, d_state)``; ``mask``: ``(rows,)``.  Elementwise
    float32 throughout: the state is read once and written once.
    Returns ``(y (rows, heads, head_dim), S')``."""
    f32 = jnp.float32
    xs, dt = xs.astype(f32), dt.astype(f32)
    if mask is not None:
        dt = jnp.where(mask[:, None], dt, 0.0)
    decay = jnp.exp(dt * A.astype(f32))
    inp = (dt[..., None] * xs)[..., None] * B.astype(f32)[:, None, None, :]
    S = decay[..., None, None] * S + inp
    y = (S * C.astype(f32)[:, None, None, :]).sum(-1)
    return y + D.astype(f32)[:, None] * xs, S


def _chunk(xs, dt, A, B, C, S):
    """One chunk of `ssd_chunked`: ``xs (rows, Q, heads, head_dim)``,
    ``dt (rows, Q, heads)`` with pads at 0, ``B``/``C (rows, Q, d_state)``."""
    Q = xs.shape[1]
    cum = jnp.cumsum(dt * A, axis=1)                       # (rows, Q, heads), <= 0
    # decay from token j to token i >= j, 0 above the diagonal
    seg = cum[:, :, None, :] - cum[:, None, :, :]          # (rows, i, j, heads)
    lower = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    within = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    xdt = xs * dt[..., None]
    scores = jnp.einsum("rin,rjn->rij", C, B, precision=_EXACT)
    y = jnp.einsum("rijh,rjhp->rihp", scores[..., None] * within, xdt, precision=_EXACT)
    # what the carried state adds, decayed from the chunk's start
    y += jnp.einsum("rin,rhpn->rihp", C, S, precision=_EXACT) * jnp.exp(cum)[..., None]
    to_end = jnp.exp(cum[:, -1:, :] - cum)                 # (rows, Q, heads)
    S = jnp.exp(cum[:, -1])[..., None, None] * S + jnp.einsum(
        "rjn,rjhp->rhpn", B, xdt * to_end[..., None], precision=_EXACT)
    return y, S


def ssd_chunked(xs, dt, A, B, C, D, S0, mask=None, *, chunk: int = 256):
    """The recurrence over ``L`` tokens a row, chunk by chunk.  ``xs``:
    ``(rows, L, heads, head_dim)``; ``dt``: ``(rows, L, heads)``; ``B``,
    ``C``: ``(rows, L, d_state)``; ``S0``: ``(rows, heads, head_dim,
    d_state)``; ``mask``: ``(rows, L)``.  ``L`` need not be a multiple of
    ``chunk``: the tail is padded with masked tokens.  Returns ``(y (rows,
    L, heads, head_dim), S_end)``, ``S_end`` the state after each row's
    last real token."""
    f32 = jnp.float32
    rows, L = xs.shape[:2]
    xs, dt, B, C = (t.astype(f32) for t in (xs, dt, B, C))
    if mask is not None:
        dt = jnp.where(mask[..., None], dt, 0.0)
    Q = min(chunk, L)
    n = -(-L // Q)

    def chunks(t):   # (rows, L, ...) -> (n, rows, Q, ...), zero (masked) tail
        t = jnp.pad(t, [(0, 0), (0, n * Q - L)] + [(0, 0)] * (t.ndim - 2))
        return jnp.moveaxis(t.reshape(rows, n, Q, *t.shape[2:]), 1, 0)

    A = A.astype(f32)

    def body(S, c):
        y, S = _chunk(c[0], c[1], A, c[2], c[3], S)
        return S, y

    S, y = lax.scan(body, S0.astype(f32), tuple(chunks(t) for t in (xs, dt, B, C)))
    y = jnp.moveaxis(y, 0, 1).reshape(rows, n * Q, *xs.shape[2:])
    return y[:, :L] + D.astype(f32)[:, None] * xs, S
