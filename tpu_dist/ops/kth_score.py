"""The k-th largest score of a row, FOUND and not sorted for.

A selecting latent layer (`nn.latent_attention`, ``index_topk``) keeps of a
query's visible places the ``k`` of largest index score, ties to the lower
index.  That set is stated by two numbers a row: ``kth``, the value of its
``k``-th largest score, and ``last``, the highest index among the picks
that tie with ``kth``; the picks are every place above ``kth`` and, of the
places equal to it, those up to ``last``.  `lax.top_k` learns the two by a
full stable sort of the row (on the TPU ONE ``sort`` of every score with
its index, 229,376 pairs for a decode call of dots3's); `kth_and_last`
learns them by two searches, each a loop of compare-and-count passes over
the row:

- the scores' bits, sign-flipped, are integers in the scores' own order
  (``-0.0`` made ``+0.0`` first); the largest integer that ``k`` places
  reach is built a bit a pass from the top, 32 passes;
- of the places equal to it, the one that fills the room the places above
  left is the smallest index ``m`` with enough ties at or under it: the
  same search over the index's bits, ``ceil(log2 L)`` passes.

The result is exact, the numbers `lax.top_k` gives.  Plain `jax.numpy`, one
form for every platform: on the v5e XLA keeps the integers in VMEM between
the passes of its own accord (46 passes over 58.7 MB in 0.68 ms).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_MIN = -(1 << 31)
_LOW = (1 << 31) - 1
_NEG_INF = _MIN + (1 << 23) - 1    # `_image` of -inf


def _image(s):
    """float32 -> int32 in the same order (no NaN): a negative number's
    low 31 bits flipped, so that the larger magnitude is the smaller
    integer; both zeros the same integer."""
    bits = lax.bitcast_convert_type(jnp.where(s == 0, 0.0, s), jnp.int32)
    return jnp.where(bits < 0, bits ^ _LOW, bits)


def _count(hits):
    return hits.sum(axis=1, keepdims=True, dtype=jnp.int32)


# Why no kernel.  One call on the v5e at dots3's two shapes, k = 2,048 of
# 14,336 places (PERF.md section 6, PR 47; device time a call, the same two
# numbers from every form bit for bit), by `lax.top_k`'s sort | this search |
# the same search as a Pallas kernel over a tile of whole rows in VMEM, by
# rows a tile 8 | 16 | 32 | 64.  A decode call's 16 rows: 175.3 | 26.6 |
# 24.6 | 14.5 us.  A prefill chunk's 1,024 rows: 12,377 | 681 | 1,453 | 759
# | 632 | 488 us.  In the serving cell, one seed, traced, sort | search |
# kernel: `dsa/topk` 0.725 | 0.102 | 0.063 ms a decode step of 11.57 | 10.95
# | 10.91, 1,293 | 1,400 | 1,400 tokens/s: the kernel's 0.04 ms is under
# what the cell resolves, for a hundred lines, a constant and a second form
# to keep equal to this one
@functools.partial(jax.jit, static_argnames=("k",))
def kth_and_last(visible, k: int):
    """Of each row of ``visible (R, L)`` float32 (``-inf`` where a place is
    not to be seen; no NaN), ``0 < k < L``: ``kth (R, 1)`` float32, its
    ``k``-th largest value, and ``last (R, 1)`` int32, the highest index
    `lax.top_k` would pick among the places equal to ``kth``.  The row's
    ``k`` picks, ties to the lower index, are then the places ``> kth``
    and the places ``== kth`` at an index ``<= last``.  A row with fewer
    than ``k`` places above ``-inf`` gives ``(-inf, L - 1)``: all of them.
    Under its own `jax.jit`, so that a model's selecting layers share one
    trace."""
    R, L = visible.shape
    if not 0 < k < L:
        raise ValueError(f"the {k}-th of {L} places: pick some and not all")
    key = _image(visible.astype(jnp.float32))

    # the largest integer that at least k of the row's images reach: the
    # sign first (from the least int32 to 0), then a bit a pass
    def raise_to(cand, found):
        return jnp.where(_count(key >= cand) >= k, cand, found)

    found = raise_to(jnp.zeros((R, 1), jnp.int32), jnp.full((R, 1), _MIN, jnp.int32))
    found = lax.fori_loop(
        0, 31, lambda i, found: raise_to(found + (jnp.int32(1) << (30 - i)), found), found)
    kth = lax.bitcast_convert_type(jnp.where(found < 0, found ^ _LOW, found), jnp.float32)

    # of the places that tie with it, the one that takes the last of the
    # room: the largest m with fewer than `room` ties under it
    room = k - _count(key > found)
    ties = jnp.where(key == found, lax.broadcasted_iota(jnp.int32, (R, L), 1), L)
    bits = (L - 1).bit_length()

    def index_bit(i, m):
        cand = m + (jnp.int32(1) << (bits - 1 - i))
        return jnp.where(_count(ties < cand) < room, cand, m)

    last = lax.fori_loop(0, bits, index_bit, jnp.zeros((R, 1), jnp.int32))
    # fewer than k places above -inf: every one of them is picked
    return kth, jnp.where(found == _NEG_INF, L - 1, last)
