"""Decode attention over a paged KV pool, read where it lies.

One query token a slot attends the blocks its slot HOLDS.  The kernel
takes the layer's two pools as they are — ``(num_blocks + 1, block_size,
kv_heads * head_dim)``, a token's k/v one row, heads major
(`serve.paged_kv`) — and fetches block ``block_tables[s, j]`` through a
`BlockSpec` whose index map reads a scalar-prefetched table, for the
``j`` that hold a visible token and no other: ``ceil(len / block_size)``
blocks, from ``len - window`` on under a sliding window, none for a slot
of length 0.  Nothing is gathered, reshaped or repeated in HBM, so a
step costs what the held tokens cost, not ``slots * max_blocks *
block_size`` places.

**Grid.**  One step attends a CHUNK of ``G = CHUNK_TOKENS // block_size``
blocks of one slot (each block an operand of its own: the same pool,
another index map), and the grid is the list of chunks that hold
something, slot after slot — `_schedule` lays it out on the device from
the lengths, and its length is the grid's one, dynamic, bound.  A slot
that holds nothing has no step; a chunk's tail past its slot's last
block names the block that operand fetched last, which the pipeline
does not fetch again.  The pipeline fetches step ``t + 1`` while step
``t`` computes, across slots too.  The tables the scalar core is handed
are built by ROWS of ``G`` ids, never one (step, operand) place at a
time: a slot's chunks are one slice of its table's row, the fill of a
tail is the row above or a carry down the slots, and one gather of rows
puts the slots' chunks one after another.  Their static length is
``slots * C``, ``C`` the chunks a slot can have: ``ceil(max_blocks /
G)``, and under ``sliding_window = w`` no more than the
``ceil((w + block_size - 1) / block_size)`` blocks a window can reach —
a ring's table as wide as the pool's (`serve.paged_kv.ring_tables`)
costs a schedule of its window's length.

**A step.**  The softmax streams over a slot's chunks (running max,
denominator and accumulator in float32 scratch; the two products take
their operands in the query's dtype, as the gathered view's einsums do).
Heads never leave the row: the query is laid out BLOCK-DIAGONALLY — row
``h`` of a ``(heads, kv_heads * head_dim)`` tile holds ``q[h]`` in the
columns of its own K/V head and zeros elsewhere — so ONE product with
the chunk's ``(tokens, kv_heads * head_dim)`` keys gives every head's
scores, no row is split into lanes of ``head_dim`` (64 of a vreg's 128),
and a GQA group reads its K/V head once.  The weighted sum is the same
product the other way, of which each head keeps its own head's columns.
Both re-layouts of the small side (q in, o out) are products with a
constant 0/1 matrix, exact in any dtype.

A row that is no multiple of 128 lanes (gpt2-xl: 25 x 64 = 1600) is a
whole block's minor dimension, so the compiler pads it to the next
multiple in VMEM as it does in HBM (a hand-written copy of such a block
is refused: "slice shape must be aligned to tiling").  ``block_size``
should be a multiple of the pool dtype's sublane tile (16 for bfloat16,
8 for float32): a chunk's blocks then stack into one operand by whole
tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # the mask's fill, as the gathered view's
# places a grid step attends.  On the v5e, a layer's kernel at 128 | 256 |
# 512 (PERF.md, PR 29): 128 | 127 | 195 us at gpt2-xl's shape (blocks of
# 32 rows of 1600), 94 | 129 | 210 at gpt2-medium's (64 of 1024, few
# held), 678 | 611 | 644 at granite's (64 of 1024, GQA 32:8, long)
CHUNK_TOKENS = 256


def _head_layout(heads: int, kv_heads: int, head_dim: int):
    """``spread (head_dim, row)``: column ``g * head_dim + d`` of row
    ``d`` is 1 — ``q @ spread`` tiles a head across every K/V head's
    columns and ``x @ spread.T`` sums them back.  ``own (heads, row)``:
    1 in the columns of head ``h``'s own K/V head."""
    col = np.arange(kv_heads * head_dim)
    spread = col[None, :] % head_dim == np.arange(head_dim)[:, None]
    own = (col[None, :] // head_dim
           == np.arange(heads)[:, None] // (heads // kv_heads))
    return spread.astype(np.float32), own.astype(np.float32)


def _schedule(block_tables, lengths, bs: int, G: int, window: int | None):
    """The grid, as tables for the scalar core.  Step ``t`` attends chunk
    ``chunk[t]`` of slot ``slot[t]``: blocks ``first[s] + chunk * G + g``
    of its table, ``g < G``, of which ``ids[t * G + g]`` is the pool's
    id, or, past the slot's last block, the id operand ``g`` held last
    (no fetch).  ``chunks[s]`` is 0 for a slot that holds nothing; the
    grid has ``max(sum(chunks), 1)`` steps.

    The tables are ``S * C`` steps long, ``C`` the most chunks a slot can
    have: its table's ``ceil(max_blocks / G)``, or, under a window, the
    ``ceil((window + bs - 1) / bs)`` blocks a window can reach, whatever
    the table's width.  They are laid out SLOT-MAJOR first, by rows of
    ``G`` ids: slot ``s``'s ``C`` chunks are its table's row from column
    ``first[s]`` on, and only a slot's last chunk has a tail, which names
    the chunk above it or, in a slot of one chunk, what the operand held
    when the last slot that had a step ended (a carry down the ``S``
    slots).  One gather of rows then lists the chunks that hold
    something, slot after slot.  Nothing is fetched or filled one (step,
    operand) place at a time, and nothing is scanned down the steps."""
    S, MB = block_tables.shape
    first = (jnp.maximum(lengths - window, 0) // bs if window is not None
             else jnp.zeros_like(lengths))
    last = (lengths + bs - 1) // bs  # blocks first .. last-1 are visible
    chunks = (last - first + G - 1) // G
    ends = jnp.cumsum(chunks)
    C = -(-MB // G)
    if window is not None:
        C = min(C, -(-(-(-(window + bs - 1) // bs)) // G))
    t = jnp.arange(S * C, dtype=jnp.int32)
    after = t[:, None] >= ends[None, :]
    slot = jnp.minimum(after.sum(axis=1, dtype=jnp.int32), S - 1)
    # a slot starts where the last one that had a step ended
    chunk = t - jnp.max(jnp.where(after, ends[None, :], 0), axis=1)

    # slot-major, (S, C, G): chunk c of slot s, held or not; a column past
    # the table's end reads its last
    if window is None:  # from column 0
        ids = jnp.pad(block_tables, ((0, 0), (0, C * G - MB)), mode="edge")
    else:
        # C * G columns from `first` on.  The TPU gathers whole rows only:
        # the aligned groups of G columns that hold them, then the G
        # shifts a `first % G` can ask for, of which each slot takes its own
        groups = -(-MB // G) + C + 1
        wide = jnp.pad(block_tables, ((0, 0), (0, groups * G - MB)), mode="edge")
        rows = ((jnp.arange(S, dtype=jnp.int32) * groups + first // G)[:, None]
                + jnp.arange(C + 1, dtype=jnp.int32))
        near = jnp.take(wide.reshape(S * groups, G), rows.reshape(-1), axis=0,
                        mode="clip").reshape(S, (C + 1) * G)
        shifts = jnp.stack([near[:, g:g + C * G] for g in range(G)], axis=1)
        ids = jnp.take_along_axis(shifts, (first % G)[:, None, None], axis=1)
    ids = ids.reshape(S, C, G)
    column = first[:, None] + jnp.arange(C * G, dtype=jnp.int32)
    held = (column < last[:, None]).reshape(S, C, G)
    above = jnp.concatenate([ids[:, :1], ids[:, :-1]], axis=1)
    # what each operand holds when its slot ends: nothing new where the
    # slot has no step, or one chunk that does not reach the operand
    tail = jnp.maximum(chunks - 1, 0)
    ended = jnp.take_along_axis(
        jnp.where(held, ids, above), tail[:, None, None], axis=1)[:, 0]
    fresh = (chunks > 1)[:, None] | (
        (first + tail * G)[:, None] + jnp.arange(G, dtype=jnp.int32)
        < last[:, None])  # ... or its one chunk holds a block for it
    # ... carried down the slots (before the first, the grid's first block:
    # held, if anything is)
    source = lax.cummax(
        jnp.where(fresh, jnp.arange(S, dtype=jnp.int32)[:, None], -1), axis=0)
    before = jnp.broadcast_to(ids[slot[0], 0, 0], (1, G))
    carried = jnp.where(
        source >= 0,
        jnp.take_along_axis(ended, jnp.maximum(source, 0), axis=0), before)
    entering = jnp.concatenate([before, carried[:-1]], axis=0)
    ids = jnp.where(held, ids, jnp.where(
        jnp.arange(C)[None, :, None] > 0, above, entering[:, None, :]))
    # the chunks that hold something, slot after slot: one gather of rows
    ids = jnp.take(ids.reshape(S * C, G), slot * C + chunk, axis=0, mode="clip")
    return jnp.maximum(ends[-1], 1), slot, chunk, ids.reshape(-1), first, chunks


def _decode_kernel(slot_ref, chunk_ref, ids_ref, first_ref, chunks_ref,
                   len_ref, q_ref, spread_ref, own_ref, *refs,
                   G: int, window: int | None):
    k_refs, v_refs = refs[:G], refs[G:2 * G]
    o_ref, q_bd, m_ref, l_ref, acc_ref, k_cat, v_cat = refs[2 * G:]
    T, _ = k_cat.shape
    bs = T // G
    heads = q_ref.shape[0]
    t = pl.program_id(0)
    s = slot_ref[t]
    c = chunk_ref[t]
    n = len_ref[s]
    nt = (((1,), (1,)), ((), ()))  # contract both operands' columns

    @pl.when(c == 0)
    def _():
        q = q_ref[...]
        q_bd[...] = (own_ref[...] * jnp.dot(
            q, spread_ref[...].astype(q.dtype),
            preferred_element_type=jnp.float32)).astype(q_bd.dtype)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(c < chunks_ref[s])  # false in the one step of an empty grid
    def _():
        for g in range(G):
            k_cat[g * bs:(g + 1) * bs, :] = k_refs[g][...]
            v_cat[g * bs:(g + 1) * bs, :] = v_refs[g][...]
        k = k_cat[...].astype(q_bd.dtype)
        v = v_cat[...].astype(q_bd.dtype)
        scores = lax.dot_general(q_bd[...], k, nt,
                                 preferred_element_type=jnp.float32)
        pos = (first_ref[s] + c * G) * bs + lax.broadcasted_iota(
            jnp.int32, (heads, T), 1)
        visible = pos < n
        if window is not None:
            visible &= pos >= n - window
        # a chunk's tail holds some other block's rows: finite, weight 0
        scores = jnp.where(visible, scores, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(c == chunks_ref[s] - 1)
    def _():
        o = lax.dot_general(acc_ref[...] * own_ref[...], spread_ref[...], nt,
                            precision=lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
        o_ref[...] = (o / l_ref[...]).astype(o_ref.dtype)


def paged_attention_decode(q, k_pool, v_pool, block_tables, lengths, *,
                           sliding_window: int | None = None,
                           interpret: bool = False):
    """Attention of one query token a slot over the slot's held blocks.

    ``q``: ``(S, heads, head_dim)``, scaled (and rotated) already;
    ``k_pool`` / ``v_pool``: ``(num_blocks + 1, block_size, kv_heads *
    head_dim)``; ``block_tables``: ``(S, max_blocks)`` int32;
    ``lengths``: ``(S,)`` int32, the number of places a slot attends —
    its query sits at place ``lengths - 1`` — and 0 for a slot that is to
    read nothing (its output row is zeros).  ``sliding_window=w`` keeps
    the last ``w`` places.  Every block a table names up to its slot's
    length must hold finite numbers, attended or not (so must the
    gathered view's).  Returns ``(S, heads, head_dim)`` in ``q``'s
    dtype."""
    S, heads, head_dim = q.shape
    _, bs, row = k_pool.shape
    if k_pool.shape != v_pool.shape or row % head_dim:
        raise ValueError(f"pools {k_pool.shape} / {v_pool.shape} do not "
                         f"hold rows of heads of {head_dim}")
    kv_heads = row // head_dim
    if heads % kv_heads:
        raise ValueError(f"heads {heads} not divisible by kv_heads {kv_heads}")
    G = max(1, min(CHUNK_TOKENS // bs, block_tables.shape[1]))
    lengths = jnp.asarray(lengths, jnp.int32)
    steps, slot, chunk, ids, first, chunks = _schedule(
        jnp.asarray(block_tables, jnp.int32), lengths, bs, G, sliding_window)
    spread, own = _head_layout(heads, kv_heads, head_dim)

    const = lambda shape: pl.BlockSpec(shape, lambda t, *_: (0, 0))  # noqa: E731
    per_slot = pl.BlockSpec((None, heads, head_dim),
                            lambda t, slot, *_: (slot[t], 0, 0))
    blocks = [
        pl.BlockSpec((None, bs, row),
                     lambda t, slot, chunk, ids, *_, g=g: (ids[t * G + g], 0, 0))
        for g in range(G)
    ]
    o = pl.pallas_call(
        functools.partial(_decode_kernel, G=G, window=sliding_window),
        name="paged_attn_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(steps,),
            in_specs=[per_slot, const(spread.shape), const(own.shape)]
            + blocks + blocks,
            out_specs=per_slot,
            scratch_shapes=[
                pltpu.VMEM((heads, row), q.dtype),       # block-diagonal q
                pltpu.VMEM((heads, 1), jnp.float32),     # running max
                pltpu.VMEM((heads, 1), jnp.float32),     # denominator
                pltpu.VMEM((heads, row), jnp.float32),   # accumulator
                pltpu.VMEM((G * bs, row), k_pool.dtype),
                pltpu.VMEM((G * bs, row), v_pool.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # a slot's chunks follow one another: the scratch carries over
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slot, chunk, ids, first, chunks, lengths, q, spread, own,
      *[k_pool] * G, *[v_pool] * G)
    # a slot with no step has a row nothing wrote
    return jnp.where((lengths > 0)[:, None, None], o, 0)
