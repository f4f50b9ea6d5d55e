"""Decode attention over a paged pool of LATENT rows, read where it lies.

`ops.paged_attention`'s kernel for a pool whose row is not heads of keys
and values but ONE latent row a token, shared by every query head
(`nn.latent_attention`'s absorbed form): the row, ``row`` lanes wide, is
the KEY of all heads, and its first ``v_width`` lanes (the key/value
latent, a whole number of 128-lane tiles) are their VALUE.  So a block is
fetched ONCE and serves both products — handed to `paged_attention_decode`
as a pool of one K/V head, the same pool would be read twice — and there
is no head layout to spread: the scores are the plain product of the
``(heads, row)`` queries with the chunk's ``(tokens, row)`` rows, the
weighted sum the product of the weights with the same rows' first lanes.
With 64 heads on a row of 1,280 bytes that is ~109 operations a byte, under
the v5e's ridge: the pool's read bounds the kernel, not the MXU.

Grid, schedule and streaming softmax are `ops.paged_attention`'s
(`_schedule`: the chunks that hold something, slot after slot; a slot that
holds nothing has no step and a row of zeros).  The queries come with
``W_uk`` folded in and padded to the pool's lanes (the pad lanes of both
are zero); ``scale`` multiplies the float32 scores, as the absorbed form's
does; ``W_uv`` is the caller's, after.

A layer that SELECTS its keys hands the selection over as a mask, ``keep``:
the kernel still fetches every held block, a step takes its chunk's part of
the mask beside the rows (4 bytes a place by the row's 1,280) and a place
that is not kept gets no weight.  That reads more rows than were picked, and
reads each at a block's price, not a single row's:
`serve.paged_kv.READ_ALL_UNDER` says how many held rows a selected one is
worth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_dist.ops.paged_attention import NEG_INF, _schedule

# places a grid step attends.  On the v5e, a sublayer's kernel at LongCat's
# shape (64 heads, rows of 640 lanes of which 512 are the value, 16 slots
# holding 1k-14k, 157 MB read) by 256 | 512 | 1024 (PERF.md section 6,
# PR 43): 446 | 331 | 294 us over blocks of 64 rows, 513 | 407 | 372 over
# blocks of 32, 673 | 562 | 543 over blocks of 16.  A block is one DMA: the
# read follows their count, so `block_size` matters more than this does
CHUNK_TOKENS = 512


def _latent_kernel(slot_ref, chunk_ref, ids_ref, first_ref, chunks_ref, len_ref, q_ref, *refs,
                   G: int, v_width: int, scale: float, masked: bool):
    if masked:
        keep_ref, *refs = refs
    row_refs = refs[:G]
    o_ref, m_ref, l_ref, acc_ref, cat = refs[G:]
    T, _ = cat.shape
    bs = T // G
    heads = q_ref.shape[0]
    t = pl.program_id(0)
    s = slot_ref[t]
    c = chunk_ref[t]
    n = len_ref[s]

    @pl.when(c == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(c < chunks_ref[s])  # false in the one step of an empty grid
    def _():
        for g in range(G):
            cat[g * bs:(g + 1) * bs, :] = row_refs[g][...]
        q = q_ref[...]
        rows = cat[...].astype(q.dtype)
        scores = scale * lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        pos = (first_ref[s] + c * G) * bs + lax.broadcasted_iota(jnp.int32, (heads, T), 1)
        # a chunk's tail holds some other block's rows: finite, weight 0
        seen = pos < n
        if masked:
            # a chunk that keeps nothing before the slot's first kept place
            # weighs every place 1 under the fill's maximum: finite, and the
            # first real maximum scales it away (alpha = 0)
            seen &= keep_ref[...] != 0
        scores = jnp.where(seen, scores, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(q.dtype), rows[:, :v_width], preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(c == chunks_ref[s] - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_latent_decode(q, pool, block_tables, lengths, *, v_width: int, scale: float = 1.0,
                        keep=None, interpret: bool = False):
    """One query token a slot over the latent rows its slot holds.

    ``q``: ``(S, heads, row)``, the absorbed queries ``[W_uk^T q_n | q_r]``
    padded with zeros to the pool's lanes; ``pool``: ``(num_blocks + 1,
    block_size, row)``; ``block_tables``: ``(S, max_blocks)`` int32;
    ``lengths``: ``(S,)`` int32, the places a slot attends (its query sits
    at ``lengths - 1``), 0 for a slot that is to read nothing; ``keep``:
    ``(S, max_blocks * block_size)``, true where a place of the slot's
    table is to get weight (default: every place under its length).
    Returns ``(S, heads, v_width)`` in ``q``'s dtype: each head's
    softmax-weighted sum of the rows' first ``v_width`` lanes, zeros for a
    slot that is empty or keeps nothing.  Every block a table names up to
    its slot's length must hold finite numbers."""
    S, heads, row = q.shape
    _, bs, width = pool.shape
    if width != row or not 0 < v_width <= row:
        raise ValueError(f"queries of {row} lanes and a value of {v_width} "
                         f"over a pool of rows of {width}")
    MB = block_tables.shape[1]
    G = max(1, min(CHUNK_TOKENS // bs, MB))
    lengths = jnp.asarray(lengths, jnp.int32)
    steps, slot, chunk, ids, first, chunks = _schedule(
        jnp.asarray(block_tables, jnp.int32), lengths, bs, G, None)

    blocks = [
        pl.BlockSpec((None, bs, row),
                     lambda t, slot, chunk, ids, *_, g=g: (ids[t * G + g], 0, 0))
        for g in range(G)
    ]
    masks = []
    if keep is not None:
        # a step's part of its slot's mask: chunk c of the table (no window
        # here: a slot's chunks start at its table's first block)
        C, T = -(-MB // G), G * bs
        masks = [jnp.pad(keep.astype(jnp.int32), ((0, 0), (0, C * T - MB * bs)))
                 .reshape(S, C, 1, T)]
        blocks.insert(0, pl.BlockSpec((None, None, 1, T),
                                      lambda t, slot, chunk, *_: (slot[t], chunk[t], 0, 0)))
    o = pl.pallas_call(
        functools.partial(_latent_kernel, G=G, v_width=v_width, scale=scale,
                          masked=keep is not None),
        name="paged_latent_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(steps,),
            in_specs=[pl.BlockSpec((None, heads, row), lambda t, slot, *_: (slot[t], 0, 0))]
            + blocks,
            out_specs=pl.BlockSpec((None, heads, v_width), lambda t, slot, *_: (slot[t], 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((heads, 1), jnp.float32),         # running max
                pltpu.VMEM((heads, 1), jnp.float32),         # denominator
                pltpu.VMEM((heads, v_width), jnp.float32),   # accumulator
                pltpu.VMEM((G * bs, row), pool.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, heads, v_width), q.dtype),
        # a slot's chunks follow one another: the scratch carries over
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slot, chunk, ids, first, chunks, lengths, q, *masks, *[pool] * G)
    # a slot with no step has a row nothing wrote; one that keeps nothing,
    # the mean of what it holds
    some = lengths > 0
    if keep is not None:
        some = (keep & (jnp.arange(MB * bs) < lengths[:, None])).any(axis=1)
    return jnp.where(some[:, None, None], o, 0)
