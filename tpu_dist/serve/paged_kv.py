"""Paged KV cache — fixed-size blocks in a preallocated pool.

The dense decode path (`TransformerLM.init_cache` / `apply_cached`)
allocates one contiguous ``(batch, kv_heads, cache_len, head_dim)``
cache per request slot, so a slot's HBM is pinned for the slot's
longest possible sequence whether or not it is used.  Serving under
continuous batching wants the opposite: KV memory is a POOL of
fixed-size blocks (``block_size`` token positions each), and every
request owns just the blocks its tokens actually fill, mapped through a
per-request **block table** (logical block index -> physical block id)
— the vLLM/PagedAttention layout.  Blocks are handed out by the
host-side `BlockAllocator` at admission and returned at eviction; the
device never sees the free list, only the tables.

The device side is `paged_apply_cached`: the SAME math as
`TransformerLM.apply_cached` (tests assert greedy decode through it is
token-identical to the dense `generate`) with two differences:

- **write**: a token's k/v scatters as ONE contiguous row of
  ``kv_heads * head_dim`` (heads major) into
  ``pool[table[pos // block_size], pos % block_size]`` instead of a
  ``dynamic_update_slice`` into a contiguous cache (masked-off tokens —
  pads, inactive slots — write to a reserved scratch block);
- **read**, by the number of new tokens a slot brings, a shape the code
  sees.  ONE (every decode step), in a program lowered for a TPU:
  `ops.paged_attention_decode`, a Pallas kernel that attends each slot's
  held blocks where they lie in the pool — ``ceil(len / block_size)`` of
  them, none for an inactive slot — with the scores' statistics in
  float32.  SEVERAL (a prefill chunk), and decode lowered for any other
  platform, where the kernel could only be interpreted: the per-slot
  tables gather the pool into a contiguous ``(slots, L, kv_heads,
  head_dim)`` view by reshape alone, contracted where it lies
  (`_gathered_attention`, the plain reference the kernel is tested
  against).  Either way the attention (scale, position mask, -1e30 fill,
  softmax) is the dense incremental attention, per-slot positions
  included.

Heads and ``head_dim`` share the pool's minor dimension because of the
TPU's layouts: with a 64-wide last dimension the device stores the
array with another dimension minor-most, the scatter/gather body wants
``head_dim`` minor, and every program then relayouts every layer's
whole pool on entry and again on exit (docs/serving.md has the table).
Folded, the argument's layout IS the body's, the donated pool is
updated in place, and the kernel's blocks are whole rows: it never
splits one into heads.

Everything is static-shape: one compiled program serves every decode
step and every prefill chunk regardless of which requests occupy which
slots.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_dist import ops
from tpu_dist.nn.latent_attention import top_visible
from tpu_dist.ops import SCORE_BYTES

# keys a step of `_walked_attention` fetches a row.  One call of a layer on
# the v5e at 48 query heads over 8 K/V heads of 128, a chunk of 256 (PERF.md
# section 6, PR 38), by 256 | 512 | 1024 | 2048 keys a step: one row at a
# context of 2k 0.34 | 0.30 | 0.36 | 0.32 ms, at 24k 2.24 | 2.15 | 1.86 |
# 1.73 ms; four rows at 2k 0.88 | 0.97 | 1.97 | 2.15 ms
WALK_TOKENS = 512
# rows a decode call of a selecting layer may HOLD for each row it selects
# and still read its whole pool under the picks' mask (`paged_latent_decode`,
# a block a fetch) rather than fetch the picked rows one by one (XLA's
# gather).  One layer's read on the v5e at dots3's shape (16 slots, 128
# heads, blocks of 16 rows of 640 lanes, 2,048 picks a slot; PERF.md section
# 6, PR 44) by held / selected 2.0 | 2.7 | 4.0 | 5.0 | 5.7 | 6.2: in place
# 419 | 539 | 748 | 904 | 1,019 | 1,099 us (99 us and 4.9 ns a held row),
# fetched 893-898 us whatever is held: equal at 4.9
READ_ALL_UNDER = 4.9


class BlockAllocator:
    """Host-side free-list over the physical KV blocks.

    Deterministic (LIFO free list, ids handed out in ascending order
    from a fresh pool) so a seeded arrival trace produces an identical
    block-table history run to run — the engine's determinism tests
    rely on it.  Double-free and foreign ids raise instead of silently
    corrupting the pool."""

    def __init__(self, num_blocks: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = num_blocks
        # pop() yields 0, 1, 2, ... for a fresh pool
        self._free = list(range(num_blocks - 1, -1, -1))
        self._allocated: set[int] = set()
        self.high_water = 0

    @property
    def used(self) -> int:
        return len(self._allocated)

    @property
    def available(self) -> int:
        return len(self._free)

    def utilization(self) -> float:
        return self.used / self.num_blocks

    def alloc(self, n: int) -> list[int] | None:
        """``n`` block ids, or None if the pool cannot satisfy the
        request (caller keeps the request queued — no partial grants)."""
        if n < 1:
            raise ValueError(f"alloc needs n >= 1, got {n}")
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        self._allocated.update(blocks)
        self.high_water = max(self.high_water, self.used)
        return blocks

    def free(self, blocks: list[int]) -> None:
        for b in blocks:
            if b not in self._allocated:
                raise ValueError(f"freeing unallocated block {b}")
            self._allocated.remove(b)
            self._free.append(b)


def init_paged_cache(lm, num_blocks: int, block_size: int, dtype=None):
    """The device pool: per transformer block one ``{"k", "v"}`` pair of
    ``(num_blocks + 1, block_size, kv_heads * head_dim)`` arrays — a
    token's k/v is one row, heads major within it.  Index ``num_blocks``
    is the SCRATCH block — masked writes (pad tokens, inactive slots)
    land there and nothing ever reads it through a real block table."""
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    hd = lm.dim // lm.heads
    dt = dtype or jnp.float32
    shape = (num_blocks + 1, block_size, lm.kv_heads * hd)
    # distinct buffers per block/side: the engine donates the whole
    # cache pytree into its jitted steps, and donation rejects aliased
    # buffers
    return [
        {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
        for _ in lm.blocks
    ]


def _rope_slots(x, positions, *, base: float = 10000.0):
    """`nn.attention.rope` with PER-SLOT positions: ``x`` is
    ``(slots, heads, s, head_dim)`` and ``positions`` is ``(slots, s)``
    — each decode slot sits at its own global position.  Elementwise
    identical to the shared-positions rope for equal position values."""
    d = x.shape[-1]
    half = d // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _gathered_attention(q, k_pool, v_pool, block_tables, positions, *,
                        sliding_window):
    """The plain read side (prefill's, decode's off the TPU, and the
    reference the decode kernel is tested against): gather the per-slot
    tables back into a contiguous per-slot view, token-major ``(S, L,
    kv_heads, hd)`` by reshape alone; from there on the math is exactly
    `apply_cached`'s, contracted where the view lies.  ``q``: ``(S,
    heads, s, hd)``, scaled; ``positions``: ``(S, s)``."""
    S, heads, _, hd = q.shape
    kv_heads = k_pool.shape[2] // hd
    L = block_tables.shape[1] * k_pool.shape[1]
    with jax.named_scope("attn/kv_gather"):
        k_full = k_pool[block_tables].reshape(S, L, kv_heads, hd)
        v_full = v_pool[block_tables].reshape(S, L, kv_heads, hd)
        if heads != kv_heads:  # GQA: each K/V head once a query head
            k_full = jnp.repeat(k_full, heads // kv_heads, axis=2)
            v_full = jnp.repeat(v_full, heads // kv_heads, axis=2)
        k_full, v_full = k_full.astype(q.dtype), v_full.astype(q.dtype)
    with jax.named_scope("attn/scores"):
        logits = jnp.einsum("bhqd,bkhd->bhqk", q, k_full)
        pos_k = jnp.arange(L)[None, None, :]
        qpos = positions[:, :, None]
        visible = pos_k <= qpos  # (S, s, L), per-slot positions
        if sliding_window is not None:
            visible = visible & (pos_k > qpos - sliding_window)
        logits = jnp.where(visible[:, None], logits, -1e30)
        weights = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bhqd", weights, v_full)


def _walked_attention(q, k_pool, v_pool, block_tables, positions, *,
                      sliding_window):
    """`_gathered_attention` where a chunk's scores over a whole table's
    view would not fit (a context of tens of thousands): the keys are
    fetched through the tables `WALK_TOKENS` a step and attended under a
    running maximum, each row from the first block its window reaches (the
    table's first without one) and all rows as far as the call's longest
    context, so a chunk reads what it attends and not what a table could
    hold.  Scores and statistics are float32; a K/V head is read once for
    its group of query heads."""
    S, heads, s, hd = q.shape
    bs = k_pool.shape[1]
    kv_heads = k_pool.shape[2] // hd
    MB = block_tables.shape[1]
    nb = max(1, min(WALK_TOKENS // bs, MB))    # pool blocks a step
    T = nb * bs
    q = q.reshape(S, kv_heads, heads // kv_heads, s, hd)
    qpos = positions[:, None, None, :, None]
    first = (jnp.zeros((S,), jnp.int32) if sliding_window is None else
             jnp.maximum(positions.min(axis=1) - sliding_window + 1, 0) // T)
    steps = ((positions.max(axis=1) + T) // T - first).max()

    def walk(i, carry):
        top, total, acc = carry
        j = (first + i)[:, None] * nb + jnp.arange(nb)
        ids = jnp.take_along_axis(block_tables, jnp.minimum(j, MB - 1), axis=1)
        k = k_pool[ids].reshape(S, T, kv_heads, hd).astype(q.dtype)
        v = v_pool[ids].reshape(S, T, kv_heads, hd).astype(q.dtype)
        # a block past the table's end is some other block read again: its
        # places lie past every query's, so none is seen
        pos_k = ((first + i) * T)[:, None, None, None, None] + jnp.arange(T)
        seen = pos_k <= qpos
        if sliding_window is not None:
            seen = seen & (pos_k > qpos - sliding_window)
        logits = jnp.einsum("bgrqd,bkgd->bgrqk", q, k, preferred_element_type=jnp.float32)
        logits = jnp.where(seen, logits, -1e30)
        new_top = jnp.maximum(top, logits.max(axis=-1))
        e = jnp.where(seen, jnp.exp(logits - new_top[..., None]), 0.0)
        keep = jnp.exp(top - new_top)
        acc = acc * keep[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", e.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return new_top, total * keep + e.sum(axis=-1), acc

    lead = q.shape[:-1]
    start = (jnp.full(lead, -1e30, jnp.float32), jnp.zeros(lead, jnp.float32),
             jnp.zeros(q.shape, jnp.float32))
    _, total, acc = jax.lax.fori_loop(0, steps, walk, start)
    o = acc / jnp.maximum(total, 1e-30)[..., None]
    return o.reshape(S, heads, s, hd).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("sliding_window",))
def _attend_in_pool(q, k_pool, v_pool, block_tables, lengths, *,
                    sliding_window):
    """Decode's read side: query ``q[s]`` ``(S, heads, hd)`` attends the
    ``lengths[s]`` places its slot holds (0: nothing, a row of zeros).
    Which way is `ops.kernel_for_platform`'s to say, where the program is
    LOWERED: `ops.paged_attention_decode` on a TPU; anywhere else the
    gathered view, because what another platform could do with the
    kernel is interpret it, at several times the view's cost on a CPU
    (the kernel's own tests do, tests/test_paged_attention_kernel.py).

    A function of its own under `jax.jit` so that a model's layers, which
    call it with the same shapes, share ONE trace and ONE lowered kernel:
    traced inline, 48 layers put 48 copies of the kernel into the
    program's module and twelve seconds onto every start."""

    def view(q, k_pool, v_pool, block_tables, lengths):
        o = _gathered_attention(
            q[:, :, None], k_pool, v_pool, block_tables,
            lengths[:, None] - 1, sliding_window=sliding_window,
        )[:, :, 0]
        return jnp.where((lengths > 0)[:, None, None], o, 0).astype(q.dtype)

    return ops.kernel_for_platform(
        functools.partial(ops.paged_attention_decode,
                          sliding_window=sliding_window),
        view, q, k_pool, v_pool, block_tables, lengths,
    )


def _paged_attention(attn, params, x, k_pool, v_pool, block_tables,
                     positions, write_mask, block_size: int,
                     scopes=("attn/kv_scatter", "attn/scores")):
    """One block's incremental attention against the paged pool (or a
    layer's rings laid out as one: `init_ring_cache`; ``scopes`` names the
    write and the read on the device).

    ``x``: ``(S, s, dim)`` new-token activations for S slots;
    ``positions``: ``(S, s)`` global positions; ``write_mask``:
    ``(S, s)`` — True rows write their k/v into the pool, False rows
    (pads / inactive slots) write to the scratch block.  Returns
    ``(y, k_pool, v_pool)`` — same contract as
    `MultiHeadAttention.apply_cached`, with the contiguous cache
    replaced by the scatter and, on the read side, `_attend_in_pool`
    (``s == 1``: the decode kernel on a TPU) or the gathered view
    (``s > 1``; walked in parts where its scores would pass
    `SCORE_BYTES`)."""
    S, s, _ = x.shape
    write, read = scopes
    with jax.named_scope("attn/qkv"):
        q, k, v = attn._project(params, x)
        if attn.use_rope:
            q, k = _rope_slots(q, positions), _rope_slots(k, positions)

    with jax.named_scope(write):
        scratch = k_pool.shape[0] - 1
        blk = jnp.take_along_axis(
            block_tables, positions // block_size, axis=1
        )
        blk = jnp.where(write_mask, blk, scratch).reshape(-1)
        off = (positions % block_size).reshape(-1)
        # (S, kv_heads, s, hd) -> one row of kv_heads * hd per token
        k_w = jnp.moveaxis(k.astype(k_pool.dtype), 1, 2).reshape(S * s, -1)
        v_w = jnp.moveaxis(v.astype(v_pool.dtype), 1, 2).reshape(S * s, -1)
        k_pool = k_pool.at[blk, off].set(k_w)
        v_pool = v_pool.at[blk, off].set(v_w)

    if s == 1:
        # decode: one query a slot attends the blocks its slot holds, in
        # the pool (the new token's row was written just above); a slot
        # that writes nothing attends nothing
        with jax.named_scope(read):
            o = _attend_in_pool(
                (q * attn.scale)[:, :, 0], k_pool, v_pool, block_tables,
                jnp.where(write_mask[:, 0], positions[:, 0] + 1, 0),
                sliding_window=attn.sliding_window,
            )[:, :, None]
    else:
        L = block_tables.shape[1] * block_size
        attend = (_walked_attention if 4 * S * attn.heads * s * L > SCORE_BYTES
                  else _gathered_attention)
        with jax.named_scope(read):
            o = attend(q * attn.scale, k_pool, v_pool, block_tables, positions,
                       sliding_window=attn.sliding_window)
    with jax.named_scope("attn/out"):
        y = attn._output(params, x, o)
    return y, k_pool, v_pool


def ring_blocks(window: int, chunk: int, block_size: int) -> int:
    """Blocks a slot's ring has: the window and ``chunk`` new tokens."""
    return -(-(window - 1 + chunk) // block_size)


def init_ring_cache(attn, max_batch: int, block_size: int, dtype, chunk: int):
    """What a windowed grouped-query layer keeps: no pool under the
    engine's tables but for every decode slot a ring of ``window - 1 +
    chunk`` positions (``chunk``: the most new tokens a call may bring a
    row), rounded up to whole blocks, position ``p`` at row ``p mod
    rows``: it never holds more of a request, however long.  All slots'
    rings lie in ONE pair of arrays of `init_paged_cache`'s layout,
    ``(max_batch * blocks + 1, block_size, kv_heads * head_dim)``, slot
    ``i``'s blocks at ``i * blocks``, the last block scratch, so that
    `_paged_attention` writes and attends a ring through the tables of
    `ring_tables` as it does the pool through the engine's."""
    blocks = ring_blocks(attn.sliding_window, chunk, block_size)
    shape = (max_batch * blocks + 1, block_size, attn.kv_heads * attn.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def ring_tables(slots, rows: int, blocks: int, max_blocks: int):
    """``(rows, max_blocks)``: the block table under which row ``r``'s
    ring of ``blocks`` blocks reads as a paged sequence: the block of
    positions ``[j * bs, (j + 1) * bs)`` is block ``j mod blocks`` of its
    slot's ring
    (``slots``: each row's decode slot, None where row ``i`` IS slot
    ``i``).  Within a query's window every position lies in a block this
    request wrote after whatever it held before (the ring holds the window
    and the call's new tokens), so a slot's earlier tenant and a wrapped
    block are never seen and admission resets nothing."""
    slot = jnp.arange(rows, dtype=jnp.int32) if slots is None else slots
    return slot[:, None] * blocks + jnp.arange(max_blocks, dtype=jnp.int32) % blocks


def paged_apply_cached(lm, params, tokens, cache, block_tables, positions,
                       write_mask, block_size: int):
    """`TransformerLM.apply_cached` against the paged pool.

    ``tokens``: ``(S, s)`` new tokens for S slots (s = 1 for decode
    steps, s = chunk for prefill); ``positions``: ``(S, s)`` each
    token's global position; ``block_tables``: ``(S, max_blocks)``
    physical block ids per slot; ``write_mask``: ``(S, s)`` True where
    the token is real (False rows read/write scratch and their logits
    are garbage the caller ignores).  Returns
    ``(logits (S, s, vocab), new_cache)``.

    Token-identical to the dense path (tested): the held blocks hold the
    dense contiguous cache's values for every visible position; the
    gathered view runs the dense ops on them, the decode kernel the same
    attention with a streaming softmax."""
    L = block_tables.shape[1] * block_size
    with jax.named_scope("embed"):
        positions = jnp.clip(positions, 0, min(lm.max_seq, L) - 1)
        h = params["embed"]["table"][tokens]
        if lm.pos_embedding == "learned":
            h = h + params["pos"][0][positions]
    new_cache = []
    for blk, pb, c in zip(lm.blocks, params["blocks"], cache):
        with jax.named_scope("ln"):
            x1, _ = blk.ln1.apply(pb["ln1"], {}, h)
        o, ck, cv = _paged_attention(
            blk.attn, pb["attn"], x1, c["k"], c["v"], block_tables,
            positions, write_mask, block_size,
        )
        h = h + o
        with jax.named_scope("ln"):
            x2, _ = blk.ln2.apply(pb["ln2"], {}, h)
        with jax.named_scope("mlp"):
            h = h + lm._mlp_or_moe(blk, pb, x2)
        new_cache.append({"k": ck, "v": cv})
    with jax.named_scope("lm_head"):
        h, _ = lm.ln.apply(params["ln"], {}, h)
        logits = h @ params["embed"]["table"].T
    return logits, new_cache


LANES = 128   # the minor tile of the device's layouts


def _whole_tiles(width: int) -> int:
    return -(-width // LANES) * LANES


def init_latent_cache(attn, max_batch: int, num_blocks: int, block_size: int,
                      dtype, ring_rows: int | None = None):
    """What a `nn.LatentAttention` layer keeps, as ``(pools, per-slot
    state)``.  A layer over the whole context: under the engine's block
    tables a pool of ONE latent row a token, ``ckv (num_blocks + 1,
    block_size, row)``, and, where it selects its keys, a pool of the
    indexer's key, ``ik (..., index_dim)``; no state.  A windowed layer:
    no pool, and a ring of
    ``ring_rows`` positions a decode slot, ``ring (max_batch, ring_rows,
    row)``, position ``p`` at row ``p mod ring_rows``: it never holds more
    of a request, however long.

    ``row`` is the latent row (``kv_rank + rope_dim`` values) rounded up
    to whole 128-lane tiles, the tail zero: the device pads a minor
    dimension to that anyway, and where the padding is left to it, it
    stores an array whose row is no whole number of tiles (576, 1088)
    with ANOTHER dimension minor-most wherever that pads less, and every
    program then relayouts every such array on entry and again on exit
    (the fault `paged_kv`'s folded k/v rows cured; compiled for the v5e
    at the published sizes: 8 copies of 264 MB and 12 of 27 MB a decode
    step)."""
    row = _whole_tiles(attn.row)
    if attn.window is None:
        pool = (num_blocks + 1, block_size)
        pools = {"ckv": jnp.zeros(pool + (row,), dtype)}
        if attn.index_topk:
            pools["ik"] = jnp.zeros(pool + (attn.index_dim,), dtype)
        return pools, {}
    return {}, {"ring": jnp.zeros((max_batch, ring_rows, row), dtype)}


def _padded(rows, width: int):
    """``rows (..., w)`` with zeros behind, ``width`` wide."""
    return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, width - rows.shape[-1])])


def _write_places(pool, block_tables, positions, write_mask, block_size: int):
    """``(block, offset)`` of each of a call's tokens in ``pool``, flat: its
    table's block for the position, the scratch block for a masked token."""
    blk = jnp.take_along_axis(block_tables, positions // block_size, axis=1)
    blk = jnp.where(write_mask, blk, pool.shape[0] - 1).reshape(-1)
    return blk, (positions % block_size).reshape(-1)


def _paged_latent_attention(attn, params, x, pools, block_tables, positions,
                            write_mask, block_size: int):
    """A key-selecting latent layer against its two pools (the contract
    of `_paged_attention`; ``pools = {"ckv", "ik"}``).  Each new token's
    latent row and index key are scattered as `_paged_attention` scatters
    k/v; every query then scores the index keys its slot holds and picks
    the ``index_topk`` places of largest score (`top_visible`'s set).  ONE
    query a slot (decode): absorbed attention over the picks, their rows
    reached one of two ways by what the call holds (one `lax.cond` on its
    own counts).  Where it holds no more than `READ_ALL_UNDER` rows for
    each it selects, the picks go as a mask (found without a sort:
    `ops.kth_score`) with the absorbed query to `_attend_rows_in_pool`,
    which reads every held row where it lies, a block a fetch; where its
    contexts are long against ``index_topk``, the picks are taken as
    indices (`lax.top_k`) and their rows of ``ckv`` fetched through the
    block table one by one and attended as fetched (a slot that holds fewer
    than ``index_topk`` masks the rest).  The same rows get weight either
    way, the others none.  SEVERAL (a prefill chunk): the same picks as a
    mask over the gathered view, scores and attention walked over it only
    as far as the call's longest context reaches.
    Returns ``(y, pools, (keys scored, rows selected, rows read))``, the
    counts over the real queries: rows read are the rows held where the
    read lay a mask over them, the rows selected where it fetched those."""
    S, s, _ = x.shape
    L = block_tables.shape[1] * block_size
    topk = min(attn.index_topk, L)
    c_q, q_n, q_r = attn.queries(params, x, positions)
    rows = attn.rows(params, x, positions)
    keys = attn.index_keys(params, x, positions)
    q_i, w = attn.index_queries(params, x, c_q, positions)

    with jax.named_scope("mla/cache_write"):
        ckv, ik = pools["ckv"], pools["ik"]
        blk, off = _write_places(ckv, block_tables, positions, write_mask, block_size)
        rows = _padded(rows.astype(ckv.dtype), ckv.shape[-1])
        ckv = ckv.at[blk, off].set(rows.reshape(S * s, -1))
        ik = ik.at[blk, off].set(keys.astype(ik.dtype).reshape(S * s, -1))

    with jax.named_scope("dsa/index"):
        held_keys = ik[block_tables].reshape(S, L, -1).astype(x.dtype)
    t = jnp.where(write_mask, positions, -1)      # a masked query sees nothing
    held = t.max() + 1                            # no row of the call sees a place past it
    scores = attn.index_scores(q_i, w, held_keys, held)
    places = jnp.where(write_mask, positions + 1, 0)     # what each real query's slot holds
    scored, selected = (places.sum(dtype=jnp.int32),
                        jnp.minimum(places, topk).sum(dtype=jnp.int32))
    if s == 1:
        read_all = scored <= READ_ALL_UNDER * selected
        o = _attend_picks_in_pool(
            attn, {name: params[name] for name in ("w_uk", "w_uv")}, q_n, q_r, ckv,
            block_tables, t, scores, read_all, block_size=block_size)
        read = jnp.where(read_all, scored, selected)
    else:
        with jax.named_scope("dsa/topk"):
            causal = jnp.arange(L)[None, None, :] <= t[:, :, None]
            picked = top_visible(scores, causal, topk)
        with jax.named_scope("dsa/gather"):
            seen = ckv[block_tables].reshape(S, L, -1)[..., :attn.row].astype(x.dtype)
        with jax.named_scope("mla/attend"):
            o = attn.absorbed(params, q_n, q_r, seen, picked, held)
        read = scored     # the mask lies over the view of everything held
    return attn.output(params, x, o), {"ckv": ckv, "ik": ik}, (scored, selected, read)


@functools.partial(jax.jit, static_argnames=("attn", "block_size"))
def _attend_picks_in_pool(attn, weights, q_n, q_r, ckv, block_tables, t, scores, read_all, *,
                          block_size: int):
    """Decode's read of a selecting layer: of the places ``<= t (S, 1)`` of
    each slot's table the ``index_topk`` of largest ``scores (S, 1, L)``,
    ties to the lower place, and absorbed attention over those rows of
    ``ckv`` -> ``o (S, 1, H, v_dim)``; ``weights``: the layer's ``w_uk`` and
    ``w_uv``.  ``read_all`` says, on the device, how the rows are reached:
    as a mask over everything held (`top_visible`, which sorts nothing,
    then `_attend_pool_absorbed`) or, the picks as indices
    (`lax.top_k`: this arm alone sorts), fetched one by one through the
    table.  A function of its own under `jax.jit` for
    `_attend_in_pool`'s reason: the two arms traced afresh in each selecting
    layer of both decode programs were a second of every start."""
    L = scores.shape[-1]
    topk = min(attn.index_topk, L)
    causal = jnp.arange(L)[None, :] <= t

    def in_place():
        with jax.named_scope("dsa/topk"):
            # the picks as a mask: every place above the k-th value and, of
            # the places that tie with it, those up to the last one picked
            # (the lower ones): two numbers a row, found without a sort
            keep = top_visible(scores[:, 0], causal, topk)
        with jax.named_scope("mla/attend"):
            return _attend_pool_absorbed(attn, weights, q_n, q_r, ckv, block_tables, t, keep)

    def fetched():
        with jax.named_scope("dsa/topk"):
            best, picks = jax.lax.top_k(jnp.where(causal, scores[:, 0], -jnp.inf), topk)
        with jax.named_scope("dsa/gather"):
            blk = jnp.take_along_axis(block_tables, picks // block_size, axis=1)
            seen = ckv[blk, picks % block_size][..., :attn.row].astype(q_n.dtype)
        with jax.named_scope("mla/attend"):
            return attn.absorbed(weights, q_n, q_r, seen, (best > -jnp.inf)[:, None])

    return jax.lax.cond(read_all, in_place, fetched)


def _attend_pool_absorbed(attn, params, q_n, q_r, ckv, block_tables, t, keep=None):
    """``o (S, 1, H, v_dim)`` of ONE query a slot, at place ``t (S, 1)``
    (-1: a slot that is to read nothing), over the rows its slot holds in
    ``ckv``, read where they lie (`_attend_rows_in_pool`): the query with
    ``W_uk`` folded in and padded to the pool's lanes, ``W_uv`` applied to
    what comes back."""
    q = jnp.concatenate(
        [jnp.einsum("shd,hdr->shr", q_n[:, 0], params["w_uk"]), q_r[:, 0]], axis=-1)
    o_c = _attend_rows_in_pool(_padded(q, ckv.shape[-1]), ckv, block_tables, t[:, 0] + 1,
                               v_width=attn.kv_rank, scale=attn.scale, keep=keep)
    return jnp.einsum("shr,hrd->shd", o_c, params["w_uv"])[:, None]


@functools.partial(jax.jit, static_argnames=("v_width", "scale"))
def _attend_rows_in_pool(q, pool, block_tables, lengths, *, v_width: int, scale: float,
                         keep=None):
    """Decode's read of a latent pool: the absorbed query ``q[s]`` ``(S,
    heads, row)`` (``W_uk`` folded in, padded to the pool's lanes) attends
    the ``lengths[s]`` rows its slot holds, or of them those that ``keep
    (S, L)`` marks -> each head's weighted sum of the rows' first
    ``v_width`` lanes ``(S, heads, v_width)``, zeros for a slot that holds
    or keeps nothing.  As `_attend_in_pool`: the kernel
    (`ops.paged_latent.paged_latent_decode`) where the program is lowered
    for a TPU, the absorbed form over the gathered view anywhere else; a
    function of its own under `jax.jit`, so that a model's sublayers share
    one trace and one lowered kernel."""
    from tpu_dist.ops.paged_latent import paged_latent_decode

    def view(q, pool, block_tables, lengths, keep=None):
        S, L = q.shape[0], block_tables.shape[1] * pool.shape[1]
        rows = pool[block_tables].reshape(S, L, -1).astype(q.dtype)
        logits = scale * jnp.einsum("shc,slc->shl", q, rows,
                                    preferred_element_type=jnp.float32)
        seen = jnp.arange(L)[None, :] < lengths[:, None]
        if keep is not None:
            seen &= keep
        weights = jax.nn.softmax(jnp.where(seen[:, None], logits, -1e30), axis=-1).astype(q.dtype)
        o = jnp.einsum("shl,slr->shr", weights, rows[..., :v_width])
        return jnp.where(seen.any(axis=1)[:, None, None], o, 0).astype(q.dtype)

    def kernel(q, pool, block_tables, lengths, keep=None):
        return paged_latent_decode(q, pool, block_tables, lengths, v_width=v_width,
                                   scale=scale, keep=keep)

    masks = () if keep is None else (keep,)
    return ops.kernel_for_platform(kernel, view, q, pool, block_tables, lengths, *masks)


def _whole_latent_attention(attn, params, x, ckv, block_tables, positions,
                            write_mask, block_size: int):
    """A latent layer with neither selection nor window against its ONE
    pool (the contract of `_paged_attention`): each new token's latent row
    is scattered as `_paged_attention` scatters k/v, then every query
    attends EVERY row its slot holds.  ONE query a slot (decode): the rows
    are read where they lie (`_attend_rows_in_pool`), the query with
    ``W_uk`` folded in, ``W_uv`` applied to what comes back.  SEVERAL (a
    prefill chunk): absorbed attention over the gathered view, walked only
    as far as the call's longest context reaches.  Returns ``(y, ckv, rows
    attended)``, the count over the real queries."""
    S, s, _ = x.shape
    L = block_tables.shape[1] * block_size
    _, q_n, q_r = attn.queries(params, x, positions)
    rows = attn.rows(params, x, positions)

    with jax.named_scope("mla/cache_write"):
        blk, off = _write_places(ckv, block_tables, positions, write_mask, block_size)
        rows = _padded(rows.astype(ckv.dtype), ckv.shape[-1])
        ckv = ckv.at[blk, off].set(rows.reshape(S * s, -1))

    t = jnp.where(write_mask, positions, -1)      # a masked query sees nothing
    with jax.named_scope("mla/attend"):
        if s == 1:
            o = _attend_pool_absorbed(attn, params, q_n, q_r, ckv, block_tables, t)
        else:
            seen = ckv[block_tables].reshape(S, L, -1)[..., :attn.row].astype(x.dtype)
            causal = jnp.arange(L)[None, None, :] <= t[:, :, None]
            o = attn.absorbed(params, q_n, q_r, seen, causal, t.max() + 1)
    return attn.output(params, x, o), ckv, (t + 1).sum(dtype=jnp.int32)


def _ring_latent_attention(attn, params, x, ring, positions, write_mask, slots):
    """A windowed latent layer against its per-slot ring ``(max_batch, R,
    row)``; ``slots``: each row's decode slot, None where row ``i`` IS
    slot ``i``.  The real tokens' rows are written at ``position mod R``
    (a masked token writes nothing), then every query attends the ring.
    A ring row is visible by the position it MUST hold, the last ``p <=``
    the slot's newest with ``p = row (mod R)``: inside a query's window
    that is a row this request wrote, so a slot's earlier tenant and a
    wrapped row are never seen and admission resets nothing.  Wants ``R
    >= window - 1 + s``.  One query a slot attends absorbed, a chunk
    expanded.  Returns ``(y, ring, rows attended)``."""
    S, s, _ = x.shape
    R, W = ring.shape[1], attn.window
    if R < W - 1 + s:
        raise ValueError(f"a ring of {R} rows holds a window of {W} and {R - W + 1} "
                         f"new tokens a call, not {s}")
    c_q, q_n, q_r = attn.queries(params, x, positions)
    rows = attn.rows(params, x, positions)
    with jax.named_scope("swa/ring_rw"):
        slot = jnp.arange(S) if slots is None else slots
        at = jnp.where(write_mask, positions % R, R)   # out of range: dropped
        ring = ring.at[slot[:, None], at].set(
            _padded(rows.astype(ring.dtype), ring.shape[-1]), mode="drop")
        seen = (ring if slots is None else ring[slots])[..., :attn.row].astype(x.dtype)
        t = jnp.where(write_mask, positions, -1)
        newest = t.max(axis=1, keepdims=True)
        holds = newest - (newest - jnp.arange(R)[None, :]) % R
        visible = attn.visible(params, x, c_q, t, holds)
    with jax.named_scope("swa/attend"):
        attend = attn.absorbed if s == 1 else attn.expanded
        o = attend(params, q_n, q_r, seen, visible)
    attended = jnp.minimum(jnp.where(write_mask, positions + 1, 0), W).sum(dtype=jnp.int32)
    return attn.output(params, x, o), ring, attended
