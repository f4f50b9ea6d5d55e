"""Ring attention — sequence/context parallelism over the ppermute ring.

The reference has no sequence models (SURVEY.md §2d records SP/CP as
absent; its only ring is the ring *allreduce*, allreduce.py:18-32), but the
communication topology is identical: blocks circulate around the same
neighbor ring the hand-rolled allreduce uses.  This module makes
long-context a first-class capability: sequences sharded over a mesh axis,
K/V blocks rotated via ``lax.ppermute``, attention accumulated blockwise
with a numerically-stable streaming softmax (the log-sum-exp running
rescale of Flash/Ring attention), so no device ever materializes the full
(seq × seq) score matrix or the full K/V.

Communication per step rides ICI exactly like `ring_all_reduce`; compute
(the two einsums) stays on the MXU, and XLA overlaps the next block's
CollectivePermute with the current block's matmuls inside the scanned body.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from tpu_dist.comm.collectives import ring_perm

NEG_INF = -1e30


def _block_update(m, l, acc, logits, v_blk, mask):
    """One streaming-softmax accumulation step.

    m: (..., sq) running row max;  l: (..., sq) running denominator;
    acc: (..., sq, d) running numerator; logits: (..., sq, sk);
    mask: broadcastable to logits (True = attend).
    """
    logits = jnp.where(mask, logits, NEG_INF)
    m_new = jnp.maximum(m, logits.max(-1))
    # Rescale previous accumulation; exp of fully-masked entries is zeroed
    # by re-masking (NEG_INF is finite, so no NaNs from inf - inf).
    correction = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new[..., None])
    p = jnp.where(mask, p, 0.0)
    l_new = l * correction + p.sum(-1)
    acc_new = acc * correction[..., None] + jnp.einsum("...qk,...kd->...qd", p, v_blk)
    return m_new, l_new, acc_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Blockwise ring attention over sequence shards.

    Args:
      q, k, v: local shards of shape ``(..., s_local, d)`` (e.g.
        ``(batch, heads, s_local, d)``), with the sequence axis sharded
        over mesh axis ``axis_name``; global sequence order is rank-major.
      causal: apply a causal mask over *global* positions.
      window: sliding-window band ``k > q - window`` over *global*
        positions (combine with ``causal`` for the Mistral-style local
        band) — same semantics as `nn.dot_product_attention(window=)`,
        so windowed models train sequence-parallel == dense.

    Returns the local output shard ``(..., s_local, d)`` in the input
    dtype.  Numerically matches `tpu_dist.nn.dot_product_attention` on the
    gathered sequence (tests assert this on the simulated mesh).
    Accumulators (running max / denominator / numerator) are kept in
    float32 regardless of input dtype — with bf16 inputs on long
    sequences, accumulating thousands of exp terms in an 8-bit mantissa
    would destroy the streaming softmax (standard flash/ring practice).
    """
    n = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    s_local = q.shape[-2]
    d = q.shape[-1]
    scale = d**-0.5
    qs = (q * scale).astype(q.dtype)

    perm = ring_perm(n)
    lead = q.shape[:-2]
    m0 = jnp.full(lead + (s_local,), NEG_INF, jnp.float32)
    l0 = jnp.zeros(lead + (s_local,), jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)

    local_pos = jnp.arange(s_local)

    def block_step(m, l, acc, k_blk, v_blk, kv_rank):
        # MXU matmul in input precision; softmax bookkeeping in f32.
        logits = jnp.einsum(
            "...qd,...kd->...qk", qs, k_blk, preferred_element_type=jnp.float32
        )
        q_pos = r * s_local + local_pos  # global query positions
        k_pos = kv_rank * s_local + local_pos
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((s_local, s_local), bool)
        if window is not None:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        return _block_update(m, l, acc, logits, v_blk, mask)

    # Local block first, then n-1 steps of (rotate, process): exactly
    # 2(n-1) CollectivePermutes — rotating after the LAST block would ship
    # a full K+V around the ring only to be discarded.
    m, l, acc = block_step(m0, l0, acc0, k, v, r)

    def step(carry, t):
        m, l, acc, k_blk, v_blk = carry
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        # after t+1 rotations we hold the block from rank (r - t - 1) mod n
        kv_rank = (r - t - 1) % n
        m, l, acc = block_step(m, l, acc, k_blk, v_blk, kv_rank)
        return (m, l, acc, k_blk, v_blk), None

    if n > 1:
        (m, l, acc, _, _), _ = lax.scan(
            step, (m, l, acc, k, v), jnp.arange(n - 1)
        )
    return (acc / l[..., None]).astype(q.dtype)


def _ring_flash_impl(q, k, v, axis_name, causal, bq, bk, interpret):
    import functools as _ft

    from tpu_dist.ops.flash_attention import flash_attention_lse

    n = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    s_local = q.shape[-2]
    perm = ring_perm(n)
    flash = _ft.partial(
        flash_attention_lse, bq=bq, bk=bk, interpret=interpret
    )

    def combine(m, l, acc, out_b, lse_b):
        # blocks arrive pre-normalized; lse re-weights them exactly
        m_new = jnp.maximum(m, lse_b)
        c = jnp.exp(m - m_new)
        w = jnp.exp(lse_b - m_new)
        return (
            m_new,
            l * c + w,
            acc * c[..., None] + w[..., None] * out_b.astype(jnp.float32),
        )

    m = jnp.full(q.shape[:-1], NEG_INF, jnp.float32)
    l = jnp.zeros(q.shape[:-1], jnp.float32)
    acc = jnp.zeros(q.shape, jnp.float32)
    # The DIAGONAL block is always the first processed (kv starts as the
    # local shard), so the causal-within-block kernel variant is selected
    # statically — one flash call per block, never two.
    out_b, lse_b = flash(q, k, v, causal=causal)
    m, l, acc = combine(m, l, acc, out_b, lse_b)

    def step(carry, t):
        m, l, acc, k_blk, v_blk = carry
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        kv_rank = (r - t - 1) % n
        # off-diagonal: fully visible, unless the kv block belongs to a
        # LATER rank under the causal mask — then its weight is zeroed
        # via lse = -inf (SPMD lockstep computes the block regardless)
        out_b, lse_b = flash(q, k_blk, v_blk, causal=False)
        if causal:
            lse_b = jnp.where(kv_rank > r, NEG_INF, lse_b)
        m, l, acc = combine(m, l, acc, out_b, lse_b)
        return (m, l, acc, k_blk, v_blk), None

    if n > 1:
        (m, l, acc, _, _), _ = lax.scan(
            step, (m, l, acc, k, v), jnp.arange(n - 1)
        )
    # fully-masked rows cannot occur: the diagonal block always
    # contributes (causal attends at least to self), so l > 0
    return (acc / l[..., None]).astype(q.dtype)


def ring_attention_flash(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    bq: int | None = None,
    bk: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """`ring_attention` with each block computed by the Pallas flash
    kernel: the (s_local, s_local) score block never round-trips HBM —
    the canonical long-context composition (a ring of flash blocks,
    recombined exactly via each block's log-sum-exp).

    Same contract as `ring_attention` (sequence shards, rank-major
    global order, causal over global positions) and numerically equal to
    it (tested).  Differentiable: the VJP recomputes through the
    dense-block ring — the same function, so gradients are exact; the
    flash path pays off on the forward (prefill/eval are forward-only,
    and in training the backward already streams blockwise).
    """
    import functools as _ft

    @_ft.partial(jax.custom_vjp)
    def rf(q, k, v):
        return _ring_flash_impl(q, k, v, axis_name, causal, bq, bk, interpret)

    def rf_fwd(q, k, v):
        return rf(q, k, v), (q, k, v)

    def rf_bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(
            lambda q_, k_, v_: ring_attention(
                q_, k_, v_, axis_name, causal=causal
            ),
            q, k, v,
        )
        return vjp(g)

    rf.defvjp(rf_fwd, rf_bwd)
    return rf(q, k, v)


class RingMultiHeadAttention:
    """Sequence-parallel MHA module: drop-in for
    `tpu_dist.nn.MultiHeadAttention` inside shard_map'd code whose inputs
    are sequence shards over ``axis_name``.

    QKV/out projections are token-local (no communication); only the
    attention core rotates K/V blocks around the ring.  Init is identical
    to the dense module's, so the same checkpoint runs sharded or not —
    tests assert numerical agreement with the unsharded module.
    """

    def __init__(self, dim: int, heads: int, *, axis_name: str,
                 causal: bool = False, use_rope: bool = False,
                 use_flash: bool = False, interpret: bool = False,
                 core: str = "ring", sliding_window: int | None = None):
        from tpu_dist import nn  # local import: nn must not depend on parallel

        if core not in ("ring", "ulysses"):
            raise ValueError(f"core must be 'ring' or 'ulysses', got {core!r}")
        if sliding_window is not None and use_flash and core != "ulysses":
            # (the ulysses core never consults use_flash — its local
            # attention is full-sequence, so the band applies exactly)
            raise ValueError(
                "sliding_window is not supported with use_flash yet — "
                "the per-block flash kernels have no cross-shard band "
                "offset; use the dense blockwise ring or ulysses cores"
            )
        self.sliding_window = sliding_window
        self.core = core
        self.axis_name = axis_name
        self.causal = causal
        self.use_rope = use_rope
        # use_flash: compute each ring block with the Pallas flash kernel
        # (`ring_attention_flash`) instead of the dense blockwise core —
        # same numbers, no (s_local, s_local) HBM round-trip per block.
        # interpret only matters with use_flash (CPU-sim testing).
        self.use_flash = use_flash
        self.interpret = interpret
        self._dense = nn.MultiHeadAttention(
            dim, heads, causal=causal, use_rope=use_rope
        )
        self.dim = dim
        self.heads = heads
        self.head_dim = dim // heads

    def init(self, key, input_shape):
        return self._dense.init(key, input_shape)

    def out_shape(self, input_shape):
        return input_shape

    def apply(self, params, state, x, *, train=False, key=None):
        d = self._dense
        b, s_local, _ = x.shape
        qkv, _ = d._qkv.apply(params["qkv"], {}, x)
        qkv = qkv.reshape(b, s_local, 3, self.heads, self.head_dim)
        q, k, v = (jnp.moveaxis(qkv[:, :, i], 1, 2) for i in range(3))
        if self.use_rope:
            # rope is a pure function of each token's GLOBAL position, so
            # rotating the local q/k shards before the ring reproduces the
            # dense rope attention exactly (K blocks travel pre-rotated).
            from jax import lax

            from tpu_dist import nn

            r = lax.axis_index(self.axis_name)
            pos = r * s_local + jnp.arange(s_local)
            q, k = nn.rope(q, pos), nn.rope(k, pos)
        if self.core == "ulysses":
            # all-to-all head resharding: full-sequence attention on a
            # head subset (q/k enter pre-rotated by GLOBAL position, so
            # rope survives the resharding exactly)
            from tpu_dist.parallel.ulysses import ulysses_attention

            o = ulysses_attention(
                q, k, v, self.axis_name, causal=self.causal,
                window=self.sliding_window,
            )
        elif self.use_flash:
            o = ring_attention_flash(
                q, k, v, self.axis_name, causal=self.causal,
                interpret=self.interpret,
            )
        else:
            o = ring_attention(
                q, k, v, self.axis_name, causal=self.causal,
                window=self.sliding_window,
            )
        o = jnp.moveaxis(o, 1, 2).reshape(b, s_local, self.dim)
        y, _ = d._out.apply(params["out"], {}, o)
        return y, state
