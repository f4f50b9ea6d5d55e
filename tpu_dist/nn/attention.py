"""Attention — functional core + module wrapper.

Not in the reference (its model is a 2-conv MNIST net, train_dist.py:53-71;
SURVEY.md §2d records sequence models as absent), but first-class here: the
ViT-Tiny extended config (BASELINE.json config 5) and the long-context
sequence-parallel path (`tpu_dist.parallel.ring_attention`) both build on
this exact function, so the single-device and ring-sharded paths are
numerically comparable by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_dist import ops
from tpu_dist.ops import partitioning
from tpu_dist.nn.core import Module
from tpu_dist.nn.layers import Dense, RMSNorm


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    mask: jax.Array | None = None,
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Softmax attention. Shapes: (..., heads, seq, head_dim).

    ``mask``: optional boolean array broadcastable to
    ``(..., heads, sq, sk)`` — True = attend.  Combined (AND) with the
    causal mask; use it for padding (keys of pad tokens False) or
    segment/block-diagonal masking of packed sequences.  Rows with no
    visible key produce zeros (softmax over an empty set is defined as
    0 here rather than NaN).

    ``causal`` with unequal query/key lengths uses BOTTOM-RIGHT (suffix)
    alignment: the queries are taken to be the last ``sq`` positions of
    the ``sk``-long key sequence (tril offset ``sk - sq``) — the
    decode-style convention flash-attention implementations use.  For any
    other cross-attention alignment, build the mask yourself.

    ``window=w`` restricts attention to the sliding band ``k > q - w``
    (Mistral-style local attention; combine with ``causal`` for the
    autoregressive band).  The flash kernel handles it NATIVELY —
    blocks outside the band are skipped, O(S·w) work — while the dense
    path materializes the band mask.

    ``scale`` multiplies the scores; ``None`` is ``head_dim ** -0.5``
    (a model with an explicit attention multiplier passes its own, and
    stays on the dense path: the flash kernel fixes the default).

    The program picks its own kernel (`ops.kernel_for_platform`): where
    `ops.flash_attention_takes` these shapes and the program is lowered
    for a TPU, the blockwise Pallas kernel (`tpu_dist.ops.flash_attention`)
    computes it with no (S, S) array; anywhere else `dense_attention`.
    In a program that XLA partitions over a mesh (traced under
    `parallel.partitioned_over`) the same choice is made for one device's
    share, inside a `shard_map` over the axes the builder named for the
    batch and the heads; where they do not divide these shapes the dense
    form stays, which the compiler can partition.
    Numerics match to fp tolerance, differentiable either way."""
    dense = functools.partial(
        dense_attention, causal=causal, mask=mask, window=window, scale=scale
    )
    if not ops.flash_attention_takes(q, k, v, mask=mask, scale=scale):
        return dense(q, k, v)
    said = partitioning.partitioned()
    spec = said.attention_spec(q.shape) if said is not None else None
    if spec is not None:
        return _per_device_attention(causal, window, said.mesh, spec)(q, k, v)
    return ops.kernel_for_platform(
        functools.partial(ops.flash_attention, causal=causal, window=window),
        dense, q, k, v,
    )


@functools.lru_cache(maxsize=None)
def _per_device_attention(causal, window, mesh, spec):
    """`dot_product_attention` of one device's share of q, k and v, which
    ``spec`` splits over ``mesh``: ONE jitted function for every call with
    the same arguments, so that a model's layers after the first, and
    their `jax.checkpoint`, JVP and transpose, reuse the first's jaxprs
    (a `shard_map` traced afresh in each of gpt2-xl's 48 layers cost 24 s
    of set-up: PERF.md section 6, PRs 33 and 36).  Inside, every axis is
    manual and `ops.kernel_for_platform` takes the kernel by itself."""

    def attention_per_device(q, k, v):
        said = partitioning.partitioned()
        if said is not None:
            said.per_device_traces += 1
        return ops.kernel_for_platform(
            functools.partial(ops.flash_attention, causal=causal, window=window),
            functools.partial(dense_attention, causal=causal, window=window),
            q, k, v,
        )

    return jax.jit(jax.shard_map(
        attention_per_device, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False,
    ))


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    mask: jax.Array | None = None,
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """`dot_product_attention`'s plain form, on every platform: the
    ``(sq, sk)`` scores materialized.  What the kernels are measured and
    checked against."""
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("...hqd,...hkd->...hqk", q * scale, k)
    sq, sk = logits.shape[-2], logits.shape[-1]
    visible = None
    if causal:
        visible = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
    if window is not None:
        # band over ABSOLUTE key positions; queries are the last sq of
        # the sk-long sequence (same alignment convention as causal)
        q_pos = jnp.arange(sq)[:, None] + (sk - sq)
        band = jnp.arange(sk)[None, :] > q_pos - window
        visible = band if visible is None else (visible & band)
    if mask is not None:
        m = jnp.broadcast_to(mask, logits.shape)
        visible = m if visible is None else (visible & m)
    if visible is not None:
        # -1e30 (not -inf) so a fully-masked row softmaxes to a uniform
        # garbage row we then zero explicitly, instead of NaN
        logits = jnp.where(visible, logits, -1e30)
    weights = jax.nn.softmax(logits, axis=-1)
    if visible is not None:
        weights = jnp.where(
            jnp.any(visible, axis=-1, keepdims=True), weights, 0.0
        )
    return jnp.einsum("...hqk,...hkd->...hqd", weights, v)


def rope(x: jax.Array, positions: jax.Array, *, base: float = 10000.0):
    """Rotary position embedding over ``(..., seq, head_dim)``.

    Rotates each (even, odd-half) feature pair by an angle proportional
    to the token's absolute position, so the q·k inner product depends
    only on RELATIVE distance (tested) — the modern long-context
    positional scheme (no learned table, extrapolates past training
    lengths).  ``positions``: ``(seq,)`` absolute indices (traced values
    fine, e.g. ``index + arange(s)`` during cached decode)."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"rope requires an even head_dim, got {d}")
    half = d // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * freqs  # (s, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return out.astype(x.dtype)


class MultiHeadAttention(Module):
    """Standard MHA block over (batch, seq, dim) inputs.

    ``kv_heads`` enables grouped-query attention (GQA): fewer key/value
    heads than query heads, each shared by ``heads // kv_heads`` query
    heads.  ``sliding_window=w`` restricts attention to the local band
    ``k > q - w`` in BOTH the parallel forward (the flash kernel skips
    out-of-band blocks) and cached decode.  The KV cache shrinks by the
    same factor — the reason GQA is the modern long-context inference
    layout (``kv_heads=1`` is
    multi-query attention).  With ``kv_heads == heads`` (default) the
    layer is exactly the classic fused-QKV MHA, param structure and all.
    ``use_bias=False`` drops the projections' biases; ``scale`` replaces
    the ``head_dim ** -0.5`` on the scores (an explicit attention
    multiplier).  ``head_dim`` is a head's size where it is not ``dim //
    heads`` (the projections are then ``heads * head_dim`` wide and only
    the output is ``dim``).  ``qk_norm=eps`` puts an RMSNorm over each
    head's query and key, one gain of ``head_dim`` values for all query
    heads and one for all key heads, before the rotation.  ``gated``
    multiplies the attention's output, before the output projection, by
    ``sigmoid(x @ W_g)`` of the layer's own input, elementwise.
    """

    def __init__(
        self,
        dim: int,
        heads: int,
        *,
        causal: bool = False,
        kv_heads: int | None = None,
        use_rope: bool = False,
        sliding_window: int | None = None,
        use_bias: bool = True,
        scale: float | None = None,
        head_dim: int | None = None,
        qk_norm: float | None = None,
        gated: bool = False,
    ):
        if head_dim is None and dim % heads:
            raise ValueError(f"dim {dim} not divisible by heads {heads}")
        self.dim = dim
        self.heads = heads
        self.head_dim = dim // heads if head_dim is None else head_dim
        self.inner = heads * self.head_dim   # what the heads hold together
        self.scale = self.head_dim**-0.5 if scale is None else scale
        self.causal = causal
        self.use_rope = use_rope
        if use_rope and self.head_dim % 2:
            raise ValueError(
                f"rope requires an even head_dim, got {self.head_dim}"
            )
        self.kv_heads = heads if kv_heads is None else kv_heads
        if self.kv_heads < 1 or heads % self.kv_heads:
            raise ValueError(
                f"heads {heads} not divisible by kv_heads {self.kv_heads}"
            )
        if sliding_window is not None and sliding_window < 1:
            raise ValueError(
                f"sliding_window must be >= 1, got {sliding_window}"
            )
        self.sliding_window = sliding_window
        self.group = heads // self.kv_heads
        if self.group == 1:
            self._qkv = Dense(3 * self.inner, use_bias=use_bias)
        else:
            self._q = Dense(self.inner, use_bias=use_bias)
            self._kv = Dense(2 * self.kv_heads * self.head_dim, use_bias=use_bias)
        self._out = Dense(dim, use_bias=use_bias)
        self._norm = None if qk_norm is None else RMSNorm(qk_norm)
        self._gate = Dense(self.inner, use_bias=False) if gated else None

    def init(self, key, input_shape):
        k1, k2, k3 = jax.random.split(key, 3)
        p = {"out": self._out.init(k3, input_shape[:-1] + (self.inner,))[0]}
        if self.group == 1:
            p["qkv"] = self._qkv.init(k1, input_shape)[0]
        else:
            p["q"] = self._q.init(k1, input_shape)[0]
            p["kv"] = self._kv.init(k2, input_shape)[0]
        if self._norm is not None:
            p["q_norm"] = {"scale": jnp.ones((self.head_dim,))}
            p["k_norm"] = {"scale": jnp.ones((self.head_dim,))}
        if self._gate is not None:
            p["gate"] = self._gate.init(jax.random.fold_in(key, 3), input_shape)[0]
        return p, {}

    def _project(self, params, x):
        """-> q (b, heads, s, hd), k/v (b, kv_heads, s, hd), q and k
        normed where the layer norms them."""
        b, s, _ = x.shape
        if self.group == 1:
            qkv, _ = self._qkv.apply(params["qkv"], {}, x)
            qkv = qkv.reshape(b, s, 3, self.heads, self.head_dim)
            q, k, v = (jnp.moveaxis(qkv[:, :, i], 1, 2) for i in range(3))
        else:
            q, _ = self._q.apply(params["q"], {}, x)
            q = jnp.moveaxis(q.reshape(b, s, self.heads, self.head_dim), 1, 2)
            kv, _ = self._kv.apply(params["kv"], {}, x)
            kv = kv.reshape(b, s, 2, self.kv_heads, self.head_dim)
            k, v = (jnp.moveaxis(kv[:, :, i], 1, 2) for i in range(2))
        if self._norm is not None:
            with jax.named_scope("attn/qk_norm"):
                q = self._norm.apply(params["q_norm"], {}, q)[0]
                k = self._norm.apply(params["k_norm"], {}, k)[0]
        return q, k, v

    def _output(self, params, x, o):
        """``o (b, heads, s, hd)`` -> the layer's output ``(b, s, dim)``:
        the heads side by side, the gate of the layer's input ``x`` where
        it has one, the output projection."""
        b, s, _ = x.shape
        o = jnp.moveaxis(o, 1, 2).reshape(b, s, self.inner)
        if self._gate is not None:
            with jax.named_scope("attn/gate"):
                g, _ = self._gate.apply(params["gate"], {}, x)
                o = (o * jax.nn.sigmoid(g.astype(jnp.float32))).astype(o.dtype)
        y, _ = self._out.apply(params["out"], {}, o)
        return y

    def _expand_kv(self, t, axis: int = 1):
        """Repeat each kv head (on ``axis``) across its query-head group
        (XLA folds the broadcast into the batched matmul; nothing
        materializes in HBM)."""
        if self.group == 1:
            return t
        return jnp.repeat(t, self.group, axis=axis)

    def apply(self, params, state, x, *, train=False, key=None, mask=None):
        """``mask``: optional boolean, either a key-padding mask
        ``(b, s)`` (True = real token; expanded to block attention TO
        pad keys) or a full ``(..., sq, sk)`` attention mask."""
        b, s, _ = x.shape
        q, k, v = self._project(params, x)
        if self.use_rope:
            pos = jnp.arange(s)
            q, k = rope(q, pos), rope(k, pos)
        if mask is not None and mask.ndim == 2:
            mask = mask[:, None, None, :]  # keys masked, all queries
        o = dot_product_attention(
            q, self._expand_kv(k), self._expand_kv(v),
            causal=self.causal, mask=mask, window=self.sliding_window,
            scale=self.scale,
        )
        return self._output(params, x, o), state

    def apply_cached(self, params, x, k_cache, v_cache, index):
        """Incremental (KV-cache) forward for autoregressive decode.

        ``x`` holds ``s`` NEW tokens whose global positions start at
        ``index`` (a traced scalar is fine); their keys/values are written
        into the static-shape caches ``(b, kv_heads, cache_len, head_dim)``
        with ``dynamic_update_slice`` and the queries attend over the
        whole cache under a position mask (``pos <= index + q_offset``) —
        static shapes throughout, so one compiled program serves every
        decode step.  Under GQA the cache carries only ``kv_heads`` heads
        (``heads / kv_heads``× less decode HBM traffic).  Returns
        ``(y, k_cache, v_cache)``.

        Only meaningful for causal self-attention (decode IS causal);
        raises otherwise to catch ViT-style misuse.
        """
        if not self.causal:
            raise ValueError("apply_cached requires causal=True attention")
        from jax import lax

        b, s, _ = x.shape
        q, k, v = self._project(params, x)
        if self.use_rope:
            # keys enter the cache already rotated (their rotation is a
            # pure function of their own absolute position)
            pos = index + jnp.arange(s)
            q, k = rope(q, pos), rope(k, pos)
        k_cache = lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), index, axis=2
        )
        v_cache = lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), index, axis=2
        )
        cache_len = k_cache.shape[2]
        logits = jnp.einsum(
            "bhqd,bhkd->bhqk",
            q * self.scale,
            self._expand_kv(k_cache).astype(q.dtype),
        )
        pos = jnp.arange(cache_len)[None, :]
        qpos = index + jnp.arange(s)[:, None]
        visible = pos <= qpos
        if self.sliding_window is not None:
            # same band as the parallel forward: k > q - window, so
            # windowed decode matches windowed training exactly
            visible = visible & (pos > qpos - self.sliding_window)
        logits = jnp.where(visible, logits, -1e30)
        weights = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum(
            "bhqk,bhkd->bhqd",
            weights,
            self._expand_kv(v_cache).astype(q.dtype),
        )
        return self._output(params, x, o), k_cache, v_cache


def sliding_window_mask(seq: int, window: int) -> jax.Array:
    """Boolean ``(seq, seq)`` mask where query i sees keys
    ``i-window+1 .. i`` (AND it with causal via dot_product_attention's
    ``causal=True``, or use alone for bidirectional local attention:
    |i-j| < window).  The Mistral-style local-attention pattern."""
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    i = jnp.arange(seq)[:, None]
    j = jnp.arange(seq)[None, :]
    return jnp.abs(i - j) < window


def segment_mask(segment_ids: jax.Array) -> jax.Array:
    """Block-diagonal mask for PACKED sequences: ``segment_ids`` is
    ``(b, s)`` ints labeling which document each token belongs to;
    returns ``(b, 1, s, s)`` boolean allowing attention only within the
    same segment.  Combine with ``causal=True`` so packed training
    matches per-document training (tested)."""
    same = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
    return same
