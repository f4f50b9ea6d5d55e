"""Latent attention (MLA, DeepSeek-V2) with an optional learned top-k
selection of the keys (the DeepSeek-V3.2 lightning indexer) or a window.

Queries and keys/values go through low-rank latents; what a token leaves
behind for later queries is ONE row shared by all heads, ``[c_kv | k_r]``
of ``kv_rank + rope_dim`` values: the normed key/value latent and a
rotated key part.  With ``x`` a row of the layer's normed input:

    c_q = sqrt(dim / q_rank) * RMSNorm(x W_dq)
    [q_n | q_r]_h = c_q W_uq                         heads of nope_dim + rope_dim
    [c | k_r] = x W_dkv;  c_kv = sqrt(dim / kv_rank) * RMSNorm(c)
    q_r, k_r <- rope(., t)                           one k_r for all heads
    [k_n | v]_h = c_kv W_ukv                         heads of nope_dim + v_dim
    s_h(t, j) = (q_n,h(t) . k_n,h(j) + q_r,h(t) . k_r(j)) / sqrt(nope_dim + rope_dim)
    o_h = sum_j softmax_j(s_h)(t, j) v_h(j) over the visible j
    y = [sigmoid(x W_g)_h * o_h]_h W_o               a gate a head, no biases
                                                     (``gated=False``: no W_g, y = [o_h]_h W_o)

`expanded` computes exactly that from the rows (it builds ``k_n`` and
``v`` of every row: right for whole sequences and for chunks of queries
over a short span).  `absorbed` is the same mathematics with ``W_uk``
folded into the query and ``W_uv`` applied after the weighted sum, so the
rows are attended as they lie, ``kv_rank + rope_dim`` wide: ``q'_h =
W_uk,h^T q_n,h``, ``s_h = (q'_h . c_kv(j) + q_r,h . k_r(j)) * scale``,
``o_h = W_uv,h (sum_j p_h(j) c_kv(j))``: right for one query a slot and
wherever the rows outnumber the queries.

The visible set of query ``t`` is the causal ``j <= t``, cut to a window
(``window``: ``t - window < j``) or to the indexer's picks
(``index_topk``): ``q^I_i = c_q W_iq`` (``index_heads`` of ``index_dim``,
the first ``rope_dim`` values rotated), ``k^I = LayerNorm(x W_ik)`` (the
same rotation; the second thing a token leaves behind), ``w = x W_iw /
sqrt(index_heads)``, ``I(t, j) = sum_i w_i(t) relu(q^I_i(t) . k^I(j)) /
sqrt(index_dim)`` in float32; visible are the ``index_topk`` causal ``j``
of largest ``I(t, j)``, ties to the lower ``j`` (`top_visible`:
`lax.top_k`'s own order, found by `ops.kth_score` without its sort).

``W_ukv`` is kept as its two parts by head, ``w_uk (H, nope_dim, kv_rank)``
and ``w_uv (H, kv_rank, v_dim)``, the operands of the absorbed form's two
batched products as they stand (kept as one ``(kv_rank, H * (nope_dim +
v_dim))`` matrix, every decode step sliced and transposed it: 42 MB a layer);
the two matrices that multiply ``c_q``, ``w_uq`` and ``index_wq``, are kept
outputs by latent, ``(features, q_rank)``, the way the TPU's compiler lays
them out for a product of a few rows (kept the other way it copied each,
transposed, every step: 50 MB a layer).

Rope is in this library's half-split layout (`nn.attention.rope`), with
one position a token of every row (``positions (b, s)``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from tpu_dist.nn.core import Module
from tpu_dist.nn.layers import RMSNorm
from tpu_dist.ops import SCORE_BYTES
from tpu_dist.ops.kth_score import kth_and_last

F32 = jnp.float32
INDEX_EPS = 1e-6   # of the LayerNorm over the indexer's key


def rope_rows(x, positions, base: float):
    """`nn.attention.rope` over ``x (b, s, ..., d)`` with each token's own
    position ``positions (b, s)``."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32).reshape(positions.shape + (1,) * (x.ndim - 2)) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _halve_until(n: int, fits) -> int:
    """The largest ``n / 2^k`` (a whole number) that ``fits``, else the
    smallest reached."""
    while not fits(n) and n % 2 == 0:
        n //= 2
    return n


def top_visible(scores, causal, k: int):
    """Of the ``causal`` places of each row of ``scores (..., L)`` the
    ``k`` of largest score, ties to the lower index (the picks of
    `lax.top_k`, as a mask); all of them where there are no more than
    ``k``.  The set is stated by its k-th value and the last place that
    ties with it and is picked, which `ops.kth_score.kth_and_last` finds
    without sorting."""
    L = scores.shape[-1]
    if k >= L:
        return causal
    s = jnp.where(causal, scores, -jnp.inf)
    kth, last = (found.reshape(s.shape[:-1] + (1,))
                 for found in kth_and_last(s.reshape(-1, L), k))
    return causal & ((s > kth) | ((s == kth) & (jnp.arange(L) <= last)))


class LatentAttention(Module):
    def __init__(self, dim: int, heads: int, *, q_rank: int, kv_rank: int, nope_dim: int,
                 rope_dim: int, v_dim: int, rope_base: float, window: int | None = None,
                 index_heads: int = 0, index_dim: int = 0, index_topk: int = 0,
                 gated: bool = True, eps: float = 1e-5):
        if window is not None and index_topk:
            raise ValueError("a layer is windowed or selects its keys, not both")
        if rope_dim % 2 or (index_topk and index_dim < rope_dim):
            raise ValueError(f"rope_dim {rope_dim}: even, and no wider than index_dim")
        self.dim, self.heads = dim, heads
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.rope_base, self.window = rope_base, window
        self.index_heads, self.index_dim, self.index_topk = index_heads, index_dim, index_topk
        self.gated = gated
        self.row = kv_rank + rope_dim          # what a token leaves behind
        self.scale = (nope_dim + rope_dim) ** -0.5
        self.q_gain, self.kv_gain = math.sqrt(dim / q_rank), math.sqrt(dim / kv_rank)
        self.norm = RMSNorm(eps)

    def init(self, key, input_shape=None):
        del input_shape
        D, H = self.dim, self.heads
        shapes = {
            "w_dq": (D, self.q_rank), "w_uq": (H * (self.nope_dim + self.rope_dim), self.q_rank),
            "w_dkv": (D, self.row), "w_uk": (H, self.nope_dim, self.kv_rank),
            "w_uv": (H, self.kv_rank, self.v_dim),
            "w_gate": (D, H), "w_out": (H * self.v_dim, D),
        }
        if not self.gated:
            del shapes["w_gate"]
        if self.index_topk:
            shapes.update(index_wq=(self.index_heads * self.index_dim, self.q_rank),
                          index_wk=(D, self.index_dim), index_ww=(D, self.index_heads))
        keys = jax.random.split(key, len(shapes))
        p = {name: jax.random.normal(k, shape) * 0.02
             for k, (name, shape) in zip(keys, shapes.items())}
        p["q_norm"] = {"scale": jnp.ones((self.q_rank,))}
        p["kv_norm"] = {"scale": jnp.ones((self.kv_rank,))}
        if self.index_topk:
            p["index_norm"] = {"scale": jnp.ones((self.index_dim,)),
                               "bias": jnp.zeros((self.index_dim,))}
        return p, {}

    # ------------------------------------------------------ projections

    def queries(self, p, x, positions):
        """-> ``(c_q (b, s, q_rank), q_n (b, s, H, nope), q_r (b, s, H, rope))``."""
        with jax.named_scope("mla/q"):
            c_q = self.norm.apply(p["q_norm"], {}, x @ p["w_dq"])[0] * self.q_gain
            q = jnp.einsum("bsq,nq->bsn", c_q, p["w_uq"]).reshape(*x.shape[:2], self.heads, -1)
            q_n, q_r = q[..., :self.nope_dim], q[..., self.nope_dim:]
            return c_q, q_n, rope_rows(q_r, positions, self.rope_base)

    def rows(self, p, x, positions):
        """What the tokens leave behind: ``[c_kv | k_r] (b, s, row)``."""
        with jax.named_scope("mla/kv"):
            ck = x @ p["w_dkv"]
            c = self.norm.apply(p["kv_norm"], {}, ck[..., :self.kv_rank])[0] * self.kv_gain
            k_r = rope_rows(ck[..., self.kv_rank:], positions, self.rope_base)
            return jnp.concatenate([c, k_r], axis=-1)

    def _index_rope(self, t, positions):
        r = self.rope_dim
        return jnp.concatenate(
            [rope_rows(t[..., :r], positions, self.rope_base), t[..., r:]], axis=-1)

    def index_keys(self, p, x, positions):
        """``k^I (b, s, index_dim)``: the indexer's key of each token."""
        with jax.named_scope("dsa/index"):
            k = (x @ p["index_wk"]).astype(F32)
            mean = k.mean(-1, keepdims=True)
            k = (k - mean) * lax.rsqrt(((k - mean) ** 2).mean(-1, keepdims=True) + INDEX_EPS)
            k = k * p["index_norm"]["scale"].astype(F32) + p["index_norm"]["bias"].astype(F32)
            return self._index_rope(k.astype(x.dtype), positions)

    def index_queries(self, p, x, c_q, positions):
        """-> ``(q^I (b, s, index_heads, index_dim), w (b, s, index_heads)
        float32)``, the weights with both scalings in them."""
        with jax.named_scope("dsa/index"):
            q = jnp.einsum("bsq,nq->bsn", c_q, p["index_wq"]).reshape(
                *x.shape[:2], self.index_heads, self.index_dim)
            w = jnp.einsum("bsd,dh->bsh", x, p["index_ww"], preferred_element_type=F32)
            return (self._index_rope(q, positions),
                    w * (self.index_heads ** -0.5 * self.index_dim ** -0.5))

    def index_scores(self, q_i, w, keys, held=None):
        """``I (b, s, L)`` in float32 of queries ``q_i, w`` against ``keys
        (b, L, index_dim)``.  Where the per-head scores of all the keys at
        once would be too many, the keys are walked in parts, and only as
        far as ``held`` (a traced count: no key at or past it can be seen;
        default all): the scores of the parts not walked are zero."""
        with jax.named_scope("dsa/index"):
            b, s, h, _ = q_i.shape
            L = keys.shape[1]

            def part(k):
                per_head = jnp.einsum("bshd,bkd->bshk", q_i, k, preferred_element_type=F32)
                # a sum of float32 products, not a matrix product: the MXU
                # would round both to bfloat16 first
                return (jax.nn.relu(per_head) * w[..., None]).sum(axis=2)

            kb = _halve_until(L, lambda n: 4 * b * s * h * n <= SCORE_BYTES)
            if kb == L:
                return part(keys)

            def walk(j, scores):
                k = lax.dynamic_slice_in_dim(keys, j * kb, kb, axis=1)
                return lax.dynamic_update_slice_in_dim(scores, part(k), j * kb, axis=2)

            parts = L // kb if held is None else (held + kb - 1) // kb
            return lax.fori_loop(0, parts, walk, jnp.zeros((b, s, L), F32))

    # ------------------------------------------------- the two attentions

    def expanded(self, p, q_n, q_r, rows, visible):
        """``o (b, s, H, v_dim)`` of queries against ``rows (b, L, row)``
        with ``k_n`` and ``v`` of every row built; ``visible (b, s, L)``."""
        c, k_r = rows[..., :self.kv_rank], rows[..., self.kv_rank:]
        k_n = jnp.einsum("blr,hdr->blhd", c, p["w_uk"])
        v = jnp.einsum("blr,hrd->blhd", c, p["w_uv"])
        logits = (jnp.einsum("bshd,blhd->bhsl", q_n, k_n, preferred_element_type=F32)
                  + jnp.einsum("bshd,bld->bhsl", q_r, k_r, preferred_element_type=F32))
        logits = jnp.where(visible[:, None], logits * self.scale, -1e30)
        weights = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("bhsl,blhd->bshd", weights, v)

    def absorbed(self, p, q_n, q_r, rows, visible, held=None):
        """The same ``o`` with the rows attended as they lie: ``W_uk``
        folded into the queries, ``W_uv`` applied to the weighted sum of
        latents.  Where the scores of all rows at once would be too many
        (a chunk of queries over a whole table's view), the rows are
        walked in blocks under a running maximum, and only as far as
        ``held`` (a traced count: no row at or past it is visible; default
        all), so a chunk costs what its context holds, not what a table
        could."""
        b, s, H, _ = q_n.shape
        L, r = rows.shape[1], self.kv_rank
        q_cat = jnp.concatenate([jnp.einsum("bshd,hdr->bshr", q_n, p["w_uk"]), q_r], axis=-1)

        def logits_of(block, seen):
            logits = jnp.einsum("bshc,blc->bhsl", q_cat, block, preferred_element_type=F32)
            return jnp.where(seen[:, None], logits * self.scale, -1e30)

        kb = _halve_until(L, lambda n: 4 * b * H * s * n <= SCORE_BYTES)
        if kb == L:
            weights = jax.nn.softmax(logits_of(rows, visible), axis=-1).astype(rows.dtype)
            o_c = jnp.einsum("bhsl,blr->bshr", weights, rows[..., :r])
            return jnp.einsum("bshr,hrd->bshd", o_c, p["w_uv"])

        def walk(j, carry):
            top, total, acc = carry
            block = lax.dynamic_slice_in_dim(rows, j * kb, kb, axis=1)
            seen = lax.dynamic_slice_in_dim(visible, j * kb, kb, axis=2)
            logits = logits_of(block, seen)
            new_top = jnp.maximum(top, logits.max(axis=-1))
            e = jnp.where(seen[:, None], jnp.exp(logits - new_top[..., None]), 0.0)
            keep = jnp.exp(top - new_top)
            acc = acc * keep[..., None] + jnp.einsum(
                "bhsl,blr->bhsr", e.astype(rows.dtype), block[..., :r],
                preferred_element_type=F32)
            return new_top, total * keep + e.sum(axis=-1), acc

        blocks = L // kb if held is None else (held + kb - 1) // kb
        start = (jnp.full((b, H, s), -1e30, F32), jnp.zeros((b, H, s), F32),
                 jnp.zeros((b, H, s, r), F32))
        _, total, acc = lax.fori_loop(0, blocks, walk, start)
        # a query that sees nothing (a pad) gets zeros, not 0 / 0
        o_c = (acc / jnp.maximum(total, 1e-30)[..., None]).astype(rows.dtype)
        return jnp.einsum("bhsr,hrd->bshd", o_c, p["w_uv"])

    def output(self, p, x, o):
        """The gate a head from the layer's input (a gated layer), then ``W_o``."""
        with jax.named_scope("mla/out"):
            if not self.gated:
                return o.astype(x.dtype).reshape(*x.shape[:2], -1) @ p["w_out"]
            g = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", x, p["w_gate"],
                                          preferred_element_type=F32))
            o = (o * g[..., None]).astype(x.dtype)
            return o.reshape(*x.shape[:2], -1) @ p["w_out"]

    # ------------------------------------------------------------- dense

    def visible(self, p, x, c_q, positions, key_positions, keys=None):
        """``(b, s, L)``: which of the keys at ``key_positions (b, L)``
        each query at ``positions (b, s)`` sees.  ``keys``: the indexer's
        keys of those places (a layer that selects)."""
        t, j = positions[:, :, None], key_positions[:, None, :]
        causal = (j <= t) & (j >= 0)
        if self.window is not None:
            return causal & (j > t - self.window)
        if not self.index_topk:
            return causal
        q_i, w = self.index_queries(p, x, c_q, positions)
        scores = self.index_scores(q_i, w, keys)
        with jax.named_scope("dsa/topk"):
            return top_visible(scores, causal, self.index_topk)

    def apply(self, params, state, x, *, train=False, key=None):
        """Whole sequences ``x (b, s, dim)``, no cache: the expanded form."""
        del train, key
        b, s, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        c_q, q_n, q_r = self.queries(params, x, pos)
        rows = self.rows(params, x, pos)
        keys = self.index_keys(params, x, pos) if self.index_topk else None
        visible = self.visible(params, x, c_q, pos, pos, keys)
        with jax.named_scope("mla/attend"):
            o = self.expanded(params, q_n, q_r, rows, visible)
        return self.output(params, x, o), state
