"""A decoder whose layers are of several kinds: each layer is a MIXER
chosen by ``layer_types[l]`` from the table `MIXERS` followed by a
feed-forward chosen by the layer's place: a dense gated one in the first
``dense_layers`` layers, after them a routed mixture of experts with one
shared expert.

    h0 = embedding_multiplier * E[token]
    h <- h + residual_multiplier * mixer_l(RMSNorm(h))
    h <- h + residual_multiplier * ff_l(RMSNorm(h))
    logits = RMSNorm(h_L) @ W_head^T / logits_scaling    (float32)

``W_head`` is the embedding table (``tied_head``) or a matrix of its own.
With ``sandwich_norms`` each sublayer's OUTPUT is normed too, four gains a
layer: ``h <- h + RMSNorm(mixer_l(RMSNorm(h)))`` and the same around the
feed-forward.

With ``shortcut = n`` (LongCat-Flash's shortcut-connected experts, ``n =
2``) ``layer_types`` lists SUBLAYERS, each ``mixer -> dense feed-forward``,
and the routed experts are a branch beside them: the first of every ``n``
sublayers LAUNCHES it from its own post-mixer norm (the dense
feed-forward's input), the last JOINS it, after its own feed-forward:

    a <- h + mixer_0(RMSNorm(h));  u = RMSNorm(a);  m = experts(u)
    h <- a + ff_0(u);  ... the sublayers between ...
    h <- h + mixer_{n-1}(RMSNorm(h));  h <- h + ff_{n-1}(RMSNorm(h)) + m

**The mixers** (`MIXERS`; each offers its weights, its dense form, what
it keeps between calls and its cached form to the ONE block function):

- ``"mamba"``, Mamba-2 (Dao & Gu 2024; one group): ``[z | xBC | dt] = x @
  in_proj``; ``xBC <- silu(causal depthwise conv(xBC))``; ``[xs | B | C] =
  xBC``; ``dt <- softplus(dt + dt_bias)``; the scan of
  `tpu_dist.ops.ssm_scan` with ``A = -exp(A_log)``; ``y <- RMSNorm(y *
  silu(z))`` over all inner channels; ``y @ out_proj``.  No biases but the
  convolution's.  Keeps a float32 ``{"conv", "ssm"}`` state a decode slot.
- ``"attention"``: bias-free grouped-query attention with no positions,
  scores scaled by ``attention_multiplier``, causal.  Keeps a ``{"k",
  "v"}`` pool of `serve.paged_kv`'s layout.
- ``"gated_attention"``, ``"gated_sliding_attention"``: grouped-query
  attention with a head size of its own, an RMSNorm over each head's query
  and key and an elementwise sigmoid gate of the layer's input on the
  output; the first with no positions, over the whole context, keeping a
  ``{"k", "v"}`` pool as ``"attention"`` does; the second with rope and a
  window, keeping no pool but a ``{"k", "v"}`` ring of ``window - 1 +
  chunk`` positions a decode slot (`serve.paged_kv.init_ring_cache`).
- ``"full_attention"``, ``"sliding_attention"``, ``"latent_attention"``:
  `nn.LatentAttention` (low-rank queries and keys/values, a rope part
  shared by the heads, a gate a head unless ``gated=False``), the first
  with the learned top-k selection of its keys, the second windowed, the
  third over every causal row; each with its own sizes (``mixers[kind]``).
  The first keeps a pool of latent rows and one of index keys under the
  engine's block tables, the third the pool of rows alone, which decode
  reads where it lies (`ops.paged_latent`); the second no pool but a ring
  of ``window - 1 + chunk`` positions a decode slot
  (`serve.paged_kv.init_latent_cache`).

**Experts**: `parallel.moe.routed_experts` (top-k over ``n_experts``
router outputs, the last ``zero_experts`` of them experts with no weights,
gates by ``expert_scoring``) over the ``held_experts`` this rank holds,
and, where ``shared_width`` is given, a `nn.GatedMLP` computed whole.

There is ONE block function (`_block`), which takes the mixer as a
callable: the dense `apply` (whole sequences, no cache: tests and
nothing in a hot path) and the serving protocol `apply_paged` hand it
the mixer of each layer's kind in its dense or its cached form.

**Serving** (`tpu_dist.serve.ServeEngine` asks a model for these and
knows nothing of a block's inside):

- ``init_serve_cache(max_batch, num_blocks, block_size, dtype)`` ->
  ``{"kv": [...], "state": ...}``: by layer what its mixer keeps, pools
  under ``kv``, what is indexed by decode slot under ``state["layers"]``.
  ``state`` also holds the running counts (`serve_counters`).
- ``apply_paged(params, tokens, cache, block_tables, positions,
  write_mask, slots, block_size)`` -> ``(logits, cache, counters)``.
  ``slots`` is each row's decode slot, or None where row ``i`` IS slot
  ``i`` (the decode step).  A row whose first token is real and at
  position 0 starts from a zero recurrent state; a masked token leaves
  every state as it was, so an inactive slot's is left alone.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from tpu_dist.models.init_span import InitSpan
from tpu_dist.nn.attention import MultiHeadAttention
from tpu_dist.nn.core import Module
from tpu_dist.nn.latent_attention import LatentAttention
from tpu_dist.nn.layers import GatedMLP, RMSNorm
from tpu_dist.ops.ssm_scan import causal_conv, ssd_chunked, ssm_step
from tpu_dist.parallel.moe import routed_experts


class Paged(NamedTuple):
    """What a cached mixer is told of the call beside its input."""
    block_tables: jax.Array
    positions: jax.Array
    write_mask: jax.Array
    slots: jax.Array | None
    block_size: int
    fresh: jax.Array     # (rows,): the row starts a request


def _normal(key, *shape):
    return jax.random.normal(key, shape) * 0.02


class MambaMixer:
    """``init(key)``; ``dense(p, x) -> y``; ``init_cache(...) -> (pools,
    per-slot state)``; ``cached(p, x, pools, state, at) -> (y, pools,
    state, counts)``, the counts in the order of ``counters``."""

    counters = ()

    def __init__(self, dim: int, norm: RMSNorm, *, heads: int, head_dim: int, state: int,
                 conv: int = 4, chunk: int = 256):
        self.dim, self.norm = dim, norm
        self.heads, self.head_dim, self.state = heads, head_dim, state
        self.inner = heads * head_dim
        self.channels = self.inner + 2 * state   # what the convolution sees
        self.conv, self.chunk = conv, chunk

    def init(self, key):
        """``A_log`` / ``dt_bias`` / ``D`` as Mamba-2 initialises them
        (``A`` uniform in [1, 16], ``dt`` log-uniform in [1e-3, 1e-1]
        through the inverse of softplus, ``D`` one)."""
        ks = jax.random.split(key, 5)
        nh, di, ch = self.heads, self.inner, self.channels
        dt = jnp.exp(jax.random.uniform(ks[3], (nh,)) * math.log(100.0) + math.log(1e-3))
        return {
            "in_proj": _normal(ks[0], self.dim, di + ch + nh),
            "conv_w": jax.random.normal(ks[1], (ch, self.conv)) * self.conv**-0.5,
            "conv_b": jnp.zeros((ch,)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(ks[4], (nh,), minval=1.0, maxval=16.0)),
            "D": jnp.ones((nh,)),
            "norm": {"scale": jnp.ones((di,))},
            "out_proj": _normal(ks[2], di, self.dim),
        }

    def mix(self, p, x, conv, ssm, mask):
        """Over ``x (rows, s, dim)`` from the carried ``conv`` window and
        ``ssm`` state -> ``(y, conv', ssm')``."""
        rows, s, _ = x.shape
        nh, hd, N, di = self.heads, self.head_dim, self.state, self.inner
        with jax.named_scope("ssm/in_proj"):
            zxbcdt = x @ p["in_proj"]
            z, xbc, dt = jnp.split(zxbcdt, [di, di + self.channels], axis=-1)
        with jax.named_scope("ssm/conv"):
            xbc, conv = causal_conv(xbc, p["conv_w"], p["conv_b"], conv, mask)
            xs, B, C = jnp.split(jax.nn.silu(xbc), [di, di + N], axis=-1)
            xs = xs.reshape(rows, s, nh, hd)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        with jax.named_scope("ssm/scan"):
            A = -jnp.exp(p["A_log"].astype(jnp.float32))
            if s == 1:   # decode: the recurrence itself
                y, ssm = ssm_step(xs[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], p["D"], ssm,
                                  None if mask is None else mask[:, 0])
            else:
                y, ssm = ssd_chunked(xs, dt, A, B, C, p["D"], ssm, mask, chunk=self.chunk)
        with jax.named_scope("ssm/gate_norm"):
            y = y.reshape(rows, s, di) * jax.nn.silu(z.astype(jnp.float32))
            y = self.norm.apply(p["norm"], {}, y)[0].astype(x.dtype)
        with jax.named_scope("ssm/out_proj"):
            return y @ p["out_proj"], conv, ssm

    def zero_state(self, rows: int):
        return (jnp.zeros((rows, self.conv - 1, self.channels), jnp.float32),
                jnp.zeros((rows, self.heads, self.head_dim, self.state), jnp.float32))

    def dense(self, p, x):
        return self.mix(p, x, *self.zero_state(x.shape[0]), None)[0]

    def init_cache(self, max_batch, num_blocks, block_size, dtype):
        return {}, dict(zip(("conv", "ssm"), self.zero_state(max_batch)))

    def cached(self, p, x, pools, state, at: Paged):
        with jax.named_scope("ssm/state_rw"):
            conv, ssm = ((state["conv"], state["ssm"]) if at.slots is None
                         else (state["conv"][at.slots], state["ssm"][at.slots]))
            conv = jnp.where(at.fresh[:, None, None], 0.0, conv)
            ssm = jnp.where(at.fresh[:, None, None, None], 0.0, ssm)
        y, conv, ssm = self.mix(p, x, conv, ssm, at.write_mask)
        with jax.named_scope("ssm/state_rw"):
            if at.slots is not None:
                conv = state["conv"].at[at.slots].set(conv)
                ssm = state["ssm"].at[at.slots].set(ssm)
        return y, {}, {"conv": conv, "ssm": ssm}, ()


class GroupedQueryMixer:
    counters = ()

    def __init__(self, dim: int, norm: RMSNorm, *, heads: int, kv_heads: int,
                 scale: float | None = None):
        del norm
        self.dim = dim
        self.attn = MultiHeadAttention(dim, heads, causal=True, kv_heads=kv_heads,
                                       use_bias=False, scale=scale)

    def init(self, key):
        return self.attn.init(key, (1, self.dim))[0]

    def dense(self, p, x):
        return self.attn.apply(p, {}, x)[0]

    def init_cache(self, max_batch, num_blocks, block_size, dtype):
        pool = (num_blocks + 1, block_size, self.attn.kv_heads * self.attn.head_dim)
        return {"k": jnp.zeros(pool, dtype), "v": jnp.zeros(pool, dtype)}, {}

    def cached(self, p, x, pools, state, at: Paged):
        from tpu_dist.serve.paged_kv import _paged_attention

        y, k, v = _paged_attention(self.attn, p, x, pools["k"], pools["v"], at.block_tables,
                                   at.positions, at.write_mask, at.block_size)
        return y, {"k": k, "v": v}, {}, ()


class GatedQueryMixer(GroupedQueryMixer):
    """Both gated grouped-query kinds: one over the whole context with no
    positions (a pool under the block tables) and one with a ``window``
    and rope (a ring a slot, which holds the window and the ``chunk`` new
    tokens a call may bring a row)."""

    def __init__(self, dim: int, norm: RMSNorm, *, heads: int, kv_heads: int, head_dim: int,
                 window: int | None = None, chunk: int = 1):
        self.dim, self.chunk = dim, chunk
        self.attn = MultiHeadAttention(
            dim, heads, causal=True, kv_heads=kv_heads, use_bias=False, head_dim=head_dim,
            qk_norm=norm.eps, gated=True, use_rope=window is not None, sliding_window=window)
        self.counters = (("attn_rows_attended",) if window is None
                         else ("swa_rows_attended", "swa_rows_unwindowed"))

    def init_cache(self, max_batch, num_blocks, block_size, dtype):
        from tpu_dist.serve.paged_kv import init_ring_cache

        if self.attn.sliding_window is None:
            return super().init_cache(max_batch, num_blocks, block_size, dtype)
        return {}, init_ring_cache(self.attn, max_batch, block_size, dtype, self.chunk)

    def cached(self, p, x, pools, state, at: Paged):
        from tpu_dist.serve.paged_kv import _paged_attention, ring_blocks, ring_tables

        W = self.attn.sliding_window
        held = jnp.where(at.write_mask, at.positions + 1, 0)   # places each real query sees
        if W is None:
            y, k, v = _paged_attention(self.attn, p, x, pools["k"], pools["v"], at.block_tables,
                                       at.positions, at.write_mask, at.block_size)
            return y, {"k": k, "v": v}, {}, (held.sum(dtype=jnp.int32),)
        if x.shape[1] > self.chunk:
            raise ValueError(f"a ring that holds a window of {W} and {self.chunk} new tokens "
                             f"a call, not {x.shape[1]}")
        rows, max_blocks = at.block_tables.shape
        tables = ring_tables(at.slots, rows, ring_blocks(W, self.chunk, at.block_size), max_blocks)
        y, k, v = _paged_attention(self.attn, p, x, state["k"], state["v"], tables, at.positions,
                                   at.write_mask, at.block_size, ("swa/ring_rw", "swa/attend"))
        return y, {}, {"k": k, "v": v}, (jnp.minimum(held, W).sum(dtype=jnp.int32),
                                         held.sum(dtype=jnp.int32))


class LatentMixer:
    """The latent kinds: one that selects its keys (``index_topk``; pools
    under the block tables), one with a ``window`` (a ring a slot, of
    ``window - 1 + chunk`` rows: ``chunk`` is the most new tokens a call
    may bring a row) and one with neither, over every row its slot holds
    (the pool of rows alone)."""

    def __init__(self, dim: int, norm: RMSNorm, *, chunk: int = 1, **sizes):
        self.attn = LatentAttention(dim, eps=norm.eps, **sizes)
        windowed = self.attn.window is not None
        self.ring_rows = self.attn.window - 1 + chunk if windowed else None
        self.counters = (("swa_rows_attended",) if windowed
                         else ("dsa_keys_scored", "dsa_rows_selected", "dsa_rows_read")
                         if self.attn.index_topk
                         else ("mla_rows_attended",))

    def init(self, key):
        return self.attn.init(key)[0]

    def dense(self, p, x):
        return self.attn.apply(p, {}, x)[0]

    def init_cache(self, max_batch, num_blocks, block_size, dtype):
        from tpu_dist.serve.paged_kv import init_latent_cache

        return init_latent_cache(self.attn, max_batch, num_blocks, block_size, dtype,
                                 self.ring_rows)

    def cached(self, p, x, pools, state, at: Paged):
        from tpu_dist.serve.paged_kv import (
            _paged_latent_attention,
            _ring_latent_attention,
            _whole_latent_attention,
        )

        if self.attn.window is None and not self.attn.index_topk:
            y, ckv, attended = _whole_latent_attention(
                self.attn, p, x, pools["ckv"], at.block_tables, at.positions, at.write_mask,
                at.block_size)
            return y, {"ckv": ckv}, {}, (attended,)
        if self.attn.window is None:
            y, pools, counts = _paged_latent_attention(
                self.attn, p, x, pools, at.block_tables, at.positions, at.write_mask,
                at.block_size)
            return y, pools, {}, counts
        y, ring, attended = _ring_latent_attention(
            self.attn, p, x, state["ring"], at.positions, at.write_mask, at.slots)
        return y, {}, {"ring": ring}, (attended,)


# layer kind -> the mixer that computes it
MIXERS = {
    "mamba": MambaMixer, "attention": GroupedQueryMixer,
    "gated_attention": GatedQueryMixer, "gated_sliding_attention": GatedQueryMixer,
    "full_attention": LatentMixer, "sliding_attention": LatentMixer,
    "latent_attention": LatentMixer,
}


class HybridLM(InitSpan, Module):
    """``mixers``: by layer kind, the sizes its `MIXERS` entry is built
    with.  The ``ssm_*`` and ``heads`` / ``kv_heads`` /
    ``attention_multiplier`` arguments are the sizes of ``"mamba"`` and
    ``"attention"`` under the names their first caller gives them
    (`chipbench/families/granitemoehybrid.py`); they go when that file
    passes ``mixers=``."""

    def __init__(
        self,
        *,
        vocab: int,
        dim: int,
        layer_types: list[str],
        heads: int | None = None,
        kv_heads: int | None = None,
        ssm_heads: int | None = None,
        ssm_head_dim: int | None = None,
        ssm_state: int | None = None,
        ssm_conv: int = 4,
        ssm_chunk: int = 256,
        mixers: dict[str, dict] | None = None,
        n_experts: int,
        experts_per_token: int,
        expert_width: int,
        shared_width: int | None = None,
        held_experts: tuple[int, int] | None = None,
        expert_scoring: str = "softmax_of_picks",
        route_scale: float = 1.0,
        zero_experts: int = 0,
        shortcut: int = 0,
        dense_layers: int = 0,
        dense_width: int | None = None,
        tied_head: bool = True,
        embedding_multiplier: float = 1.0,
        residual_multiplier: float = 1.0,
        attention_multiplier: float | None = None,
        logits_scaling: float = 1.0,
        sandwich_norms: bool = False,
        norm_eps: float = 1e-5,
        max_seq: int = 2048,
    ):
        unknown = sorted(set(layer_types) - set(MIXERS))
        if unknown:
            raise ValueError(f"layer_types of {unknown}; the kinds are {sorted(MIXERS)}")
        self.vocab, self.dim, self.max_seq = vocab, dim, max_seq
        self.layer_types = list(layer_types)
        self.norm = RMSNorm(norm_eps)
        sizes = {
            "mamba": dict(heads=ssm_heads, head_dim=ssm_head_dim, state=ssm_state,
                          conv=ssm_conv, chunk=ssm_chunk),
            "attention": dict(heads=heads, kv_heads=kv_heads, scale=attention_multiplier),
            **(mixers or {}),
        }
        self.mixers = {kind: MIXERS[kind](dim, self.norm, **sizes[kind])
                       for kind in dict.fromkeys(self.layer_types)}
        if shortcut and (len(self.layer_types) % shortcut or dense_layers):
            raise ValueError(f"a routed branch spans {shortcut} sublayers, each with a dense "
                             f"feed-forward: not {len(self.layer_types)} of them, nor "
                             f"dense_layers {dense_layers}")
        self.n_experts, self.experts_per_token = n_experts, experts_per_token
        self.expert_width, self.zero_experts, self.shortcut = expert_width, zero_experts, shortcut
        self.held_experts = (tuple(held_experts) if held_experts
                             else (0, n_experts - zero_experts))
        self.expert_scoring, self.route_scale = expert_scoring, route_scale
        self.sandwich_norms = sandwich_norms
        self.dense_layers, self.tied_head = dense_layers, tied_head
        self.embedding_multiplier = embedding_multiplier
        self.residual_multiplier = residual_multiplier
        self.logits_scaling = logits_scaling
        self.shared = GatedMLP(shared_width) if shared_width else None
        self.mlp = GatedMLP(dense_width) if dense_layers or shortcut else None
        lo, hi = self.held_experts
        # what `apply_paged`'s counters count, position by position:
        # (name, label key, label values) -> tpu_dist_serve_<name>_total
        own = [name for mixer in self.mixers.values() for name in mixer.counters]
        self.serve_counters = (
            ("moe_picks", None, ()),
            ("moe_picks_held", None, ()),
            ("moe_expert_tokens", "expert", tuple(range(lo, hi))),
            ("moe_experts_hit", None, ()),
            *([("moe_picks_zero", None, ())] if zero_experts else []),
            *((name, None, ()) for name in own),
        )
        routed = 3 + hi - lo + bool(zero_experts)
        self._count_at = {name: routed + i for i, name in enumerate(own)}
        self._counts = routed + len(own)

    # ------------------------------------------------------------ weights

    def init(self, key=None, input_shape=None):
        """Seeded weights: normal(0, 0.02) matrices (grouped-query
        attention's as `MultiHeadAttention` draws them), unit norms, zero
        selection bias, each mixer's own as its ``init`` says.  Under
        ``shortcut`` every sublayer has a dense feed-forward and the first
        of each span the experts beside it."""
        del input_shape
        key = jax.random.key(0) if key is None else key
        D, E = self.dim, self.n_experts
        H = self.held_experts[1] - self.held_experts[0]
        ones = lambda d: {"scale": jnp.ones((d,))}  # noqa: E731

        def block(at, kind, k):
            ks = jax.random.split(k, 6)
            p = {"ln1": ones(D), "mixer": self.mixers[kind].init(ks[0]), "ln2": ones(D)}
            if self.sandwich_norms:
                p.update(ln1_out=ones(D), ln2_out=ones(D))
            if at < self.dense_layers or self.shortcut:
                k_in, k_out = (jax.random.split(jax.random.fold_in(k, 1)) if self.shortcut
                               else (ks[1], ks[2]))   # beside experts: keys of its own
                p["mlp"] = {"w_in": _normal(k_in, D, 2 * self.mlp.width),
                            "w_out": _normal(k_out, self.mlp.width, D)}
                if not self.shortcut or at % self.shortcut:
                    return p
            p["moe"] = {"router": _normal(ks[1], D, E),
                        "w_in": _normal(ks[2], H, D, 2 * self.expert_width),
                        "w_out": _normal(ks[3], H, self.expert_width, D)}
            if self.expert_scoring != "softmax_of_picks":
                p["moe"]["bias"] = jnp.zeros((E,))
            if self.shared is not None:
                p["shared"] = {"w_in": _normal(ks[4], D, 2 * self.shared.width),
                               "w_out": _normal(ks[5], self.shared.width, D)}
            return p

        k_emb, k_head, *k_blocks = jax.random.split(key, len(self.layer_types) + 2)
        params = {
            "embed": {"table": _normal(k_emb, self.vocab, D)},
            "blocks": [block(at, kind, k)
                       for at, (kind, k) in enumerate(zip(self.layer_types, k_blocks))],
            "ln": ones(D),
        }
        if not self.tied_head:
            params["head"] = {"table": _normal(k_head, self.vocab, D)}
        return params, {}

    # -------------------------------------------------------- the layers

    def _ln(self, p, x):
        with jax.named_scope("ln"):
            return self.norm.apply(p, {}, x)[0]

    def _experts(self, p, u, mask):
        """Routed experts held here plus the shared expert, if there is
        one, over ``u (rows, s, dim)`` -> ``(y, counts (3 + held,))``:
        picks, picks held, tokens a held expert, held experts given a token
        (those whose weights the grouped product has to read), and with
        ``zero_experts`` the picks that cost nothing."""
        flat = u.reshape(-1, u.shape[-1])
        y, c = routed_experts(
            flat, p["moe"]["router"], p["moe"]["w_in"], p["moe"]["w_out"],
            top_k=self.experts_per_token, held=self.held_experts,
            mask=None if mask is None else mask.reshape(-1),
            scoring=self.expert_scoring, bias=p["moe"].get("bias"), scale=self.route_scale,
            zero_experts=self.zero_experts,
        )
        if self.shared is not None:
            with jax.named_scope("moe/shared"):
                y = y + self.shared.apply(p["shared"], {}, flat)[0]
        hit = (c["expert_tokens"] > 0).sum(dtype=jnp.int32)
        counts = jnp.concatenate([jnp.stack([c["picks"], c["picks_held"]]), c["expert_tokens"],
                                  hit[None], *([c["picks_zero"][None]] if self.zero_experts else [])])
        return y.reshape(u.shape), counts

    def _feed_forward(self, p, u, mask):
        """The sublayer's own, ``-> (f, counts, launched)``: the experts
        where it has no dense weights; else the dense one (``counts``
        None), and beside it, where the sublayer has experts too, the
        routed branch it launches from the same ``u``."""
        if "mlp" not in p:
            return *self._experts(p, u, mask), None
        launched, counts = self._experts(p, u, mask) if "moe" in p else (None, None)
        with jax.named_scope("mlp"):
            return self.mlp.apply(p["mlp"], {}, u)[0], counts, launched

    def _block(self, p, h, mixer, mask, shortcut=None, join=False):
        """One layer (under ``shortcut`` one sublayer), whatever its kind:
        ``mixer(params, x) -> (y, kept)`` is the layer's mixer in its dense
        or its cached form, ``kept`` what it keeps for the next call.  Both
        residual forms: a norm before each sublayer, and with
        ``sandwich_norms`` one after it.  ``shortcut``: the routed branch an
        earlier sublayer launched and none has joined yet; this one hands
        it on, or its own, or, where it is to ``join``, adds it to the
        stream after its own feed-forward and hands on nothing."""
        y, kept = mixer(p["mixer"], self._ln(p["ln1"], h))
        if self.sandwich_norms:
            y = self._ln(p["ln1_out"], y)
        h = h + self.residual_multiplier * y.astype(h.dtype)
        f, counts, launched = self._feed_forward(p, self._ln(p["ln2"], h), mask)
        if self.sandwich_norms:
            f = self._ln(p["ln2_out"], f)
        h = h + self.residual_multiplier * f
        shortcut = shortcut if launched is None else launched
        if join and shortcut is not None:
            with jax.named_scope("moe/join"):
                h, shortcut = h + self.residual_multiplier * shortcut, None
        return h, kept, counts, shortcut

    def _joins(self, at: int) -> bool:
        """Whether sublayer ``at`` ends a span of ``shortcut`` sublayers."""
        return bool(self.shortcut) and at % self.shortcut == self.shortcut - 1

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return self.embedding_multiplier * params["embed"]["table"][tokens]

    def _head(self, params, h):
        with jax.named_scope("lm_head"):
            h = self.norm.apply(params["ln"], {}, h)[0]
            table = params["embed" if self.tied_head else "head"]["table"]
            logits = jnp.einsum("...d,vd->...v", h, table, preferred_element_type=jnp.float32)
            return logits / self.logits_scaling

    # ------------------------------------------------------------- dense

    def apply(self, params, state, tokens, *, train=False, key=None):
        """``tokens (batch, seq)`` -> logits ``(batch, seq, vocab)`` in
        float32: every sequence whole, from a zero state, no cache."""
        del train, key
        h, shortcut = self._embed(params, tokens), None
        for at, (kind, p) in enumerate(zip(self.layer_types, params["blocks"])):
            dense = self.mixers[kind].dense
            h, _, _, shortcut = self._block(p, h, lambda pm, x, f=dense: (f(pm, x), None), None,
                                            shortcut, self._joins(at))
        return self._head(params, h), state

    # ----------------------------------------------------------- serving

    def init_serve_cache(self, max_batch: int, num_blocks: int, block_size: int, dtype=None):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        kept = [self.mixers[kind].init_cache(max_batch, num_blocks, block_size,
                                             dtype or jnp.float32)
                for kind in self.layer_types]
        counts = jnp.zeros((self._counts,), jnp.int32)
        return {"kv": [pools for pools, _ in kept],
                "state": {"layers": [state for _, state in kept], "counts": counts}}

    def apply_paged(self, params, tokens, cache, block_tables, positions, write_mask, slots,
                    block_size: int):
        L = block_tables.shape[1] * block_size
        positions = jnp.clip(positions, 0, L - 1)
        h = self._embed(params, tokens)
        fresh = write_mask[:, 0] & (positions[:, 0] == 0)
        at = Paged(block_tables, positions, write_mask, slots, block_size, fresh)
        kv, state, shortcut = [], [], None
        counts = cache["state"]["counts"]
        for at_, (kind, p, ckv, cst) in enumerate(zip(
                self.layer_types, params["blocks"], cache["kv"], cache["state"]["layers"])):
            mixer = self.mixers[kind]

            def cached(pm, x, m=mixer, c=ckv, s=cst):
                y, pools, kept, own = m.cached(pm, x, c, s, at)
                return y, (pools, kept, own)

            h, (k_new, s_new, own), c, shortcut = self._block(
                p, h, cached, write_mask, shortcut, self._joins(at_))
            kv.append(k_new)
            state.append(s_new)
            if c is not None:   # the experts' counts lead the vector
                counts = counts.at[: c.size].add(c)
            for name, n in zip(mixer.counters, own):
                counts = counts.at[self._count_at[name]].add(n)
        cache = {"kv": kv, "state": {"layers": state, "counts": counts}}
        return self._head(params, h), cache, counts
