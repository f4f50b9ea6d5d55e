"""A decoder whose layers are of several kinds: each layer is a MIXER
chosen by ``layer_types[l]`` (a Mamba-2 state-space mixer, or grouped-query
attention with no positional encoding) followed by a routed
mixture-of-experts feed-forward with one shared expert.

    h0 = embedding_multiplier * E[token]
    h <- h + residual_multiplier * mixer_l(RMSNorm(h))
    h <- h + residual_multiplier * (routed(u) + shared(u)),  u = RMSNorm(h)
    logits = RMSNorm(h_L) @ E^T / logits_scaling            (tied table)

**Mamba-2 mixer** (Dao & Gu 2024; one group): ``[z | xBC | dt] = x @
in_proj``; ``xBC <- silu(causal depthwise conv(xBC))``; ``[xs | B | C] =
xBC``; ``dt <- softplus(dt + dt_bias)``; the scan of
`tpu_dist.ops.ssm_scan` with ``A = -exp(A_log)``; ``y <- RMSNorm(y *
silu(z))`` over all inner channels; ``y @ out_proj``.  No biases but the
convolution's.  **Attention**: bias-free projections, scores scaled by
``attention_multiplier``, causal.  **Experts**: `parallel.moe.routed_experts`
(top-k over ``n_experts`` router outputs, gates the softmax of the picked
logits) over the ``held_experts`` this rank holds, and a `nn.GatedMLP` of
``shared_width`` computed whole.

There is ONE block function (`_block`), which takes the mixer as a
callable: the dense `apply` (whole sequences, no cache: tests and
nothing in a hot path) and the serving protocol `apply_paged` hand it
the mixer of each layer's kind in its dense or its cached form.

**Serving** (`tpu_dist.serve.ServeEngine` asks a model for these and
knows nothing of a block's inside):

- ``init_serve_cache(max_batch, num_blocks, block_size, dtype)`` ->
  ``{"kv": [...], "state": ...}``: by layer a ``{"k", "v"}`` pool of
  `serve.paged_kv`'s layout for an attention layer; for a Mamba layer a
  float32 ``{"conv": (max_batch, K-1, channels), "ssm": (max_batch, heads,
  head_dim, d_state)}`` indexed by decode slot.  ``state`` also holds the
  running expert-load counts (`serve_counters`).
- ``apply_paged(params, tokens, cache, block_tables, positions,
  write_mask, slots, block_size)`` -> ``(logits, cache, counters)``.
  ``slots`` is each row's decode slot, or None where row ``i`` IS slot
  ``i`` (the decode step).  A row whose first token is real and at
  position 0 starts from a zero state; a masked token leaves the state
  as it was, so an inactive slot's state is left alone.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from tpu_dist.nn.attention import MultiHeadAttention
from tpu_dist.nn.core import Module
from tpu_dist.nn.layers import GatedMLP, RMSNorm
from tpu_dist.ops.ssm_scan import causal_conv, ssd_chunked, ssm_step
from tpu_dist.parallel.moe import routed_experts

MIXERS = ("mamba", "attention")


class HybridLM(Module):
    def __init__(
        self,
        *,
        vocab: int,
        dim: int,
        layer_types: list[str],
        heads: int,
        kv_heads: int,
        ssm_heads: int,
        ssm_head_dim: int,
        ssm_state: int,
        ssm_conv: int = 4,
        ssm_chunk: int = 256,
        n_experts: int,
        experts_per_token: int,
        expert_width: int,
        shared_width: int,
        held_experts: tuple[int, int] | None = None,
        embedding_multiplier: float = 1.0,
        residual_multiplier: float = 1.0,
        attention_multiplier: float | None = None,
        logits_scaling: float = 1.0,
        norm_eps: float = 1e-5,
        max_seq: int = 2048,
    ):
        unknown = set(layer_types) - set(MIXERS)
        if unknown:
            raise ValueError(f"layer_types of {sorted(unknown)}; known: {MIXERS}")
        self.vocab, self.dim, self.max_seq = vocab, dim, max_seq
        self.layer_types = list(layer_types)
        self.ssm_heads, self.ssm_head_dim, self.ssm_state = ssm_heads, ssm_head_dim, ssm_state
        self.ssm_inner = ssm_heads * ssm_head_dim
        self.ssm_channels = self.ssm_inner + 2 * ssm_state   # what the convolution sees
        self.ssm_conv, self.ssm_chunk = ssm_conv, ssm_chunk
        self.n_experts, self.experts_per_token = n_experts, experts_per_token
        self.expert_width = expert_width
        self.held_experts = tuple(held_experts) if held_experts else (0, n_experts)
        self.embedding_multiplier = embedding_multiplier
        self.residual_multiplier = residual_multiplier
        self.logits_scaling = logits_scaling
        self.norm = RMSNorm(norm_eps)
        self.attn = MultiHeadAttention(
            dim, heads, causal=True, kv_heads=kv_heads, use_bias=False,
            scale=attention_multiplier,
        )
        self.shared = GatedMLP(shared_width)
        lo, hi = self.held_experts
        # what `apply_paged`'s counters count, position by position:
        # (name, label key, label values) -> tpu_dist_serve_<name>_total
        self.serve_counters = (
            ("moe_picks", None, ()),
            ("moe_picks_held", None, ()),
            ("moe_expert_tokens", "expert", tuple(range(lo, hi))),
        )

    # ------------------------------------------------------------ weights

    def init(self, key=None, input_shape=None):
        """Seeded weights: normal(0, 0.02) matrices (attention's as
        `MultiHeadAttention` draws them), unit norms, and the
        scan's ``A_log`` / ``dt_bias`` / ``D`` as Mamba-2 initialises
        them (``A`` uniform in [1, 16], ``dt`` log-uniform in [1e-3,
        1e-1] through the inverse of softplus, ``D`` one)."""
        del input_shape
        key = jax.random.key(0) if key is None else key
        D, E = self.dim, self.n_experts
        H = self.held_experts[1] - self.held_experts[0]
        n = lambda k, *shape: jax.random.normal(k, shape) * 0.02  # noqa: E731
        ones = lambda d: {"scale": jnp.ones((d,))}  # noqa: E731

        def mixer(kind, k):
            if kind == "attention":
                return self.attn.init(k, (1, D))[0]
            ks = jax.random.split(k, 5)
            nh, di, ch = self.ssm_heads, self.ssm_inner, self.ssm_channels
            dt = jnp.exp(jax.random.uniform(ks[3], (nh,)) * math.log(100.0) + math.log(1e-3))
            return {
                "in_proj": n(ks[0], D, di + ch + nh),
                "conv_w": jax.random.normal(ks[1], (ch, self.ssm_conv)) * self.ssm_conv**-0.5,
                "conv_b": jnp.zeros((ch,)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(ks[4], (nh,), minval=1.0, maxval=16.0)),
                "D": jnp.ones((nh,)),
                "norm": ones(di),
                "out_proj": n(ks[2], di, D),
            }

        def block(kind, k):
            ks = jax.random.split(k, 6)
            return {
                "ln1": ones(D), "mixer": mixer(kind, ks[0]), "ln2": ones(D),
                "moe": {"router": n(ks[1], D, E),
                        "w_in": n(ks[2], H, D, 2 * self.expert_width),
                        "w_out": n(ks[3], H, self.expert_width, D)},
                "shared": {"w_in": n(ks[4], D, 2 * self.shared.width),
                           "w_out": n(ks[5], self.shared.width, D)},
            }

        k_emb, *k_blocks = jax.random.split(key, len(self.layer_types) + 1)
        return {
            "embed": {"table": n(k_emb, self.vocab, D)},
            "blocks": [block(kind, k) for kind, k in zip(self.layer_types, k_blocks)],
            "ln": ones(D),
        }, {}

    # -------------------------------------------------------- the layers

    def _ln(self, p, x):
        with jax.named_scope("ln"):
            return self.norm.apply(p, {}, x)[0]

    def _mamba(self, p, x, conv, ssm, mask):
        """The Mamba-2 mixer over ``x (rows, s, dim)`` from the carried
        ``conv`` window and ``ssm`` state -> ``(y, conv', ssm')``."""
        rows, s, _ = x.shape
        nh, hd, N, di = self.ssm_heads, self.ssm_head_dim, self.ssm_state, self.ssm_inner
        with jax.named_scope("ssm/in_proj"):
            zxbcdt = x @ p["in_proj"]
            z, xbc, dt = jnp.split(zxbcdt, [di, di + self.ssm_channels], axis=-1)
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
                y, ssm = ssd_chunked(xs, dt, A, B, C, p["D"], ssm, mask, chunk=self.ssm_chunk)
        with jax.named_scope("ssm/gate_norm"):
            y = y.reshape(rows, s, di) * jax.nn.silu(z.astype(jnp.float32))
            y = self.norm.apply(p["norm"], {}, y)[0].astype(x.dtype)
        with jax.named_scope("ssm/out_proj"):
            return y @ p["out_proj"], conv, ssm

    def _zero_state(self, rows: int):
        return (jnp.zeros((rows, self.ssm_conv - 1, self.ssm_channels), jnp.float32),
                jnp.zeros((rows, self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                          jnp.float32))

    def _experts(self, p, u, mask):
        """Routed experts held here plus the shared expert, over ``u
        (rows, s, dim)`` -> ``(y, counts (2 + held,))``."""
        flat = u.reshape(-1, u.shape[-1])
        y, c = routed_experts(
            flat, p["moe"]["router"], p["moe"]["w_in"], p["moe"]["w_out"],
            top_k=self.experts_per_token, held=self.held_experts,
            mask=None if mask is None else mask.reshape(-1),
        )
        with jax.named_scope("moe/shared"):
            y = y + self.shared.apply(p["shared"], {}, flat)[0]
        counts = jnp.concatenate([jnp.stack([c["picks"], c["picks_held"]]), c["expert_tokens"]])
        return y.reshape(u.shape), counts

    def _block(self, p, h, mixer, mask):
        """One layer, whatever its kind: ``mixer(params, x) -> (y, kept)``
        is the layer's mixer in its dense or its cached form, ``kept``
        what it keeps for the next call."""
        y, kept = mixer(p["mixer"], self._ln(p["ln1"], h))
        h = h + self.residual_multiplier * y.astype(h.dtype)
        f, counts = self._experts(p, self._ln(p["ln2"], h), mask)
        return h + self.residual_multiplier * f, kept, counts

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return self.embedding_multiplier * params["embed"]["table"][tokens]

    def _head(self, params, h):
        with jax.named_scope("lm_head"):
            h = self.norm.apply(params["ln"], {}, h)[0]
            logits = jnp.einsum("...d,vd->...v", h, params["embed"]["table"],
                                preferred_element_type=jnp.float32)
            return logits / self.logits_scaling

    # ------------------------------------------------------------- dense

    def apply(self, params, state, tokens, *, train=False, key=None):
        """``tokens (batch, seq)`` -> logits ``(batch, seq, vocab)`` in
        float32: every sequence whole, from a zero state, no cache."""
        del train, key
        h = self._embed(params, tokens)
        dense = {
            "attention": lambda p, x: (self.attn.apply(p, {}, x)[0], None),
            "mamba": lambda p, x: (self._mamba(p, x, *self._zero_state(x.shape[0]), None)[0], None),
        }
        for kind, p in zip(self.layer_types, params["blocks"]):
            h, _, _ = self._block(p, h, dense[kind], None)
        return self._head(params, h), state

    # ----------------------------------------------------------- serving

    def init_serve_cache(self, max_batch: int, num_blocks: int, block_size: int, dtype=None):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        pool = (num_blocks + 1, block_size, self.attn.kv_heads * self.attn.head_dim)
        dt = dtype or jnp.float32
        kv, state = [], []
        for kind in self.layer_types:
            attends = kind == "attention"
            kv.append({"k": jnp.zeros(pool, dt), "v": jnp.zeros(pool, dt)} if attends else {})
            state.append({} if attends else dict(zip(("conv", "ssm"), self._zero_state(max_batch))))
        held = self.held_experts[1] - self.held_experts[0]
        return {"kv": kv, "state": {"layers": state, "counts": jnp.zeros((2 + held,), jnp.int32)}}

    def apply_paged(self, params, tokens, cache, block_tables, positions, write_mask, slots,
                    block_size: int):
        from tpu_dist.serve.paged_kv import _paged_attention

        L = block_tables.shape[1] * block_size
        positions = jnp.clip(positions, 0, L - 1)
        h = self._embed(params, tokens)
        fresh = write_mask[:, 0] & (positions[:, 0] == 0)
        kv, state = [], []
        counts = cache["state"]["counts"]
        for kind, p, ckv, cst in zip(self.layer_types, params["blocks"], cache["kv"],
                                     cache["state"]["layers"]):
            if kind == "attention":
                def mixer(pm, x, c=ckv):
                    y, k, v = _paged_attention(self.attn, pm, x, c["k"], c["v"], block_tables,
                                               positions, write_mask, block_size)
                    return y, ({"k": k, "v": v}, {})
            else:
                def mixer(pm, x, c=cst):
                    with jax.named_scope("ssm/state_rw"):
                        conv, ssm = ((c["conv"], c["ssm"]) if slots is None
                                     else (c["conv"][slots], c["ssm"][slots]))
                        conv = jnp.where(fresh[:, None, None], 0.0, conv)
                        ssm = jnp.where(fresh[:, None, None, None], 0.0, ssm)
                    y, conv, ssm = self._mamba(pm, x, conv, ssm, write_mask)
                    with jax.named_scope("ssm/state_rw"):
                        if slots is not None:
                            conv = c["conv"].at[slots].set(conv)
                            ssm = c["ssm"].at[slots].set(ssm)
                    return y, ({}, {"conv": conv, "ssm": ssm})
            h, (k_new, s_new), c = self._block(p, h, mixer, write_mask)
            kv.append(k_new)
            state.append(s_new)
            counts = counts + c
        cache = {"kv": kv, "state": {"layers": state, "counts": counts}}
        return self._head(params, h), cache, counts
