"""Decoder-only transformer LM — the long-context flagship.

No reference analog (the 2017 tutorial has no sequence models,
SURVEY.md §2d) — this family exists because long-context/sequence
parallelism is first-class in this framework: the same parameter pytree
runs either dense (`TransformerLM.apply`) or sequence-parallel
(`TransformerLM.apply_seq_parallel` inside shard_map, attention cores
swapped for `tpu_dist.parallel.ring_attention`), and tests assert the two
agree numerically.  Token embedding, learned positions, pre-norm blocks,
weight-tied output head.

Inference is first-class too: `generate` runs KV-cache autoregressive
decode (prefill + `lax.scan` over single-token steps against a
static-shape cache — one compiled program end to end), with greedy,
temperature, and top-k sampling; `tests/test_generate.py` asserts the
cached path reproduces the dense forward exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpu_dist import nn
from tpu_dist.ops import partitioning
from tpu_dist.nn.core import Module
from tpu_dist.models.init_span import InitSpan
from tpu_dist.models.vit import EncoderBlock


def _make_sampler(temperature, top_k, top_p, dtype):
    """The decode sampling rule, shared by `TransformerLM.generate` and
    `generate_tensor_parallel`: greedy at ``temperature=0``, otherwise
    tempered softmax optionally truncated to the ``top_k`` highest logits
    and/or the ``top_p`` nucleus.  Deterministic given the key, so every
    model-parallel rank sampling replicated logits with the same key
    picks the same token."""
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")

    def sample(logits, k):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(dtype)
        logits = logits / temperature
        if top_k is not None:
            kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
            logits = jnp.where(logits < kth, -1e30, logits)
        if top_p is not None:
            # nucleus: drop tokens in the tail beyond cumulative
            # probability top_p (the highest-probability token always
            # survives: its exclusive-cumsum is 0 < top_p)
            sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1) - probs  # exclusive
            cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True) - 1
            cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
            logits = jnp.where(logits < cutoff, -1e30, logits)
        return jax.random.categorical(k, logits).astype(dtype)

    return sample


class TransformerLM(InitSpan, Module):
    def __init__(
        self,
        *,
        vocab: int = 256,
        dim: int = 128,
        depth: int = 4,
        heads: int = 4,
        max_seq: int = 1024,
        kv_heads: int | None = None,
        pos_embedding: str = "learned",
        remat: bool = False,
        moe_experts: int = 0,
        moe_capacity_factor: float = 2.0,
        moe_balance_weight: float = 0.01,
        sliding_window: int | None = None,
    ):
        if pos_embedding not in ("learned", "rope"):
            raise ValueError(
                f"pos_embedding must be 'learned' or 'rope', got "
                f"{pos_embedding!r}"
            )
        if moe_experts < 0 or moe_experts == 1:
            # top-2 routing needs at least two experts; a single-expert
            # "mixture" would otherwise surface as an obscure trace-time
            # top_k(k=2) crash deep in the MoE paths.
            raise ValueError(
                f"moe_experts must be 0 (dense MLP) or >= 2 (top-2 "
                f"routing), got {moe_experts}"
            )
        # moe_experts > 0 swaps every block's dense MLP for a top-2
        # (GShard-style) mixture of experts: per block a router
        # ``gate (d, E)`` plus expert-stacked ``up (E, d, 4d)`` /
        # ``down (E, 4d, d)`` weights replace the ``mlp`` subtree.  The
        # dense paths (`apply`, cached decode) evaluate every expert and
        # combine the top-2 (exact, no capacity bound); `loss_moe_ep`
        # trains with real expert parallelism (all_to_all dispatch over
        # a mesh axis, `parallel.moe_mlp_top2`).
        self.moe_experts = moe_experts
        self.moe_capacity_factor = moe_capacity_factor
        self.moe_balance_weight = moe_balance_weight
        # sliding_window=w: every block attends only the local band
        # (q-w, q] — Mistral-style long-context attention; flows through
        # dense forward, cached decode/generate, and the windowed flash
        # kernels.
        self.sliding_window = sliding_window
        # Rematerialize each block's forward during backward
        # (jax.checkpoint): activation HBM drops from O(depth · B·S·d)
        # to O(B·S·d) + one extra forward of FLOPs — the standard TPU
        # memory/compute trade for long sequences or big batches.
        self.remat = remat
        self.vocab = vocab
        self.dim = dim
        self.heads = heads
        self.kv_heads = heads if kv_heads is None else kv_heads
        self.max_seq = max_seq
        self.pos_embedding = pos_embedding
        self.embed = nn.Embedding(vocab, dim)
        self.blocks = [
            EncoderBlock(
                dim, heads, causal=True, kv_heads=kv_heads,
                use_rope=pos_embedding == "rope",
                sliding_window=sliding_window,
            )
            for _ in range(depth)
        ]
        self.ln = nn.LayerNorm()

    def init(self, key, input_shape=None):
        del input_shape
        ks = jax.random.split(key, len(self.blocks) + 3)
        tok_shape = (self.max_seq, self.dim)
        params = {
            "embed": self.embed.init(ks[0], ())[0],
            "blocks": [
                blk.init(k, tok_shape)[0] for blk, k in zip(self.blocks, ks[2:])
            ],
            "ln": self.ln.init(ks[-1], tok_shape)[0],
        }
        if self.moe_experts:
            E, d, hdim = self.moe_experts, self.dim, 4 * self.dim
            for pb, k in zip(params["blocks"], ks[2:]):
                kg, ku, kd = jax.random.split(jax.random.fold_in(k, 7), 3)
                del pb["mlp"]
                pb["moe"] = {
                    "gate": jax.random.normal(kg, (d, E)) * 0.02,
                    "up": jax.random.normal(ku, (E, d, hdim)) / jnp.sqrt(d),
                    "down": jax.random.normal(kd, (E, hdim, d))
                    / jnp.sqrt(hdim),
                }
        if self.pos_embedding == "learned":
            params["pos"] = (
                jax.random.normal(ks[1], (1, self.max_seq, self.dim)) * 0.02
            )
        return params, {}

    def _require_no_window(self, method: str) -> None:
        """Context-parallel decode does not carry the sliding-window
        band yet (its prompt-phase ring + LSE merge assume the full
        causal mask) — raise loudly instead of silently decoding wrong
        (same precedent as the rope/kv_heads guards).  Windowed
        elsewhere: dense + TP decode, and every training strategy
        except the flash-block ring (which has its own guard)."""
        if self.sliding_window is not None:
            raise ValueError(
                f"{method} does not support sliding_window yet — "
                "context-parallel decode computes the full causal mask; "
                "use dense generate() or generate_tensor_parallel()"
            )

    def _moe_dense(self, pm, x):
        """Exact dense evaluation of the top-2 MoE over ``(..., d)``
        activations: every expert computes every token, the router's
        top-2 (renormalized, GShard-style) combine selects — no capacity
        bound, so this is the drop-free reference the EP path
        (`loss_moe_ep` with ample capacity) matches to fp tolerance."""
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        scores = x2 @ pm["gate"]  # (T, E)
        probs = jax.nn.softmax(scores, axis=-1)
        top2_p, top2_e = jax.lax.top_k(probs, 2)
        gates = top2_p / jnp.maximum(top2_p.sum(-1, keepdims=True), 1e-9)
        hidden = jax.nn.gelu(jnp.einsum("td,edh->eth", x2, pm["up"]))
        y_all = jnp.einsum("eth,ehd->etd", hidden, pm["down"])  # (E, T, d)
        t_idx = jnp.arange(x2.shape[0])
        y = (
            gates[:, 0, None] * y_all[top2_e[:, 0], t_idx]
            + gates[:, 1, None] * y_all[top2_e[:, 1], t_idx]
        )
        return y.reshape(*lead, x.shape[-1])

    def _mlp_or_moe(self, blk, pb, x):
        """The feed-forward half of a block: dense MLP, or the dense
        (every-expert) MoE evaluation for ``moe_experts > 0`` models."""
        if self.moe_experts:
            return self._moe_dense(pb["moe"], x)
        return blk.mlp.apply(pb["mlp"], {}, x)[0]

    def _trunk(self, params, tokens, *, pos_offset=0):
        b, s = tokens.shape
        with jax.named_scope("embed"):
            h = params["embed"]["table"][tokens]
            if self.pos_embedding == "learned":
                h = h + jax.lax.dynamic_slice_in_dim(
                    params["pos"], pos_offset, s, axis=1
                )
        # rope: positions enter inside attention (q/k rotation), not here
        return h

    def apply(self, params, state, tokens, *, train=False, key=None,
              attn_mask=None):
        """Dense forward: (batch, seq) int tokens -> (batch, seq, vocab)
        logits (weight-tied head).

        ``attn_mask``: optional boolean — a key-padding mask ``(b, s)``
        (True = real token) or a full ``(..., s, s)`` mask; combined
        with the causal mask in every block (use for padded or packed
        batches)."""
        h = self._trunk(params, tokens)
        # Where XLA partitions the program it is free to move the
        # residual stream between the batch's layout and the one the
        # fsdp rule gives the weights' features, around every norm and
        # projection: 46 `all-to-all` in two layers of gpt2-xl's widths
        # compiled for four v5e chips, and with it pinned after each
        # block the embedding's two (PERF.md section 6, PR 36)
        said = partitioning.partitioned()
        for blk, pb in zip(self.blocks, params["blocks"]):
            def block_fn(pb_, h_, blk=blk):
                if not self.moe_experts:
                    return blk.apply(pb_, {}, h_, train=train,
                                     mask=attn_mask)[0]
                with jax.named_scope("block/attn"):
                    x1, _ = blk.ln1.apply(pb_["ln1"], {}, h_)
                    o, _ = blk.attn.apply(
                        pb_["attn"], {}, x1, mask=attn_mask
                    )
                    h_ = h_ + o
                with jax.named_scope("block/mlp"):
                    x2, _ = blk.ln2.apply(pb_["ln2"], {}, h_)
                    return h_ + self._mlp_or_moe(blk, pb_, x2)

            if self.remat:
                h = jax.checkpoint(block_fn)(pb, h)
            else:
                h = block_fn(pb, h)
            if said is not None:
                h = said.pin_to_batch(h)
        with jax.named_scope("lm_head"):
            h, _ = self.ln.apply(params["ln"], {}, h)
            logits = h @ params["embed"]["table"].T
        return logits, state

    # ---- autoregressive inference (KV cache) ----------------------------

    def init_cache(self, batch: int, cache_len: int | None = None, dtype=None):
        """Static-shape KV cache: one ``{"k", "v"}`` pair per block, each
        ``(batch, kv_heads, cache_len, head_dim)`` (GQA models cache only
        their kv heads).  Allocated once and updated in place
        (``dynamic_update_slice``) so every decode step reuses one
        compiled program."""
        L = cache_len or self.max_seq
        hd = self.dim // self.heads
        dt = dtype or jnp.float32
        z = jnp.zeros((batch, self.kv_heads, L, hd), dt)
        return [{"k": z, "v": z} for _ in self.blocks]

    def apply_cached(self, params, tokens, cache, index):
        """Forward ``tokens`` (``(b, s)`` new tokens at global positions
        ``index..index+s-1``) against/into the KV cache.  Same math as
        `apply` restricted to the new positions — `tests/test_generate.py`
        asserts prefill logits match the dense forward.  Returns
        ``(logits (b, s, vocab), new_cache)``."""
        h = self._trunk(params, tokens, pos_offset=index)
        new_cache = []
        for blk, pb, c in zip(self.blocks, params["blocks"], cache):
            x1, _ = blk.ln1.apply(pb["ln1"], {}, h)
            o, ck, cv = blk.attn.apply_cached(
                pb["attn"], x1, c["k"], c["v"], index
            )
            h = h + o
            x2, _ = blk.ln2.apply(pb["ln2"], {}, h)
            h = h + self._mlp_or_moe(blk, pb, x2)
            new_cache.append({"k": ck, "v": cv})
        h, _ = self.ln.apply(params["ln"], {}, h)
        logits = h @ params["embed"]["table"].T
        return logits, new_cache

    # ---- serving (the model-side protocol `serve.ServeEngine` asks) ------

    # this model's serving programs count nothing themselves
    serve_counters = ()

    def init_serve_cache(self, max_batch: int, num_blocks: int,
                         block_size: int, dtype=None):
        """What `ServeEngine` keeps on the device for this model: paged
        pools of keys and values under ``"kv"`` (`serve.paged_kv`) and no
        per-slot state."""
        from tpu_dist.serve.paged_kv import init_paged_cache

        del max_batch
        return {"kv": init_paged_cache(self, num_blocks, block_size, dtype),
                "state": {}}

    def apply_paged(self, params, tokens, cache, block_tables, positions,
                    write_mask, slots, block_size: int):
        """`serve.paged_kv.paged_apply_cached` under the engine's
        protocol: ``-> (logits, cache, counters)``, here no counters."""
        from tpu_dist.serve.paged_kv import paged_apply_cached

        del slots
        logits, kv = paged_apply_cached(
            self, params, tokens, cache["kv"], block_tables, positions,
            write_mask, block_size,
        )
        return logits, {"kv": kv, "state": cache["state"]}, None

    def generate(
        self,
        params,
        prompt,
        steps: int,
        *,
        key=None,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        cache_len: int | None = None,
        stop_token: int | None = None,
        sampler=None,
    ):
        """Sample ``steps`` tokens after ``prompt`` ``(b, s_prompt)``.

        TPU-native decode: one multi-token prefill, then a ``lax.scan``
        over single-token steps against the static KV cache — the whole
        call is one compiled program (jit-compatible; ``steps``,
        ``temperature``, ``top_k``, ``top_p`` are static).
        ``temperature=0`` is greedy argmax; otherwise softmax sampling at
        the given temperature, optionally truncated to the ``top_k``
        highest-logit tokens and/or the nucleus of smallest-probability
        mass ``top_p`` (both cut the tail; tokens surviving both are
        renormalized by the categorical draw).  Returns ``(b, steps)``
        sampled tokens.

        ``stop_token``: EOS semantics under static shapes — a stream that
        emits it keeps emitting it for the remaining steps (frozen), so
        callers can trim on the first occurrence; shapes and compiled
        programs are unchanged.

        ``sampler``: optional ``(logits, key) -> tokens`` override used
        in place of the static sampling config — the hook through which
        `serve.sampling.generate_runtime` threads TRACED
        temperature/top_k/top_p (one compiled program for every
        sampling configuration); the static kwargs are then ignored.
        """
        from jax import lax

        b, s_p = prompt.shape
        L = cache_len or self.max_seq
        if s_p + steps > L:
            raise ValueError(
                f"prompt {s_p} + steps {steps} exceeds cache length {L}"
            )
        if key is None:
            key = jax.random.key(0)
        sample = (
            sampler
            if sampler is not None
            else _make_sampler(temperature, top_k, top_p, prompt.dtype)
        )

        cache = self.init_cache(b, L, dtype=params["embed"]["table"].dtype)
        logits, cache = self.apply_cached(params, prompt, cache, 0)
        last = logits[:, -1]
        done0 = jnp.zeros((b,), bool)

        def body(carry, k):
            cache, last, idx, done = carry
            tok = sample(last, k)
            if stop_token is not None:
                tok = jnp.where(done, jnp.asarray(stop_token, tok.dtype), tok)
                done = done | (tok == stop_token)
            logits, cache = self.apply_cached(params, tok[:, None], cache, idx)
            return (cache, logits[:, 0], idx + 1, done), tok

        keys = jax.random.split(key, steps)
        _, toks = lax.scan(body, (cache, last, jnp.int32(s_p), done0), keys)
        return jnp.moveaxis(toks, 0, 1)

    def generate_beam(
        self,
        params,
        prompt,
        steps: int,
        *,
        beams: int = 4,
        cache_len: int | None = None,
        return_all: bool = False,
    ):
        """Beam-search decode: keep the ``beams`` highest-total-log-prob
        continuations at every step (deterministic; the search analog of
        `generate`'s sampling).  One prefill on the un-tiled prompt, the
        cache tiled ``beams``-fold, then a ``lax.scan`` whose carry
        re-gathers the KV cache and token history under each step's
        surviving beam indices — still one compiled program.

        No EOS semantics (byte/markov corpora here have none): all beams
        run exactly ``steps`` tokens, so the total log-prob comparison
        needs no length normalization.  Returns the best beam's tokens
        ``(b, steps)`` — or, with ``return_all``, ``(tokens (b, beams,
        steps), scores (b, beams))`` sorted best-first.  ``beams=1``
        reproduces greedy `generate` exactly (tested).
        """
        from jax import lax

        if beams < 1:
            raise ValueError(f"beams must be >= 1, got {beams}")
        b, s_p = prompt.shape
        L = cache_len or self.max_seq
        if s_p + steps > L:
            raise ValueError(
                f"prompt {s_p} + steps {steps} exceeds cache length {L}"
            )
        k = beams
        cache = self.init_cache(b, L, dtype=params["embed"]["table"].dtype)
        logits, cache = self.apply_cached(params, prompt, cache, 0)
        # tile the cache beam-fold: rows [b0 x k, b1 x k, ...]
        cache = jax.tree.map(lambda c: jnp.repeat(c, k, axis=0), cache)
        last = jnp.repeat(logits[:, -1], k, axis=0)  # (b*k, V)
        V = last.shape[-1]
        # beam 0 live, the rest -inf: step 0 picks k distinct tokens from
        # beam 0 instead of k copies of the same argmax
        scores0 = jnp.tile(
            jnp.concatenate(
                [jnp.zeros((1,)), jnp.full((k - 1,), -1e30)]
            )[None, :],
            (b, 1),
        )
        toks0 = jnp.zeros((b, k, steps), prompt.dtype)
        batch_base = (jnp.arange(b)[:, None] * k)  # (b, 1)

        def body(carry, t):
            cache, last, scores, toks = carry
            logp = jax.nn.log_softmax(
                last.astype(jnp.float32), axis=-1
            ).reshape(b, k, V)
            total = scores[:, :, None] + logp  # (b, k, V)
            top_scores, top_idx = lax.top_k(total.reshape(b, k * V), k)
            beam_idx = top_idx // V  # (b, k) surviving parent beams
            tok = (top_idx % V).astype(prompt.dtype)  # (b, k)
            flat = (batch_base + beam_idx).reshape(-1)  # (b*k,)
            cache = jax.tree.map(lambda c: c[flat], cache)
            toks = jnp.take_along_axis(
                toks, beam_idx[:, :, None], axis=1
            )
            toks = lax.dynamic_update_slice_in_dim(
                toks, tok[:, :, None], t, axis=2
            )
            logits, cache = self.apply_cached(
                params, tok.reshape(b * k, 1), cache, s_p + t
            )
            return (cache, logits[:, 0], top_scores, toks), None

        (cache, last, scores, toks), _ = lax.scan(
            body, (cache, last, scores0, toks0), jnp.arange(steps)
        )
        order = jnp.argsort(-scores, axis=1)
        toks = jnp.take_along_axis(toks, order[:, :, None], axis=1)
        scores = jnp.take_along_axis(scores, order, axis=1)
        if return_all:
            return toks, scores
        return toks[:, 0]

    def apply_tensor_parallel(self, params, tokens, axis_name):
        """Tensor-parallel forward for use INSIDE shard_map over a
        ``model`` axis: attention heads and MLP hidden dims shard across
        ranks (Megatron layout, two psums per block —
        `tpu_dist.parallel.tp_encoder_block`); embeddings, LayerNorms and
        the tied vocab head stay replicated.  Same replicated params as
        `apply`; tests assert fp-tolerance agreement."""
        from tpu_dist.parallel.tensor_parallel import tp_encoder_block

        if self.pos_embedding != "learned":
            raise ValueError(
                "apply_tensor_parallel supports learned positions only "
                "(tp_attention does not apply rope)"
            )
        h = self._trunk(params, tokens)
        for blk, pb in zip(self.blocks, params["blocks"]):
            h = tp_encoder_block(blk, pb, h, axis_name)
        h, _ = self.ln.apply(params["ln"], {}, h)
        return h @ params["embed"]["table"].T

    def loss_tensor_parallel(self, params, tokens, axis_name):
        """Next-token loss with the whole model tensor-parallel INCLUDING
        the output head: blocks via `tp_encoder_block`, cross-entropy via
        `parallel.tp_vocab_cross_entropy` — the full `(b, s, vocab)`
        logits tensor is never materialized on any rank.  Equals
        `lm_loss(apply(...))` (tested).

        Gradient contract (tested): each rank's ``jax.grad`` of this
        loss is its shard's CONTRIBUTION; ``pmean`` over the model axis
        recovers the dense gradient exactly — i.e. treat the model axis
        like a data axis in the gradient average and the training step
        needs no other change."""
        from tpu_dist.parallel.tensor_parallel import (
            tp_encoder_block,
            tp_vocab_cross_entropy,
        )

        if self.pos_embedding != "learned":
            raise ValueError(
                "loss_tensor_parallel supports learned positions only "
                "(tp_attention does not apply rope)"
            )
        h = self._trunk(params, tokens)
        for blk, pb in zip(self.blocks, params["blocks"]):
            h = tp_encoder_block(blk, pb, h, axis_name)
        h, _ = self.ln.apply(params["ln"], {}, h)
        return tp_vocab_cross_entropy(
            h[:, :-1], params["embed"]["table"], tokens[:, 1:], axis_name
        )

    def apply_tensor_parallel_sp(self, params, tokens_local, axis_name):
        """Megatron-SP tensor-parallel forward for use INSIDE shard_map:
        ``tokens_local`` is this rank's SEQUENCE shard (rank-major global
        order), activations stay sequence-sharded between sublayers (1/n
        of `apply_tensor_parallel`'s activation memory), and every
        all-gather/reduce-scatter is a collective matmul
        (`parallel.tp_encoder_block_sp` — the overlap the reference names
        as the per-parameter-loop vs real-DDP gap, tuto.md:319-320,
        applied at layer granularity).  Heads and MLP hidden dims shard
        over ``axis_name`` exactly like `apply_tensor_parallel`.  Returns
        this rank's LOCAL logits ``(b, s_local, vocab)``; gathering them
        over the axis reproduces the dense `apply` (tested)."""
        from jax import lax

        from tpu_dist.parallel.overlap import tp_encoder_block_sp

        if self.pos_embedding != "learned":
            raise ValueError(
                "apply_tensor_parallel_sp supports learned positions only "
                "(tp_attention_overlapped does not apply rope)"
            )
        if self.kv_heads != self.heads:
            raise ValueError(
                "apply_tensor_parallel_sp requires kv_heads == heads "
                "(fused-QKV layout)"
            )
        b, s_local = tokens_local.shape
        n = lax.axis_size(axis_name)
        if n * s_local > self.max_seq:
            raise ValueError(
                f"global sequence {n} ranks x {s_local} tokens = "
                f"{n * s_local} exceeds max_seq {self.max_seq}"
            )
        r = lax.axis_index(axis_name)
        h = self._trunk(params, tokens_local, pos_offset=r * s_local)
        for blk, pb in zip(self.blocks, params["blocks"]):
            h = tp_encoder_block_sp(blk, pb, h, axis_name)
        h, _ = self.ln.apply(params["ln"], {}, h)
        return h @ params["embed"]["table"].T

    def loss_tensor_parallel_sp(self, params, tokens_local, axis_name):
        """Next-token loss over the Megatron-SP forward: local logits +
        `lm_loss_seq_parallel`'s boundary ppermute (each shard's first
        token travels left to become its left neighbor's last target).
        The ``pmean`` over ``axis_name`` equals the dense `lm_loss`
        (tested) — so the model axis folds into the gradient average like
        a data axis, same contract as `loss_tensor_parallel`."""
        logits_local = self.apply_tensor_parallel_sp(
            params, tokens_local, axis_name
        )
        return lm_loss_seq_parallel(logits_local, tokens_local, axis_name)

    def init_cache_tp(self, batch, axis_name, cache_len=None, dtype=None):
        """Per-rank KV cache for tensor-parallel decode, built INSIDE
        shard_map: each rank caches only its head shard —
        ``(batch, kv_heads/n, cache_len, head_dim)`` — so cache HBM
        drops n-fold per chip (the serving reason to decode
        tensor-parallel).  GQA composes: the smaller kv-head set shards
        the same way (``kv_heads % n == 0`` required)."""
        from jax import lax

        n = lax.axis_size(axis_name)
        if self.heads % n:
            raise ValueError(
                f"heads {self.heads} not divisible by axis size {n}"
            )
        if self.kv_heads % n:
            raise ValueError(
                f"kv_heads {self.kv_heads} not divisible by axis size "
                f"{n} — the per-rank KV cache cannot be head-sharded"
            )
        L = cache_len or self.max_seq
        hd = self.dim // self.heads
        z = jnp.zeros(
            (batch, self.kv_heads // n, L, hd), dtype or jnp.float32
        )
        return [{"k": z, "v": z} for _ in self.blocks]

    def apply_cached_tensor_parallel(
        self, params, tokens, cache, index, axis_name
    ):
        """Tensor-parallel `apply_cached` for use INSIDE shard_map:
        sharded-heads incremental attention against the per-rank cache
        (`parallel.tp_attention_cached`) + the Megatron MLP — two psums
        per block, replicated logits out.  Same replicated params as
        `apply`; tests assert the gathered decode equals the dense one."""
        from tpu_dist.parallel.tensor_parallel import (
            tp_attention_cached,
            tp_mlp_block,
        )

        h = self._trunk(params, tokens, pos_offset=index)
        new_cache = []
        for blk, pb, c in zip(self.blocks, params["blocks"], cache):
            x1, _ = blk.ln1.apply(pb["ln1"], {}, h)
            o, ck, cv = tp_attention_cached(
                x1, pb["attn"], blk.attn.heads, c["k"], c["v"], index,
                axis_name, use_rope=self.pos_embedding == "rope",
                window=self.sliding_window,
            )
            h = h + o
            x2, _ = blk.ln2.apply(pb["ln2"], {}, h)
            h = h + tp_mlp_block(x2, pb["mlp"], axis_name)
            new_cache.append({"k": ck, "v": cv})
        h, _ = self.ln.apply(params["ln"], {}, h)
        logits = h @ params["embed"]["table"].T
        return logits, new_cache

    def generate_tensor_parallel(
        self,
        params,
        prompt,
        steps: int,
        axis_name,
        *,
        key=None,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        cache_len: int | None = None,
    ):
        """`generate` with the model tensor-parallel, for use INSIDE
        shard_map over ``axis_name``: one prefill + a ``lax.scan`` of
        single-token steps, heads and KV cache sharded n-ways, logits
        replicated by the per-block psum so every rank samples the SAME
        token from the same key (sampling is deterministic given both).
        Multi-chip serving: n chips' HBM bandwidth reads one model —
        the decode-latency analog of the training-side sharding."""
        from jax import lax

        b, s_p = prompt.shape
        L = cache_len or self.max_seq
        if s_p + steps > L:
            raise ValueError(
                f"prompt {s_p} + steps {steps} exceeds cache length {L}"
            )
        if key is None:
            key = jax.random.key(0)
        sample = _make_sampler(temperature, top_k, top_p, prompt.dtype)

        cache = self.init_cache_tp(
            b, axis_name, L, dtype=params["embed"]["table"].dtype
        )
        logits, cache = self.apply_cached_tensor_parallel(
            params, prompt, cache, 0, axis_name
        )
        last = logits[:, -1]

        def body(carry, k):
            cache, last, idx = carry
            tok = sample(last, k)
            logits, cache = self.apply_cached_tensor_parallel(
                params, tok[:, None], cache, idx, axis_name
            )
            return (cache, logits[:, 0], idx + 1), tok

        keys = jax.random.split(key, steps)
        _, toks = lax.scan(body, (cache, last, jnp.int32(s_p)), keys)
        return jnp.moveaxis(toks, 0, 1)

    def apply_pipeline(
        self, params, tokens, axis_name, *,
        n_microbatches: int = 4, interleave: int = 1, head_params=None,
    ):
        """Pipeline-parallel forward for use INSIDE shard_map over a
        ``pipe`` axis: rank r runs ``depth / n`` consecutive blocks as
        its stage; activations hop stage-to-stage through the GPipe
        microbatch schedule (`tpu_dist.parallel.pipeline_apply`).  The
        embedding trunk and the LN/vocab head are token-local and cheap,
        so they run replicated on every rank rather than as dedicated
        stages.  Same replicated params as `apply`; tests assert
        agreement.

        ``interleave=v > 1`` switches to the interleaved (Megatron
        1F1B-style) schedule: rank r holds ``v`` chunks of
        ``depth/(n·v)`` blocks (chunk c = global stage ``c·n + r``),
        cutting the bubble from ``(n-1)/(M+n-1)`` to
        ``(n-1)/(M·v+n-1)``; ``n_microbatches`` must then be a multiple
        of the pipe world.

        ``head_params``: optional ``(ln_params, embed_table)`` override
        for the replicated LN/vocab head — `loss_pipeline` passes
        gradient-scaled copies so the training gradient contract holds;
        forward values are unchanged."""
        from jax import lax

        from tpu_dist.parallel.pipeline import (
            pipeline_apply,
            pipeline_apply_interleaved,
        )
        from tpu_dist.utils.tree import stack_pytrees

        n = lax.axis_size(axis_name)
        r = lax.axis_index(axis_name)
        depth = len(self.blocks)
        if depth % (n * interleave):
            raise ValueError(
                f"depth {depth} not divisible by pipeline world {n} x "
                f"interleave {interleave}"
            )
        stacked = stack_pytrees(params["blocks"])  # (depth, ...) leaves
        blk = self.blocks[0]  # stages share the block architecture

        def run_blocks(stage_params, h, count):
            for i in range(count):
                pb = jax.tree.map(lambda t: t[i], stage_params)
                h, _ = blk.apply(pb, {}, h)
            return h

        h = self._trunk(params, tokens)
        if interleave == 1:
            per = depth // n
            mine = jax.tree.map(
                lambda t: lax.dynamic_slice_in_dim(t, r * per, per, 0),
                stacked,
            )
            h = pipeline_apply(
                lambda p, a: run_blocks(p, a, per), mine, h,
                n_microbatches=n_microbatches, axis_name=axis_name,
            )
        else:
            pc = depth // (n * interleave)
            chunks = [
                jax.tree.map(
                    lambda t: lax.dynamic_slice_in_dim(
                        t, (c * n + r) * pc, pc, 0
                    ),
                    stacked,
                )
                for c in range(interleave)
            ]
            chunks_local = jax.tree.map(
                lambda *xs: jnp.stack(xs), *chunks
            )
            h = pipeline_apply_interleaved(
                lambda p, a: run_blocks(p, a, pc), chunks_local, h,
                n_microbatches=n_microbatches, axis_name=axis_name,
            )
        ln_p, table = (
            head_params
            if head_params is not None
            else (params["ln"], params["embed"]["table"])
        )
        h, _ = self.ln.apply(ln_p, {}, h)
        return h @ table.T

    def loss_pipeline(
        self, params, tokens, axis_name, *,
        n_microbatches: int = 4, interleave: int = 1,
        engine: bool = False, remat_stages: bool = False,
        schedule_kind: str | None = None,
    ):
        """Pipeline-parallel TRAINING loss for use INSIDE shard_map over
        a ``pipe`` axis (`parallel.make_spmd_train_step` with
        ``grad_psum_axes=(axis_name,)``).

        ``engine=False`` (the GPipe-era path): forward-only scheduling
        through `apply_pipeline`; autodiff replays the schedule scan in
        reverse, so activation memory is O(M) scan residuals.  Gradient
        contract: the psum over ``axis_name`` of the per-rank grad
        pytrees equals the dense `lm_loss` gradient (tested).  The
        pieces: block grads land only on the rank owning each stage
        (`parallel.pipeline_apply`'s convention — summing recovers the
        sequential grads); the embedding-lookup/positional grads land
        only on rank 0 (it alone injects microbatches); the LN/vocab
        head runs REPLICATED on every rank, so its params enter with
        their differentiable path scaled 1/n (forward value unchanged)
        — n identical head grads then psum back to exactly the dense
        grad, and the weight-tied embedding table gets its lookup and
        head contributions each counted once.

        ``engine=True`` routes through the schedule-driven TRUE 1F1B
        executor instead (`loss_pipeline_1f1b`): backward ticks
        interleave with forward ticks, activation stash O(n·v) not
        O(M).  Same psum gradient contract (tested against this path
        and against dense)."""
        if engine:
            return self.loss_pipeline_1f1b(
                params, tokens, axis_name,
                n_microbatches=n_microbatches, interleave=interleave,
                remat_stages=remat_stages, schedule_kind=schedule_kind,
            )
        from jax import lax

        n = lax.axis_size(axis_name)

        def scale(a):
            return a / n + lax.stop_gradient(a * (n - 1) / n)

        head = (
            jax.tree.map(scale, params["ln"]),
            scale(params["embed"]["table"]),
        )
        logits = self.apply_pipeline(
            params, tokens, axis_name,
            n_microbatches=n_microbatches, interleave=interleave,
            head_params=head,
        )
        return lm_loss(logits.astype(jnp.float32), tokens)

    def loss_pipeline_1f1b(
        self, params, tokens, axis_name, *,
        n_microbatches: int = 4, interleave: int = 1,
        remat_stages: bool = False, schedule_kind: str | None = None,
    ):
        """TRUE 1F1B pipeline training loss — the schedule-driven engine
        (`parallel.pipeline_engine_loss`) for use INSIDE shard_map over
        a ``pipe`` axis.

        Stage split matches `apply_pipeline` exactly (rank r, chunk c =
        global stage ``c·n + r`` of ``depth/(n·v)`` consecutive blocks;
        the embedding trunk runs replicated up front), but the loss is
        computed PER MICROBATCH on the last global stage, whose backward
        starts the tick after that microbatch's forward — forwards and
        backwards interleave tick-for-tick and the activation stash
        holds O(n·v) stage inputs instead of O(M) scan residuals.

        Gradient contract (psum over ``axis_name`` equals the dense
        `lm_loss` gradient, tested): chunk-block grads land on the
        owning rank, the LN/vocab-head grads land on rank n-1 (the only
        rank that runs the head), and the embedding-lookup/positional
        grads land on rank 0 via the engine's trunk cotangent — each
        contribution counted exactly once, no replicated-head 1/n
        scaling needed.

        ``schedule_kind`` overrides the schedule table (default:
        ``'interleaved_1f1b'`` when ``interleave > 1`` else ``'1f1b'``;
        ``'gpipe'`` gives the flush schedule with the O(M) stash —
        useful for measuring what 1F1B buys)."""
        from jax import lax

        from tpu_dist.parallel.pipeline import (
            build_schedule,
            default_schedule_kind,
            pipeline_engine_loss,
        )
        from tpu_dist.utils.tree import stack_pytrees

        n = lax.axis_size(axis_name)
        r = lax.axis_index(axis_name)
        v = interleave
        depth = len(self.blocks)
        if depth % (n * v):
            raise ValueError(
                f"depth {depth} not divisible by pipeline world {n} x "
                f"interleave {v}"
            )
        pc = depth // (n * v)
        stacked = stack_pytrees(params["blocks"])
        chunks = [
            jax.tree.map(
                lambda t: lax.dynamic_slice_in_dim(t, (c * n + r) * pc, pc, 0),
                stacked,
            )
            for c in range(v)
        ]
        chunks_local = stack_pytrees(chunks)
        blk = self.blocks[0]  # stages share the block architecture

        def stage_fn(chunk_params, a):
            for i in range(pc):
                pb = jax.tree.map(lambda t: t[i], chunk_params)
                a, _ = blk.apply(pb, {}, a)
            return a

        def last_fn(chunk_params, head, x_in, tok_mb):
            y = stage_fn(chunk_params, x_in)
            ln_p, table = head
            y, _ = self.ln.apply(ln_p, {}, y)
            return lm_loss((y @ table.T).astype(jnp.float32), tok_mb)

        kind = schedule_kind or default_schedule_kind(v)
        sched = build_schedule(n, n_microbatches, v, kind)
        h = self._trunk(params, tokens)
        return pipeline_engine_loss(
            stage_fn, last_fn, sched, chunks_local,
            (params["ln"], params["embed"]["table"]), h, tokens,
            axis_name=axis_name, remat_stages=remat_stages,
        )

    def apply_moe_ep(self, params, tokens_local, axis_name):
        """Expert-parallel forward for use INSIDE shard_map: the batch
        is sharded over ``axis_name`` (attention is per-sample, so batch
        sharding is exact) and each rank owns ONE expert per block —
        every MoE layer dispatches its local tokens to their routed
        experts with one ``all_to_all`` each way
        (`parallel.moe_mlp_top2`).  Requires ``moe_experts == axis
        size``.  Params enter replicated (each rank slices its expert
        row), which makes the gradient contract a UNIFORM pmean over
        ``axis_name``: shared params replicate per-rank full grads, and
        each expert's grads appear on exactly one rank (the psum inside
        pmean sums them once, the 1/n is the global-batch mean).

        Returns ``(logits_local, balance)`` — the mean GShard balance
        loss over blocks (its gradient flows into the routers).
        """
        from jax import lax

        from tpu_dist.parallel.moe import moe_mlp_top2

        n = lax.axis_size(axis_name)
        if self.moe_experts != n:
            raise ValueError(
                f"moe_experts {self.moe_experts} != expert-axis size {n} "
                "(one expert per rank)"
            )
        r = lax.axis_index(axis_name)
        b, s = tokens_local.shape
        h = self._trunk(params, tokens_local)
        balances = []
        for blk, pb in zip(self.blocks, params["blocks"]):
            x1, _ = blk.ln1.apply(pb["ln1"], {}, h)
            o, _ = blk.attn.apply(pb["attn"], {}, x1)
            h = h + o
            x2, _ = blk.ln2.apply(pb["ln2"], {}, h)
            pm = pb["moe"]
            y2, stats = moe_mlp_top2(
                x2.reshape(b * s, self.dim),
                pm["gate"],
                lax.dynamic_index_in_dim(pm["up"], r, 0, keepdims=False),
                lax.dynamic_index_in_dim(pm["down"], r, 0, keepdims=False),
                axis_name=axis_name,
                capacity_factor=self.moe_capacity_factor,
            )
            h = h + y2.reshape(b, s, self.dim)
            balances.append(stats["balance_loss"])
        h, _ = self.ln.apply(params["ln"], {}, h)
        logits = h @ params["embed"]["table"].T
        return logits, jnp.mean(jnp.stack(balances))

    def loss_moe_ep(self, params, tokens_local, axis_name):
        """Expert-parallel training loss: local next-token loss plus
        ``moe_balance_weight ×`` the mean balance loss (the router
        regularizer keeping experts utilized).  pmean over ``axis_name``
        == the global-batch loss; uniform-pmean gradient contract per
        `apply_moe_ep` (tested == dense in test_moe.py)."""
        logits, balance = self.apply_moe_ep(params, tokens_local, axis_name)
        return (
            lm_loss(logits.astype(jnp.float32), tokens_local)
            + self.moe_balance_weight * balance
        )

    def apply_seq_parallel(self, params, tokens_local, axis_name, *,
                           flash: bool = False, interpret: bool = False,
                           attention: str = "ring"):
        """Sequence-parallel forward for use INSIDE shard_map: tokens are
        the local sequence shard; attention runs as a ppermute ring over
        ``axis_name``; everything else is token-local.  Same params as
        `apply` — tests assert bitwise-tolerance agreement.

        ``flash=True`` computes each ring block with the Pallas flash
        kernel (`parallel.ring_attention_flash`) — same numbers, no
        per-block (s_local, s_local) score materialization; ``interpret``
        runs the kernel in interpret mode (CPU-sim testing).
        ``attention="ulysses"`` swaps the ring core for the all-to-all
        head-resharding strategy (`parallel.ulysses_attention`; needs
        ``heads % world == 0``) — pick by topology: the ring hides
        communication behind block matmuls on a torus, Ulysses pays two
        all-to-alls but runs full-sequence attention locally."""
        from jax import lax

        from tpu_dist.parallel.ring_attention import RingMultiHeadAttention

        # flash+window is refused by RingMultiHeadAttention's own guard
        if self.kv_heads != self.heads:
            raise ValueError(
                "apply_seq_parallel requires kv_heads == heads (the ring "
                "attention core uses the fused-QKV layout)"
            )
        b, s_local = tokens_local.shape
        # Same block math as `apply`, with the attention core swapped for
        # the ring module (identical param structure by construction).
        # Constructed BEFORE any axis query so its validation (e.g. the
        # flash+window refusal) raises cleanly outside shard_map too.
        ring_mha = RingMultiHeadAttention(
            self.dim, self.heads, axis_name=axis_name, causal=True,
            use_rope=self.pos_embedding == "rope",
            use_flash=flash, interpret=interpret, core=attention,
            sliding_window=self.sliding_window,
        )
        n = lax.axis_size(axis_name)
        if n * s_local > self.max_seq:
            raise ValueError(
                f"global sequence {n} ranks x {s_local} tokens = "
                f"{n * s_local} exceeds max_seq {self.max_seq} — the "
                f"positional table would silently clamp"
            )
        r = lax.axis_index(axis_name)
        h = self._trunk(params, tokens_local, pos_offset=r * s_local)
        for blk, pb in zip(self.blocks, params["blocks"]):
            x1, _ = blk.ln1.apply(pb["ln1"], {}, h)
            o, _ = ring_mha.apply(pb["attn"], {}, x1)
            h = h + o
            x2, _ = blk.ln2.apply(pb["ln2"], {}, h)
            m, _ = blk.mlp.apply(pb["mlp"], {}, x2)
            h = h + m
        h, _ = self.ln.apply(params["ln"], {}, h)
        return h @ params["embed"]["table"].T

    # ---- context-parallel decode (sequence-sharded prompt cache) -------

    def _project_qkv(self, attn_params, x, positions):
        """Fused-QKV projection + optional rope at GLOBAL ``positions``;
        x (b, s, d) -> q, k, v each (b, heads, s, head_dim)."""
        from tpu_dist.nn.attention import rope

        b, s, _ = x.shape
        hd = self.dim // self.heads
        qkv = (x @ attn_params["qkv"]["w"] + attn_params["qkv"]["b"]).reshape(
            b, s, 3, self.heads, hd
        )
        q, k, v = (jnp.moveaxis(qkv[:, :, i], 1, 2) for i in range(3))
        if self.pos_embedding == "rope":
            q, k = rope(q, positions), rope(k, positions)
        return q, k, v

    def generate_seq_parallel(
        self,
        params,
        prompt_local,
        steps: int,
        axis_name,
        *,
        key=None,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
    ):
        """Decode after a SEQUENCE-SHARDED prompt, for use INSIDE
        shard_map over ``axis_name`` — context-parallel serving: a
        prompt too long for one chip's KV cache is prefilled with ring
        attention and its K/V stay sharded, 1/n per rank, for the whole
        decode.

        Prefill: `apply_seq_parallel`'s block math, additionally saving
        each block's LOCAL K/V shard (the distributed prompt cache); the
        last global position's logits reach every rank with one psum.
        Decode: each new token is computed replicated; every rank scores
        it against its prompt-cache shard, and the per-rank partials
        merge EXACTLY via log-sum-exp (the flash/ring recombination) with
        a small replicated cache of the generated window.  Every rank
        samples the same token from the same key.  Token-exact vs the
        dense `generate` on the gathered prompt (tested; fused-QKV
        layout, learned or rope positions).

        ``prompt_local``: (b, s_p_local) — rank r holds global positions
        ``r*s_p_local ..``.  Returns (b, steps) sampled tokens
        (replicated).
        """
        self._require_no_window("generate_seq_parallel")
        from jax import lax

        if self.kv_heads != self.heads:
            raise ValueError(
                "generate_seq_parallel requires kv_heads == heads "
                "(fused-QKV layout)"
            )
        n = lax.axis_size(axis_name)
        r = lax.axis_index(axis_name)
        b, s_l = prompt_local.shape
        S = n * s_l  # global prompt length
        if S + steps > self.max_seq:
            raise ValueError(
                f"prompt {S} + steps {steps} exceeds max_seq {self.max_seq}"
            )
        if key is None:
            key = jax.random.key(0)
        sample = _make_sampler(temperature, top_k, top_p, prompt_local.dtype)
        from tpu_dist.parallel.ring_attention import ring_attention

        # --- prefill: ring attention, saving local K/V per block ---
        h = self._trunk(params, prompt_local, pos_offset=r * s_l)
        pos_local = r * s_l + jnp.arange(s_l)
        prompt_cache = []
        for blk, pb in zip(self.blocks, params["blocks"]):
            x1, _ = blk.ln1.apply(pb["ln1"], {}, h)
            q, k, v = self._project_qkv(pb["attn"], x1, pos_local)
            o = ring_attention(q, k, v, axis_name, causal=True)
            o = jnp.moveaxis(o, 1, 2).reshape(b, s_l, self.dim)
            h = h + o @ pb["attn"]["out"]["w"] + pb["attn"]["out"]["b"]
            x2, _ = blk.ln2.apply(pb["ln2"], {}, h)
            m, _ = blk.mlp.apply(pb["mlp"], {}, x2)
            h = h + m
            prompt_cache.append({"k": k, "v": v})  # (b, heads, s_l, hd)
        h, _ = self.ln.apply(params["ln"], {}, h)
        last_local = h[:, -1] @ params["embed"]["table"].T  # (b, V)
        # the last GLOBAL token lives on rank n-1; one psum replicates it
        last = lax.psum(
            jnp.where(r == n - 1, last_local, jnp.zeros_like(last_local)),
            axis_name,
        )

        # --- decode: replicated window cache + sharded prompt cache ---
        hd = self.dim // self.heads
        dt = params["embed"]["table"].dtype
        dec_cache = [
            {
                "k": jnp.zeros((b, self.heads, steps, hd), dt),
                "v": jnp.zeros((b, self.heads, steps, hd), dt),
            }
            for _ in self.blocks
        ]

        def decode_one(tok, dec_cache, t):
            """One replicated token at global position S + t."""
            pos = S + t
            hh = self._trunk(params, tok[:, None], pos_offset=pos)
            new_cache = []
            for blk, pb, pc, dc in zip(
                self.blocks, params["blocks"], prompt_cache, dec_cache
            ):
                x1, _ = blk.ln1.apply(pb["ln1"], {}, hh)
                q, k_new, v_new = self._project_qkv(
                    pb["attn"], x1, pos + jnp.arange(1)
                )
                dk = lax.dynamic_update_slice_in_dim(
                    dc["k"], k_new.astype(dt), t, axis=2
                )
                dv = lax.dynamic_update_slice_in_dim(
                    dc["v"], v_new.astype(dt), t, axis=2
                )
                scale = hd**-0.5
                qs = (q * scale).astype(jnp.float32)
                # partial attention over this rank's prompt shard
                lg_p = jnp.einsum(
                    "bhqd,bhkd->bhqk", qs, pc["k"].astype(jnp.float32)
                )
                m_p = lg_p.max(-1)
                p_p = jnp.exp(lg_p - m_p[..., None])
                l_p = p_p.sum(-1)
                out_p = jnp.einsum(
                    "bhqk,bhkd->bhqd", p_p, pc["v"].astype(jnp.float32)
                ) / l_p[..., None]
                lse_p = m_p + jnp.log(l_p)
                # replicated decode window (positions < t+1 valid)
                lg_d = jnp.einsum(
                    "bhqd,bhkd->bhqk", qs, dk.astype(jnp.float32)
                )
                valid = (jnp.arange(dk.shape[2]) <= t)[None, None, None, :]
                lg_d = jnp.where(valid, lg_d, -1e30)
                m_d = lg_d.max(-1)
                p_d = jnp.exp(lg_d - m_d[..., None])
                p_d = jnp.where(valid, p_d, 0.0)
                l_d = p_d.sum(-1)
                out_d = jnp.einsum(
                    "bhqk,bhkd->bhqd", p_d, dv.astype(jnp.float32)
                ) / jnp.maximum(l_d, 1e-30)[..., None]
                lse_d = m_d + jnp.log(jnp.maximum(l_d, 1e-30))
                # exact merge: psum the prompt partials, add the decode
                # part ONCE (it is identical on every rank)
                m_star = jnp.maximum(lax.pmax(lse_p, axis_name), lse_d)
                w_p = jnp.exp(lse_p - m_star)
                w_d = jnp.exp(lse_d - m_star)
                num = lax.psum(w_p[..., None] * out_p, axis_name) + (
                    w_d[..., None] * out_d
                )
                den = lax.psum(w_p, axis_name) + w_d
                o = (num / den[..., None]).astype(hh.dtype)
                o = jnp.moveaxis(o, 1, 2).reshape(b, 1, self.dim)
                hh = hh + o @ pb["attn"]["out"]["w"] + pb["attn"]["out"]["b"]
                x2, _ = blk.ln2.apply(pb["ln2"], {}, hh)
                mm, _ = blk.mlp.apply(pb["mlp"], {}, x2)
                hh = hh + mm
                new_cache.append({"k": dk, "v": dv})
            hh, _ = self.ln.apply(params["ln"], {}, hh)
            return hh[:, 0] @ params["embed"]["table"].T, new_cache

        def body(carry, kk):
            dec_cache, last, t = carry
            tok = sample(last, kk)
            logits, dec_cache = decode_one(tok, dec_cache, t)
            return (dec_cache, logits, t + 1), tok

        keys = jax.random.split(key, steps)
        _, toks = lax.scan(body, (dec_cache, last, jnp.int32(0)), keys)
        return jnp.moveaxis(toks, 0, 1)


def lm_loss(
    logits: jax.Array, tokens: jax.Array, *, mask: jax.Array | None = None
) -> jax.Array:
    """Next-token cross-entropy: predict tokens[:, 1:] from positions
    [:, :-1].

    ``mask``: optional ``(b, s)`` boolean of REAL (non-pad) tokens; a
    position's loss counts only when its target token is real, and the
    mean is over counted positions — pair with ``apply(attn_mask=...)``
    so padded batches train identically to trimmed ones (tested)."""
    b, s, V = logits.shape
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(
            logits[:, :-1].astype(jnp.float32), axis=-1
        )
        picked = jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1
        )[..., 0]
        if mask is None:
            return -picked.mean()
        w = mask[:, 1:].astype(jnp.float32)
        return -(picked * w).sum() / jnp.maximum(w.sum(), 1.0)


def lm_loss_seq_parallel(
    logits_local: jax.Array, tokens_local: jax.Array, axis_name: str
) -> jax.Array:
    """Next-token loss over sequence shards, boundary-correct.

    Position ``t``'s target is token ``t+1`` — for the LAST position of
    each shard that token lives on the RIGHT neighbor, so targets are
    built by shifting in each right neighbor's first token via
    ``ppermute`` (one tiny collective).  The final global position has no
    target and is masked.  Averaged so that the mean over ranks equals
    the dense `lm_loss` on the gathered sequence (tests assert this),
    which makes it directly usable under a data-axis ``pmean``.
    """
    from jax import lax

    n = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    b, s_local, vocab = logits_local.shape
    # left neighbor -> me: I receive my RIGHT... ppermute ring sends
    # i -> i+1; to receive the right neighbor's first token, send each
    # shard's first token LEFT: perm (i -> i-1).
    first = tokens_local[:, :1]
    from_right = lax.ppermute(
        first, axis_name, [(i, (i - 1) % n) for i in range(n)]
    )
    targets = jnp.concatenate([tokens_local[:, 1:], from_right], axis=1)
    # f32 like lm_loss: bf16 log-softmax would make the TP trajectory
    # diverge from the dense one under compute_dtype='bfloat16'
    logp = jax.nn.log_softmax(logits_local.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    # mask the last global position (rank n-1's last token has no target)
    pos_valid = jnp.where(
        (r == n - 1)
        & (jnp.arange(s_local) == s_local - 1)[None, :].astype(bool),
        0.0,
        1.0,
    )
    # normalize so the pmean over ranks equals the dense mean over the
    # (S_global - 1) predicted positions
    total_positions = n * s_local - 1
    return -(picked * pos_valid).sum() / (b * total_positions / n)


def markov_table(vocab: int = 256, *, seed: int = 0):
    """The transition table behind `synthetic_tokens` (a seeded
    permutation): ``next_token = table[token]``.  Exposed so demos/tests
    can verify generated continuations against the chain without
    replaying the corpus RNG call order by hand."""
    import numpy as np

    return np.random.default_rng(seed).permutation(vocab)


def synthetic_tokens(
    n: int, seq: int, vocab: int = 256, *, seed: int = 0
) -> jax.Array:
    """Deterministic learnable token streams: a fixed random Markov chain
    (every next-token distribution is a delta on a seeded permutation —
    see `markov_table`), so a model that learns the transition table
    drives loss toward zero."""
    import numpy as np

    rng = np.random.default_rng(seed)
    table = rng.permutation(vocab)
    starts = rng.integers(0, vocab, size=n)
    out = np.empty((n, seq), np.int32)
    out[:, 0] = starts
    for t in range(1, seq):
        out[:, t] = table[out[:, t - 1]]
    return jnp.asarray(out)


def lm_perplexity(lm, params, tokens, *, batch: int = 64):
    """Token-weighted mean next-token loss and perplexity over a
    ``(N, S)`` token array (e.g. stacked `data.TextCorpus` windows).

    Batches are processed with at most two compiled shapes (full batches
    plus one tail batch); each window contributes ``S - 1`` predicted
    positions.  Returns ``(mean_loss, perplexity)`` — the reference-style
    scalar observable for the LM family (perplexity = exp(loss))."""
    import numpy as np

    n, s = tokens.shape
    if n == 0:
        raise ValueError("empty token array")

    @jax.jit
    def batch_loss(p, t):
        logits, _ = lm.apply(p, {}, t)
        return lm_loss(logits, t)

    total, weight = 0.0, 0
    for i in range(0, n, batch):
        chunk = tokens[i : i + batch]
        loss = float(batch_loss(params, jnp.asarray(np.asarray(chunk))))
        w = chunk.shape[0] * (s - 1)
        total += loss * w
        weight += w
    mean = total / weight
    return mean, float(jnp.exp(mean))
