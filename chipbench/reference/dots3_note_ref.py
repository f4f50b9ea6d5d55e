"""A decoder of latent-attention layers, some selecting their keys and some
windowed, over sigmoid-routed experts, plainly: the forward pass in float32
`jax.numpy`.

Written from the published `config.json` keys of ``model_type: dots3_note``
(`chipbench/configs/dots3-note-prev.json` has them, and under ``assumed``
the three conventions the keys name without spelling out) and importing
nothing of the program under test.  ``x`` is a row of the residual stream,
``t`` its position:

- stream: ``h0 = E[token]``; for each layer ``h <- h + Mix_l(RMSNorm(h))``,
  then ``h <- h + FF_l(RMSNorm(h))``; logits ``= RMSNorm(h_L) @ W_head``
  (untied).  RMSNorm: ``x / sqrt(mean(x^2) + rms_norm_eps) * g``.
- latent attention, sizes by ``layer_types[l]`` (`sizes`: the plain keys for
  ``full_attention``, the ``swa_`` keys for ``sliding_attention``): ``c_q =
  sqrt(hidden / q_lora_rank) * RMSNorm(x W_dq)``; ``[q_n | q_r]_h = c_q
  W_uq``; ``[c | k_r] = x W_dkv``; ``c_kv = sqrt(hidden / kv_lora_rank) *
  RMSNorm(c)``; ``q_r, k_r`` rotated by ``t`` (rope, half-split pairs, base
  ``rope_theta``; ONE ``k_r`` for all heads); ``[k_n | v]_h = c_kv W_ukv``;
  ``s_h(t, j) = (q_n,h(t) . k_n,h(j) + q_r,h(t) . k_r(j)) / sqrt(d_n + d_r)``
  over the visible ``j``, softmax, ``o_h = sum_j p_h(t, j) v_h(j)``; ``o_h
  <- sigmoid(x W_g)_h * o_h``; ``[o_1 .. o_H] W_o``.  No biases.  Expanded:
  ``k_n`` and ``v`` of every token are built; nothing is cached or absorbed.
- visible set.  ``sliding_attention``: ``t - sliding_window_size < j <= t``.
  ``full_attention``: the indexer's picks: ``q^I_i = c_q W_iq``
  (``index_n_heads`` of ``index_head_dim``, the first ``qk_rope_head_dim``
  values rotated), ``k^I = LayerNorm(x W_ik)`` (rotated alike), ``w = x W_iw
  / sqrt(index_n_heads)``, ``I(t, j) = sum_i w_i(t) relu(q^I_i(t) . k^I(j))
  / sqrt(index_head_dim)``, all ``S x S`` of them; visible are the
  ``index_topk`` places ``j <= t`` of largest ``I(t, j)`` (`lax.top_k`: ties
  to the lower ``j``), all of them while ``t < index_topk``.
- feed-forward: layer ``l < first_k_dense_replace`` ``(silu(a) * b) W_out``,
  ``[a | b] = u W_in``, ``intermediate_size`` wide.  Later layers: ``sig =
  sigmoid(u W_r)`` (``router_experts`` of them), picks ``= top_k(sig + b,
  num_experts_per_tok)``, gates ``g_j = routed_scaling_factor * sig_j / sum
  over the picks of sig``, ``sum_j g_j expert_j(u) + shared(u)``, both of the
  dense form at ``moe_intermediate_size``: a loop over the experts HELD here
  (``held_experts = [lo, hi)``), each computing every token and masked by
  the picks.  What the absent experts would add is left out; the gates stay
  normalised over all the picks.

Every matrix product runs under ``default_matmul_precision("highest")``.
Weights keep the dtype they are given in and are cast to float32 where they
are used.  Attention walks the queries in blocks of `BLOCK` and, for each,
the keys in blocks, skipping a key block that lies wholly past the diagonal
or before the window (exact: its terms are zero); a block's scores are
``heads x BLOCK x BLOCK``, so a sequence of 14,336 fits beside the weights.
``quant`` rounds both operands of every matrix product through a
lower-precision type: the control of `correct`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
BLOCK = 256       # queries, and keys, a block of the attention's walk


def sizes(cfg: dict) -> dict:
    """By layer kind, the latent attention's sizes under one set of names."""
    def of(pre: str, theta: str) -> dict:
        return {"heads": cfg[pre + "num_attention_heads"], "q_rank": cfg[pre + "q_lora_rank"],
                "kv_rank": cfg[pre + "kv_lora_rank"], "nope": cfg[pre + "qk_nope_head_dim"],
                "rope": cfg[pre + "qk_rope_head_dim"], "v": cfg[pre + "v_head_dim"],
                "base": float(cfg[theta])}

    lo, hi = cfg["held_experts"]
    assert hi - lo == cfg["n_routed_experts"] and hi <= cfg["router_experts"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"]
    return {"full_attention": of("", "rope_theta"),
            "sliding_attention": of("swa_", "swa_rope_theta")}


def init_parts(key, cfg: dict, dtype=F32) -> tuple[dict, list[dict]]:
    """Seeded weights (the configuration's ``assumed`` says why each):
    matrices normal(0, ``initializer_range``), but the embedding normal(0,
    ``embedding_initializer_range``) and the queries' up-projection ``W_uq``
    normal(0, ``query_initializer_range``); norm gains one, the LayerNorm's
    bias zero, the selection bias normal(0, 0.01).  Router and bias are
    float32 whatever ``dtype``."""
    D, V, sz = cfg["hidden_size"], cfg["vocab_size"], sizes(cfg)
    std = cfg.get("initializer_range", 0.02)
    std_e = cfg.get("embedding_initializer_range", std)
    std_q = cfg.get("query_initializer_range", std)
    n = lambda k, shape, dt=dtype, s=std: (jax.random.normal(k, shape, F32) * s).astype(dt)  # noqa: E731
    ones = lambda d: jnp.ones((d,), dtype)  # noqa: E731

    def mixer(kind, k):
        z, ks = sz[kind], jax.random.split(k, 9)
        H = z["heads"]
        p = {"w_dq": n(ks[0], (D, z["q_rank"])), "q_norm": ones(z["q_rank"]),
             "w_uq": n(ks[1], (z["q_rank"], H * (z["nope"] + z["rope"])), s=std_q),
             "w_dkv": n(ks[2], (D, z["kv_rank"] + z["rope"])), "kv_norm": ones(z["kv_rank"]),
             "w_ukv": n(ks[3], (z["kv_rank"], H * (z["nope"] + z["v"]))),
             "w_gate": n(ks[4], (D, H)), "w_o": n(ks[5], (H * z["v"], D))}
        if kind == "full_attention":
            ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
            p.update(w_iq=n(ks[6], (z["q_rank"], ih * idim)), w_ik=n(ks[7], (D, idim)),
                     ik_gain=ones(idim), ik_bias=jnp.zeros((idim,), dtype),
                     w_iw=n(ks[8], (D, ih)))
        return p

    def layer(at, kind, k):
        ks = jax.random.split(k, 7)
        p = {"ln1": ones(D), "mixer": mixer(kind, ks[0]), "ln2": ones(D)}
        if at < cfg["first_k_dense_replace"]:
            W = cfg["intermediate_size"]
            return {**p, "ff_in": n(ks[1], (D, 2 * W)), "ff_out": n(ks[2], (W, D))}
        W, held, E = cfg["moe_intermediate_size"], cfg["n_routed_experts"], cfg["router_experts"]
        return {**p, "router": n(ks[1], (D, E), F32), "router_bias": n(ks[6], (E,), F32, 0.01),
                "experts_in": n(ks[2], (held, D, 2 * W)), "experts_out": n(ks[3], (held, W, D)),
                "shared_in": n(ks[4], (D, 2 * W * cfg["n_shared_experts"])),
                "shared_out": n(ks[5], (W * cfg["n_shared_experts"], D))}

    kinds = cfg["layer_types"]
    k_wte, k_head, *k_layers = jax.random.split(key, len(kinds) + 2)
    top = {"wte": n(k_wte, (V, D), s=std_e), "lnf": ones(D), "head": n(k_head, (D, V))}
    return top, [layer(at, kind, k) for at, (kind, k) in enumerate(zip(kinds, k_layers))]


def init(key, cfg: dict, dtype=F32) -> dict:
    """`init_parts` in the layout `forward` takes."""
    top, layers = init_parts(key, cfg, dtype)
    return {**top, "layers": layers}


def _round_through(dtype):
    if dtype is None:
        return lambda x: x
    return lambda x: x.astype(dtype).astype(F32)


def _rms_norm(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g.astype(F32)


def _rope(x, base):
    """``x (S, ..., d)``, token ``t`` at position ``t``: pairs ``(i, i + d/2)``
    rotated by ``t * base^(-2i/d)``."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=F32) / half)
    angles = jnp.arange(x.shape[0], dtype=F32).reshape((-1,) + (1,) * (x.ndim - 1)) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _blocks(a):
    """``(S, ...)`` -> ``(S / BLOCK, BLOCK, ...)``."""
    return a.reshape((a.shape[0] // BLOCK, BLOCK) + a.shape[1:])


def _picks(x, c_q, p, cfg, q):
    """The indexer: ``(S, S)`` bool, the places each query selects."""
    S = x.shape[0]
    ih, idim, r = cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"]
    base = float(cfg["rope_theta"])
    rotated = lambda t: jnp.concatenate([_rope(t[..., :r], base), t[..., r:]], axis=-1)  # noqa: E731
    q_i = rotated((q(c_q) @ q(p["w_iq"].astype(F32))).reshape(S, ih, idim))
    k = q(x) @ q(p["w_ik"].astype(F32))
    mean = k.mean(-1, keepdims=True)
    k = (k - mean) / jnp.sqrt(((k - mean) ** 2).mean(-1, keepdims=True) + 1e-6)
    k_i = rotated(k * p["ik_gain"].astype(F32) + p["ik_bias"].astype(F32))
    w = (q(x) @ q(p["w_iw"].astype(F32))) / math.sqrt(ih)
    topk = min(cfg["index_topk"], S)

    def rows(blk):   # a block of queries against every key
        q_b, w_b, t = blk
        per_head = jnp.einsum("qhd,kd->qhk", q(q_b), q(k_i))
        score = jnp.einsum("qhk,qh->qk", q(jax.nn.relu(per_head)), q(w_b)) / math.sqrt(idim)
        causal = jnp.arange(S)[None, :] <= t[:, None]
        score = jnp.where(causal, score, -jnp.inf)
        _, idx = lax.top_k(score, topk)
        picked = jnp.zeros((BLOCK, S), bool).at[jnp.arange(BLOCK)[:, None], idx].set(True)
        return picked & causal

    return lax.map(rows, (_blocks(q_i), _blocks(w), _blocks(jnp.arange(S)))).reshape(S, S)


def _attention(x, p, z, cfg, q, *, window=None, select=False):
    """One latent-attention layer over ``x (S, hidden)``, ``S`` a multiple
    of `BLOCK`; ``z``: the kind's `sizes`."""
    S, D = x.shape
    H, dn, dr, dv, eps = z["heads"], z["nope"], z["rope"], z["v"], cfg["rms_norm_eps"]
    w = lambda name: q(p[name].astype(F32))  # noqa: E731
    gain = math.sqrt(D / z["q_rank"]), math.sqrt(D / z["kv_rank"])
    if not cfg["apply_mla_qkv_lora_rescale"]:
        gain = 1.0, 1.0
    c_q = gain[0] * _rms_norm(q(x) @ w("w_dq"), p["q_norm"], eps)
    qs = (q(c_q) @ w("w_uq")).reshape(S, H, dn + dr)
    q_n, q_r = qs[..., :dn], _rope(qs[..., dn:], z["base"])
    ck = q(x) @ w("w_dkv")
    c_kv = gain[1] * _rms_norm(ck[:, :z["kv_rank"]], p["kv_norm"], eps)
    k_r = _rope(ck[:, z["kv_rank"]:], z["base"])
    kv = (q(c_kv) @ w("w_ukv")).reshape(S, H, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    picked = _picks(x, c_q, p, cfg, q) if select else None
    scale = 1.0 / math.sqrt(dn + dr)
    keys = tuple(_blocks(q(a)) for a in (k_n, k_r, v))

    def queries(blk):
        i, qn_b, qr_b = blk
        t = i * BLOCK + jnp.arange(BLOCK)

        def one(j, carry):
            top, total, acc = carry
            kn_b, kr_b, v_b = (a[j] for a in keys)
            at = j * BLOCK + jnp.arange(BLOCK)
            s = (jnp.einsum("qhd,khd->hqk", qn_b, kn_b)
                 + jnp.einsum("qhd,kd->hqk", qr_b, kr_b)) * scale
            seen = at[None, :] <= t[:, None]
            if window is not None:
                seen &= at[None, :] > t[:, None] - window
            if picked is not None:
                seen &= lax.dynamic_slice(picked, (i * BLOCK, j * BLOCK), (BLOCK, BLOCK))
            s = jnp.where(seen, s, -jnp.inf)
            new_top = jnp.maximum(top, s.max(-1))
            safe = jnp.where(jnp.isfinite(new_top), new_top, 0.0)
            e = jnp.exp(s - safe[..., None])
            keep = jnp.exp(jnp.where(jnp.isfinite(top), top, -jnp.inf) - safe)
            acc = acc * keep[..., None] + jnp.einsum("hqk,khd->hqd", q(e), v_b)
            return new_top, total * keep + e.sum(-1), acc

        first = 0 if window is None else jnp.maximum(0, (i * BLOCK - window + 1) // BLOCK)
        start = (jnp.full((H, BLOCK), -jnp.inf), jnp.zeros((H, BLOCK)), jnp.zeros((H, BLOCK, dv)))
        _, total, acc = lax.fori_loop(first, i + 1, one, start)
        return jnp.moveaxis(acc / total[..., None], 0, 1)    # (BLOCK, H, dv)

    n = S // BLOCK
    o = lax.map(queries, (jnp.arange(n), _blocks(q(q_n)), _blocks(q(q_r)))).reshape(S, H, dv)
    if cfg["attention_gate_type"] == "headwise":
        o = o * jax.nn.sigmoid(q(x) @ w("w_gate"))[..., None]
    return q(o.reshape(S, H * dv)) @ w("w_o")


def _gated(u, w_in, w_out, q):
    ab = q(u) @ q(w_in.astype(F32))
    a, b = jnp.split(ab, 2, axis=-1)
    return q(jax.nn.silu(a) * b) @ q(w_out.astype(F32))


def _experts(u, p, cfg, q):
    lo, _ = cfg["held_experts"]
    sig = jax.nn.sigmoid(q(u) @ q(p["router"]))
    _, idx = lax.top_k(sig + p["router_bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(sig, idx, axis=-1)
    g = cfg["routed_scaling_factor"] * picked
    if cfg["norm_topk_prob"]:
        g = g / picked.sum(-1, keepdims=True)

    def one(acc, e):
        w_in, w_out, number = e
        gate = (g * (idx == number)).sum(-1)          # 0 where not picked
        return acc + gate[..., None] * _gated(u, w_in, w_out, q), None

    held = (p["experts_in"], p["experts_out"], lo + jnp.arange(p["experts_in"].shape[0]))
    routed, _ = lax.scan(one, jnp.zeros_like(u), held)
    return routed + _gated(u, p["shared_in"], p["shared_out"], q)


def _one(p, tokens, cfg, q):
    """``tokens (S,)`` -> logits ``(S, vocab)``."""
    S = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, -S % BLOCK))       # pads lie after every real token
    sz, eps = sizes(cfg), cfg["rms_norm_eps"]
    h = p["wte"][tokens].astype(F32)
    for kind, lp in zip(cfg["layer_types"], p["layers"]):
        full = kind == "full_attention"
        h = h + _attention(_rms_norm(h, lp["ln1"], eps), lp["mixer"], sz[kind], cfg, q,
                           window=None if full else cfg["sliding_window_size"], select=full)
        u = _rms_norm(h, lp["ln2"], eps)
        h = h + (_gated(u, lp["ff_in"], lp["ff_out"], q) if "ff_in" in lp
                 else _experts(u, lp, cfg, q))
    return (q(_rms_norm(h, p["lnf"], eps)) @ q(p["head"].astype(F32)))[:S]


def forward(p: dict, tokens, cfg: dict, *, quant=None):
    """``tokens`` (B, S) int -> logits (B, S, vocab), float32."""
    q = _round_through(quant)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_one(p, row, cfg, q) for row in tokens])
