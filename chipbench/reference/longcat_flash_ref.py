"""LongCat-Flash's language model, plainly: a decoder whose layer is two
latent-attention sublayers and two dense feed-forwards with one routed
mixture of experts beside them, some of whose experts have no weights; the
forward pass in float32 `jax.numpy`.

Written from the published `config.json` keys of LongCat-Flash-Omni's
language model (`chipbench/configs/LongCat-Flash-Omni.json` has them, and
under ``assumed`` what the keys name without spelling out) and importing
nothing of the program under test.  ``h`` is a row of the residual stream,
``t`` its position, ``N`` an RMSNorm (``x / sqrt(mean(x^2) + rms_norm_eps) *
g``), four gains a layer:

- a layer (all ``num_layers`` alike):

      a1 = h  + MLA_0(N_0(h))
      u  = N'_0(a1)
      m  = MoE(u)                        # leaves here ...
      b1 = a1 + MLP_0(u)
      a2 = b1 + MLA_1(N_1(b1))
      h' = a2 + MLP_1(N'_1(a2)) + m      # ... and joins here

  ``h0 = E[token]``; logits ``= N(h_L) @ W_head`` (untied).
- ``MLA`` (``attention_method: MLA``), ``x`` its input, ``D`` the hidden
  size: ``c_q = sqrt(D / q_lora_rank) * RMSNorm(x W_dq)``
  (``mla_scale_q_lora``); ``[q_n | q_r]_h = c_q W_uq``, heads of
  ``qk_nope_head_dim + qk_rope_head_dim``; ``[c | k_r] = x W_dkv``; ``c_kv =
  sqrt(D / kv_lora_rank) * RMSNorm(c)`` (``mla_scale_kv_lora``); ``q_r, k_r``
  rotated by ``t`` (rope, half-split pairs, base ``rope_theta``; ONE ``k_r``
  for all heads, neither normed nor rescaled); ``[k_n | v]_h = c_kv W_ukv``;
  ``s_h(t, j) = (q_n,h(t) . k_n,h(j) + q_r,h(t) . k_r(j)) / sqrt(d_n + d_r)``
  over EVERY causal ``j <= t`` (no window, no selection), softmax, ``o_h =
  sum_j p_h(t, j) v_h(j)``; ``[o_1 .. o_H] W_o``.  No gate, no biases.
  Expanded: ``k_n`` and ``v`` of every token are built; nothing is cached or
  absorbed.
- ``MLP``: ``(silu(a) * b) W_down``, ``[a | b] = x [W_gate | W_up]``,
  ``ffn_hidden_size`` wide.
- ``MoE``: ``r = u W_r`` over ``router_experts + zero_expert_num`` outputs;
  ``s = softmax(r)`` over ALL of them; the picks are the ``moe_topk`` largest
  of ``s + b`` (``b`` a selection bias that enters no gate); ``g_j =
  routed_scaling_factor * s_j``, the picks' scores NOT renormalised; ``m =
  sum over the picks j < router_experts of g_j Expert_j(u) + sum over the
  picks j >= router_experts of g_j u`` (``zero_expert_type: identity``).  An
  ``Expert`` has the MLP's form at ``expert_ffn_hidden_size``.  No shared
  expert.  Only the experts HELD here (``held_experts = [lo, hi)`` of the
  ``router_experts`` that have weights) are computed, each over the tokens
  that picked it: the picks are sorted by expert and an expert walks its own
  stretch of them `EXPERT_ROWS` at a time.  What the absent experts would add
  is left out; the zero experts hold nothing, so their part is whole.

Every matrix product of `forward` runs under ``default_matmul_precision
("highest")``.  Weights keep the dtype they are given in and are cast to
float32 where they are used.  So that a sequence of 14,336 fits beside
10 GB of weights: attention takes `HEAD_GROUP` heads at a time and walks
the queries in blocks of `BLOCK` and, for each, the keys in blocks up to the
diagonal (exact: the terms past it are zero); a dense feed-forward takes
its hidden width in `FF_GROUPS` parts; and a weight's float32 copy is made
only once its input is there (`_after`: left to itself the compiler makes
every layer's copies at the program's start, 7 GB of them).  ``quant``
rounds both operands of every matrix product through a lower-precision
type: the control of `correct`.

**The selection bias is calibrated, not drawn** (`balanced_bias`, as
`afmoe_ref`'s): a trained router is balanced, which is what the published
model's bias is for; a random one is not, and which outputs it favours
follows the seed.  After the other weights are drawn, the layers run over a
seeded sample of tokens and, layer by layer, ``b`` is moved by the sign rule
of the auxiliary-loss-free balancing until the sample's load is even over
ALL the router's outputs: the zero experts then take ``zero_expert_num /
(router_experts + zero_expert_num)`` of the picks, the published third, and
the held experts their share of the rest.  It is a weight like any other:
program and reference are given the same ``b``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.afmoe_ref import _rms_norm, _rope, _round_through, balance

F32 = jnp.float32
BLOCK = 256         # queries, and keys, a block of the attention's walk
HEAD_GROUP = 16     # heads an attention sublayer computes at a time
FF_GROUPS = 4       # parts a dense feed-forward's hidden width is taken in
EXPERT_ROWS = 512   # picks an expert computes at a time
# the calibration's sample (sequences x tokens, tokens a multiple of `BLOCK`) and steps,
# where the configuration gives none; the steps are in units of a softmax score over
# hundreds of outputs: from the first to the last they can move a bias by 0.44, a lean
# of a whole standard deviation of the router's logits needs 0.12
CALIBRATION = {"sequences": 32, "tokens": 1024, "steps": 300, "first_step": 1e-2,
               "last_step": 1e-5}


def sizes(cfg: dict) -> dict:
    """The sizes under short names, the configuration's keys checked
    against each other and against what this file computes."""
    lo, hi = cfg["held_experts"]
    assert hi - lo == cfg["n_routed_experts"] and hi <= cfg["router_experts"]
    told = (cfg["attention_method"], cfg["zero_expert_type"], cfg["attention_bias"],
            cfg["mla_scale_q_lora"], cfg["mla_scale_kv_lora"])
    if told != ("MLA", "identity", False, True, True):
        raise ValueError(f"this file computes one form of these keys, not {told}")
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "base": float(cfg["rope_theta"]),
            "outputs": cfg["router_experts"] + cfg["zero_expert_num"],
            "eps": cfg["rms_norm_eps"]}


def init_parts(key, cfg: dict, dtype=F32) -> tuple[dict, list[dict]]:
    """Seeded weights (the configuration's ``assumed.weights`` says why
    each): matrices normal(0, ``initializer_range``) but the embedding
    normal(0, ``embedding_initializer_range``); norm gains one; the
    selection bias calibrated to an even load over a seeded sample
    (`balanced_bias`).  Router and bias are float32 whatever ``dtype``."""
    z = sizes(cfg)
    D, H, V = z["D"], z["H"], cfg["vocab_size"]
    std = cfg.get("initializer_range", 0.02)
    std_e = cfg.get("embedding_initializer_range", std)
    n = lambda k, shape, dt=dtype, s=std: (jax.random.normal(k, shape, F32) * s).astype(dt)  # noqa: E731
    ones = lambda m: jnp.ones((m,), dtype)  # noqa: E731

    def sublayer(k):
        ks = jax.random.split(k, 7)
        W = cfg["ffn_hidden_size"]
        attn = {"w_dq": n(ks[0], (D, z["q_rank"])), "q_norm": ones(z["q_rank"]),
                "w_uq": n(ks[1], (z["q_rank"], H * (z["nope"] + z["rope"]))),
                "w_dkv": n(ks[2], (D, z["kv_rank"] + z["rope"])), "kv_norm": ones(z["kv_rank"]),
                "w_ukv": n(ks[3], (z["kv_rank"], H * (z["nope"] + z["v"]))),
                "w_o": n(ks[4], (H * z["v"], D))}
        return {"ln_in": ones(D), "attn": attn, "ln_post": ones(D),
                "ff_in": n(ks[5], (D, 2 * W)), "ff_out": n(ks[6], (W, D))}

    def layer(k):
        ks = jax.random.split(k, 5)
        W, held = cfg["expert_ffn_hidden_size"], cfg["n_routed_experts"]
        return {"sub": [sublayer(ks[0]), sublayer(ks[1])],
                "router": n(ks[2], (D, z["outputs"]), F32),
                "router_bias": jnp.zeros((z["outputs"],), F32),
                "experts_in": n(ks[3], (held, D, 2 * W)), "experts_out": n(ks[4], (held, W, D))}

    k_wte, k_head, k_sample, *k_layers = jax.random.split(key, cfg["num_layers"] + 3)
    top = {"wte": n(k_wte, (V, D), s=std_e), "lnf": ones(D), "head": n(k_head, (D, V))}
    return top, balanced_bias(top, [layer(k) for k in k_layers], cfg, k_sample)


def init(key, cfg: dict, dtype=F32) -> dict:
    """`init_parts` in the layout `forward` takes."""
    top, layers = init_parts(key, cfg, dtype)
    return {**top, "layers": layers}


def _blocks(a):
    """``(S, ...)`` -> ``(S / BLOCK, BLOCK, ...)``."""
    return a.reshape((a.shape[0] // BLOCK, BLOCK) + a.shape[1:])


def _after(x, w):
    """``w`` as it is, but not before ``x`` is computed: what is made of it
    (its float32 copy) is then made where it is used."""
    return lax.optimization_barrier((x, w))[1]


def _attention(x, p, cfg, q):
    """One latent-attention sublayer over ``x (S, hidden)``, ``S`` a
    multiple of `BLOCK`: every causal key; the heads in groups."""
    z = sizes(cfg)
    S, D = x.shape
    H, dn, dr, dv, eps = z["H"], z["nope"], z["rope"], z["v"], z["eps"]
    G = HEAD_GROUP if H % HEAD_GROUP == 0 else H
    p = _after(x, p)
    w = lambda name: q(p[name].astype(F32))  # noqa: E731
    c_q = math.sqrt(D / z["q_rank"]) * _rms_norm(q(x) @ w("w_dq"), p["q_norm"], eps)
    ck = q(x) @ w("w_dkv")
    c_kv = math.sqrt(D / z["kv_rank"]) * _rms_norm(ck[:, :z["kv_rank"]], p["kv_norm"], eps)
    k_r = _blocks(q(_rope(ck[:, z["kv_rank"]:], z["base"])))
    scale = 1.0 / math.sqrt(dn + dr)

    def by_group(m, width):
        """``m (rank, H * width)``, a head's columns together -> ``(H / G,
        rank, G * width)``."""
        return jnp.moveaxis(m.reshape(m.shape[0], H // G, G * width), 1, 0)

    def group(y, ws):
        w_uq, w_ukv, w_o = (q(a.astype(F32)) for a in ws)
        qs = (q(c_q) @ w_uq).reshape(S, G, dn + dr)
        q_n, q_r = qs[..., :dn], _rope(qs[..., dn:], z["base"])
        kv = (q(c_kv) @ w_ukv).reshape(S, G, dn + dv)
        k_n, v = _blocks(q(kv[..., :dn])), _blocks(q(kv[..., dn:]))

        def queries(blk):
            i, qn_b, qr_b = blk
            t = i * BLOCK + jnp.arange(BLOCK)

            def one(j, carry):
                top, total, acc = carry
                at = j * BLOCK + jnp.arange(BLOCK)
                s = (jnp.einsum("qhd,khd->hqk", qn_b, k_n[j])
                     + jnp.einsum("qhd,kd->hqk", qr_b, k_r[j])) * scale
                s = jnp.where(at[None, :] <= t[:, None], s, -jnp.inf)
                new_top = jnp.maximum(top, s.max(-1))   # finite: block 0 holds a key of every query
                e = jnp.exp(s - new_top[..., None])
                keep = jnp.exp(top - new_top)
                acc = acc * keep[..., None] + jnp.einsum("hqk,khd->hqd", q(e), v[j])
                return new_top, total * keep + e.sum(-1), acc

            start = (jnp.full((G, BLOCK), -jnp.inf), jnp.zeros((G, BLOCK)),
                     jnp.zeros((G, BLOCK, dv)))
            _, total, acc = lax.fori_loop(0, i + 1, one, start)
            return jnp.moveaxis(acc / total[..., None], 0, 1)    # (BLOCK, G, dv)

        blocks = (jnp.arange(S // BLOCK), _blocks(q(q_n)), _blocks(q(q_r)))
        o = lax.map(queries, blocks).reshape(S, G * dv)
        return y + q(o) @ w_o, None

    parts = (by_group(p["w_uq"], dn + dr), by_group(p["w_ukv"], dn + dv),
             p["w_o"].reshape(H // G, G * dv, D))
    return lax.scan(group, jnp.zeros((S, D), F32), parts)[0]


def _gated(u, w_in, w_out, q, groups: int = 1):
    """``(silu(a) * b) W_out``, ``[a | b] = u W_in``; the hidden width in
    ``groups`` parts, one after another."""
    w_in, w_out = _after(u, (w_in, w_out))
    W = w_out.shape[0]
    if W % groups:
        groups = 1
    parts = (jnp.moveaxis(w_in.reshape(-1, 2, groups, W // groups), 2, 0),   # (groups, D, 2, W / groups)
             w_out.reshape(groups, W // groups, -1))

    def part(y, ws):
        w_ab, w_o = (q(a.astype(F32)) for a in ws)
        a, b = q(u) @ w_ab[:, 0], q(u) @ w_ab[:, 1]
        return y + q(jax.nn.silu(a) * b) @ w_o, None

    return lax.scan(part, jnp.zeros(u.shape[:-1] + (w_out.shape[-1],), F32), parts)[0]


def _route(u, p, cfg, q):
    """-> ``(s (T, outputs), idx (T, k), gates (T, k))``: every output's
    softmax score, each token's picks and their gates."""
    s = jax.nn.softmax(jnp.dot(q(u), q(p["router"]), precision=lax.Precision.HIGHEST), axis=-1)
    _, idx = lax.top_k(s + p["router_bias"], cfg["moe_topk"])
    return s, idx, cfg["routed_scaling_factor"] * jnp.take_along_axis(s, idx, axis=-1)


def _moe(u, p, cfg, q, *, zero_part=True):
    """What the experts give ``u (T, hidden)``: the picks on the experts
    held here, sorted by expert, each held expert over its own stretch of
    them; and for the picks on zero experts ``g * u``."""
    T, k = u.shape[0], cfg["moe_topk"]
    lo, hi = cfg["held_experts"]
    _, idx, g = _route(u, p, cfg, q)
    expert = idx.reshape(-1) - lo
    expert = jnp.where((expert >= 0) & (expert < hi - lo), expert, hi - lo)   # not held: last
    order = jnp.argsort(expert, stable=True)
    counts = jnp.zeros((hi - lo + 1,), jnp.int32).at[expert].add(1)
    starts = jnp.cumsum(counts) - counts
    order = jnp.pad(order, (0, EXPERT_ROWS))          # a stretch's last rows may overhang
    gates = g.reshape(-1)

    def one_expert(e, y):
        w_in, w_out = p["experts_in"][e], p["experts_out"][e]

        def rows(i, y):
            at = starts[e] + i * EXPERT_ROWS
            picks = lax.dynamic_slice(order, (at,), (EXPERT_ROWS,))
            mine = at + jnp.arange(EXPERT_ROWS) < starts[e] + counts[e]
            token = picks // k
            out = _gated(u[token], w_in, w_out, q) * gates[picks][:, None]
            return y.at[token].add(jnp.where(mine[:, None], out, 0.0))

        return lax.fori_loop(0, (counts[e] + EXPERT_ROWS - 1) // EXPERT_ROWS, rows, y)

    y = lax.fori_loop(0, hi - lo, one_expert, jnp.zeros_like(u))
    if not zero_part:
        return y
    free = jnp.where(idx >= cfg["router_experts"], g, 0.0).sum(-1)
    return y + free[:, None] * u


def _leave(h, lp, cfg, q):
    """The first half of a layer up to where the routed branch leaves:
    ``(a1, u)``."""
    s0, eps = lp["sub"][0], cfg["rms_norm_eps"]
    a1 = h + _attention(_rms_norm(h, s0["ln_in"], eps), s0["attn"], cfg, q)
    return a1, _rms_norm(a1, s0["ln_post"], eps)


def _rejoin(a1, u, m, lp, cfg, q):
    """The rest of the layer, the branch's output ``m`` joining at its end."""
    s0, s1, eps = *lp["sub"], cfg["rms_norm_eps"]
    b1 = a1 + _gated(u, s0["ff_in"], s0["ff_out"], q, FF_GROUPS)
    a2 = b1 + _attention(_rms_norm(b1, s1["ln_in"], eps), s1["attn"], cfg, q)
    return a2 + _gated(_rms_norm(a2, s1["ln_post"], eps), s1["ff_in"], s1["ff_out"], q,
                       FF_GROUPS) + m


def _one(p, tokens, cfg, q):
    """``tokens (S,)`` -> logits ``(S, vocab)``."""
    S = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, -S % BLOCK))       # pads lie after every real token
    h = p["wte"][tokens].astype(F32)
    for lp in p["layers"]:
        a1, u = _leave(h, lp, cfg, q)
        h = _rejoin(a1, u, _moe(u, lp, cfg, q), lp, cfg, q)
    return (q(_rms_norm(h, p["lnf"], cfg["rms_norm_eps"])) @ q(p["head"].astype(F32)))[:S]


def forward(p: dict, tokens, cfg: dict, *, quant=None):
    """``tokens`` (B, S) int -> logits (B, S, vocab), float32."""
    q = _round_through(quant)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_one(p, row, cfg, q) for row in tokens])


# ------------------------------------------------------ the selection bias


def balanced_bias(top: dict, layers: list[dict], cfg: dict, key) -> list[dict]:
    """``layers`` with every router's ``router_bias`` calibrated: the layers
    run over a seeded sample of sequences (at the default precision: the
    sample's load is all that is read), each layer's bias set from the
    scores of its own branch's input (`afmoe_ref.balance`, over all the
    router's outputs) before the branch's output goes on to the next."""
    c = {**CALIBRATION, **cfg.get("bias_calibration", {})}
    q = _round_through(None)
    sample = jax.random.randint(key, (c["sequences"], c["tokens"]), 0, cfg["vocab_size"])
    h = top["wte"][sample].astype(F32)
    out = []
    for lp in layers:
        a1, u = lax.map(lambda hs, lp=lp: _leave(hs, lp, cfg, q), h)
        flat = u.reshape(-1, u.shape[-1])
        lp = {**lp, "router_bias": balance(
            _route(flat, lp, cfg, q)[0], cfg["moe_topk"], steps=c["steps"],
            first_step=c["first_step"], last_step=c["last_step"])}
        out.append(lp)
        if len(out) == len(layers):
            return out         # nothing reads what the last layer adds
        m = _moe(flat, lp, cfg, q).reshape(u.shape)
        h = lax.map(lambda x, lp=lp: _rejoin(*x, lp, cfg, q), (a1, u, m))
