"""A decoder of gated grouped-query attention layers, windowed ones with
rope among full ones with no positions, between sandwich norms, over
sigmoid-routed experts, plainly: the forward pass in float32 `jax.numpy`.

Written from the published `config.json` keys of ``model_type: afmoe``
(`chipbench/configs/Trinity-Large-Preview.json` has them, and under
``assumed`` what the keys name without spelling out) and importing nothing
of the program under test.  ``x`` is a row of the residual stream, ``t`` its
position, ``D`` the hidden size, ``H`` query heads and ``G`` key/value heads
of ``d`` values:

- stream: ``h0 = sqrt(D) * E[token]`` (``mup_enabled``); for each layer ``h <-
  h + RMSNorm_2(Attn(RMSNorm_1(h)))``, then ``h <- h + RMSNorm_4(FF(RMSNorm_3
  (h)))``; logits ``= RMSNorm(h_L) @ W_head`` (untied).  RMSNorm: ``x /
  sqrt(mean(x^2) + rms_norm_eps) * g``.
- attention: ``q = x W_q`` as ``(H, d)``, ``k = x W_k``, ``v = x W_v`` as
  ``(G, d)``, no biases; ``q <- RMSNorm_d(q)``, ``k <- RMSNorm_d(k)`` (one gain
  of ``d`` values each, shared by the heads); on a ``sliding_attention``
  layer ``q, k`` rotated by ``t`` (rope, half-split pairs, base
  ``rope_theta``), on a ``full_attention`` layer no positions at all; ``s(t,
  j) = q(t) . k(j) / sqrt(d)`` over the causal ``j <= t``, on a sliding layer
  only ``t - sliding_window < j``; softmax; ``o = sum_j p(t, j) v(j)``, each
  key/value head shared by ``H / G`` query heads; ``y = (o * sigmoid(x W_g))
  W_o``, the gate ``H * d`` wide.
- feed-forward: layer ``l < num_dense_layers`` ``(silu(a) * b) W_out``, ``[a
  | b] = u W_in``, ``intermediate_size`` wide.  Later layers: ``sig =
  sigmoid(u W_r)`` (``router_experts`` of them), picks ``= top_k(sig + b,
  num_experts_per_tok)`` with ``b`` the selection bias, which enters no gate;
  gates ``g_j = route_scale * sig_j / sum over the picks of sig``
  (``route_norm``); ``sum_j g_j expert_j(u) + shared(u)``, both of the dense
  form at ``moe_intermediate_size``, the shared expert neither gated nor
  scaled.  Only the experts HELD here (``held_experts = [lo, hi)``) are
  computed, each over the tokens that picked it: the picks are sorted by
  expert and an expert walks its own stretch of them `EXPERT_ROWS` at a
  time.  What the absent experts would add is left out; the gates stay
  normalised over all the picks.

Every matrix product of `forward` runs under ``default_matmul_precision
("highest")``.  Weights keep the dtype they are given in and are cast to
float32 where they are used.  Attention walks the queries in blocks of
`BLOCK` and, for each, the keys in blocks, skipping a key block that lies
wholly past the diagonal or before the window (exact: its terms are zero);
the feed-forwards take `BLOCK` rows at a time; so a sequence of 24,576 fits
beside the weights.  ``quant`` rounds both operands of every matrix product
through a lower-precision type: the control of `correct`.

**The selection bias is calibrated, not drawn** (`init_parts`,
`balanced_bias`): a trained router is balanced, which is what the published
model's bias and its ``load_balance_coeff`` are for; a random one is not, and
which experts it favours follows the seed.  After the other weights are
drawn, these layers run over a seeded sample of tokens and, layer by layer,
``b`` is moved by the sign rule of the auxiliary-loss-free balancing (``b_e
<- b_e - u * sign(load_e - mean load)``, the step ``u`` falling) until the
sample's load is even.  It is a weight like any other: program and reference
are given the same ``b``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
BLOCK = 256         # queries, and keys, a block of the attention's walk; rows of a feed-forward's
EXPERT_ROWS = 512   # picks an expert computes at a time
# the calibration's sample (sequences x tokens) and steps, where the configuration gives none
CALIBRATION = {"sequences": 64, "tokens": 1024, "steps": 300, "first_step": 0.02,
               "last_step": 2e-4}


def sizes(cfg: dict) -> dict:
    """The sizes under short names, the configuration's keys checked
    against each other and against what this file computes."""
    lo, hi = cfg["held_experts"]
    assert hi - lo == cfg["num_experts"] and hi <= cfg["router_experts"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"]
    assert set(cfg["layer_types"]) <= {"full_attention", "sliding_attention"}
    told = (cfg["score_func"], cfg["route_norm"], cfg["mup_enabled"], cfg["n_group"],
            cfg["topk_group"], cfg["rope_scaling"], cfg["tie_word_embeddings"], cfg["hidden_act"])
    if told != ("sigmoid", True, True, 1, 1, None, False, "silu"):
        raise ValueError(f"this file computes one form of these keys, not {told}")
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "G": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "dense": cfg["num_dense_layers"], "eps": cfg["rms_norm_eps"]}


def init_parts(key, cfg: dict, dtype=F32) -> tuple[dict, list[dict]]:
    """Seeded weights (the configuration's ``assumed.weights`` says why
    each): matrices normal(0, ``initializer_range``) but the routed
    experts' output projections normal(0, ``expert_out_initializer_range``);
    norm gains one but the two output norms' of each layer
    ``sandwich_out_gain``; the selection bias calibrated to an even load
    over a seeded sample (`balanced_bias`).  Router and bias are float32
    whatever ``dtype``."""
    z = sizes(cfg)
    D, H, G, d, V = z["D"], z["H"], z["G"], z["d"], cfg["vocab_size"]
    std = cfg.get("initializer_range", 0.02)
    std_out = cfg.get("expert_out_initializer_range", std)
    out_gain = cfg.get("sandwich_out_gain", 1.0)
    n = lambda k, shape, dt=dtype, s=std: (jax.random.normal(k, shape, F32) * s).astype(dt)  # noqa: E731
    ones = lambda m, g=1.0: jnp.full((m,), g, dtype)  # noqa: E731

    def layer(at, k):
        ks = jax.random.split(k, 11)
        p = {"ln1": ones(D), "ln1_out": ones(D, out_gain), "ln2": ones(D), "ln2_out": ones(D, out_gain),
             "mixer": {"wq": n(ks[0], (D, H * d)), "wk": n(ks[1], (D, G * d)),
                       "wv": n(ks[2], (D, G * d)), "wg": n(ks[3], (D, H * d)),
                       "wo": n(ks[4], (H * d, D)), "q_norm": ones(d), "k_norm": ones(d)}}
        if at < z["dense"]:
            W = cfg["intermediate_size"]
            return {**p, "ff_in": n(ks[5], (D, 2 * W)), "ff_out": n(ks[6], (W, D))}
        W, held, E = cfg["moe_intermediate_size"], cfg["num_experts"], cfg["router_experts"]
        shared = W * cfg["num_shared_experts"]
        return {**p, "router": n(ks[5], (D, E), F32), "router_bias": jnp.zeros((E,), F32),
                "experts_in": n(ks[6], (held, D, 2 * W)), "experts_out": n(ks[7], (held, W, D), s=std_out),
                "shared_in": n(ks[8], (D, 2 * shared)), "shared_out": n(ks[9], (shared, D))}

    k_wte, k_head, k_sample, *k_layers = jax.random.split(key, cfg["num_hidden_layers"] + 3)
    top = {"wte": n(k_wte, (V, D)), "lnf": ones(D), "head": n(k_head, (D, V))}
    layers = [layer(at, k) for at, k in enumerate(k_layers)]
    return top, balanced_bias(top, layers, cfg, k_sample)


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


def _attention(x, p, cfg, q, *, window=None):
    """One attention layer over ``x (S, hidden)``, ``S`` a multiple of
    `BLOCK`; with ``window`` the sliding kind (rope, the band), without it
    the full kind (no positions)."""
    z = sizes(cfg)
    S, H, G, d = x.shape[0], z["H"], z["G"], z["d"]
    w = lambda name: q(p[name].astype(F32))  # noqa: E731
    qs = _rms_norm((q(x) @ w("wq")).reshape(S, H, d), p["q_norm"], z["eps"])
    ks = _rms_norm((q(x) @ w("wk")).reshape(S, G, d), p["k_norm"], z["eps"])
    vs = (q(x) @ w("wv")).reshape(S, G, d)
    if window is not None:
        qs, ks = _rope(qs, float(cfg["rope_theta"])), _rope(ks, float(cfg["rope_theta"]))
    scale = 1.0 / math.sqrt(d)
    keys = _blocks(q(ks)), _blocks(q(vs))

    def queries(blk):
        i, q_b = blk                                   # (BLOCK, G, H / G, d)
        t = i * BLOCK + jnp.arange(BLOCK)

        def one(j, carry):
            top, total, acc = carry
            k_b, v_b = keys[0][j], keys[1][j]
            at = j * BLOCK + jnp.arange(BLOCK)
            s = jnp.einsum("qgrd,kgd->grqk", q_b, k_b) * scale
            seen = at[None, :] <= t[:, None]
            if window is not None:
                seen &= at[None, :] > t[:, None] - window
            s = jnp.where(seen, s, -jnp.inf)
            new_top = jnp.maximum(top, s.max(-1))
            safe = jnp.where(jnp.isfinite(new_top), new_top, 0.0)
            e = jnp.exp(s - safe[..., None])
            keep = jnp.exp(jnp.where(jnp.isfinite(top), top, -jnp.inf) - safe)
            acc = acc * keep[..., None] + jnp.einsum("grqk,kgd->grqd", q(e), v_b)
            return new_top, total * keep + e.sum(-1), acc

        first = 0 if window is None else jnp.maximum(0, (i * BLOCK - window + 1) // BLOCK)
        lead = (G, H // G, BLOCK)
        start = (jnp.full(lead, -jnp.inf), jnp.zeros(lead), jnp.zeros(lead + (d,)))
        _, total, acc = lax.fori_loop(first, i + 1, one, start)
        return jnp.moveaxis(acc / total[..., None], 2, 0)     # (BLOCK, G, H / G, d)

    n = S // BLOCK
    o = lax.map(queries, (jnp.arange(n), _blocks(q(qs.reshape(S, G, H // G, d)))))
    o = o.reshape(S, H * d) * jax.nn.sigmoid(q(x) @ w("wg"))
    return q(o) @ w("wo")


def _gated(u, w_in, w_out, q):
    """``(silu(a) * b) W_out``, ``[a | b] = u W_in``, `BLOCK` rows at a time."""
    w_in, w_out = q(w_in.astype(F32)), q(w_out.astype(F32))

    def rows(u):
        a, b = jnp.split(q(u) @ w_in, 2, axis=-1)
        return q(jax.nn.silu(a) * b) @ w_out

    if u.shape[0] <= BLOCK or u.shape[0] % BLOCK:
        return rows(u)
    return lax.map(rows, _blocks(u)).reshape(u.shape)


def _route(u, p, cfg, q):
    """-> ``(sig (T, E), idx (T, k), gates (T, k))``: every expert's score,
    each token's picks and their gates."""
    sig = jax.nn.sigmoid(jnp.dot(q(u), q(p["router"]), precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(sig + p["router_bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(sig, idx, axis=-1)
    g = cfg["route_scale"] * picked
    if cfg["route_norm"]:
        g = g / picked.sum(-1, keepdims=True)
    return sig, idx, g


def _routed(u, p, cfg, q):
    """What the held experts give ``u (T, hidden)``: the picks sorted by
    expert, each held expert over its own stretch of them."""
    T, k = u.shape[0], cfg["num_experts_per_tok"]
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

    return lax.fori_loop(0, hi - lo, one_expert, jnp.zeros_like(u))


def _experts(u, p, cfg, q):
    return _routed(u, p, cfg, q) + _gated(u, p["shared_in"], p["shared_out"], q)


def _window(kind: str, cfg: dict):
    return cfg["sliding_window"] if kind == "sliding_attention" else None


def _mix(h, lp, kind, cfg, q):
    """The attention sublayer between its two norms, on the stream."""
    eps = cfg["rms_norm_eps"]
    a = _attention(_rms_norm(h, lp["ln1"], eps), lp["mixer"], cfg, q, window=_window(kind, cfg))
    return h + _rms_norm(a, lp["ln1_out"], eps)


def _one(p, tokens, cfg, q):
    """``tokens (S,)`` -> logits ``(S, vocab)``."""
    S = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, -S % BLOCK))       # pads lie after every real token
    eps = cfg["rms_norm_eps"]
    h = math.sqrt(cfg["hidden_size"]) * p["wte"][tokens].astype(F32)
    for kind, lp in zip(cfg["layer_types"], p["layers"]):
        h = _mix(h, lp, kind, cfg, q)
        u = _rms_norm(h, lp["ln2"], eps)
        f = _gated(u, lp["ff_in"], lp["ff_out"], q) if "ff_in" in lp else _experts(u, lp, cfg, q)
        h = h + _rms_norm(f, lp["ln2_out"], eps)
    return (q(_rms_norm(h, p["lnf"], eps)) @ q(p["head"].astype(F32)))[:S]


def forward(p: dict, tokens, cfg: dict, *, quant=None):
    """``tokens`` (B, S) int -> logits (B, S, vocab), float32."""
    q = _round_through(quant)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_one(p, row, cfg, q) for row in tokens])


# ------------------------------------------------------ the selection bias


def balance(sig, k: int, *, steps: int, first_step: float, last_step: float):
    """The bias ``b (E,)`` under which ``top_k(sig + b, k)`` spreads the
    rows of ``sig (T, E)`` evenly over the experts: ``steps`` moves of ``b_e
    <- b_e - u * sign(load_e - mean load)``, ``u`` falling geometrically
    from ``first_step`` to ``last_step``."""
    T, E = sig.shape
    rates = first_step * (last_step / first_step) ** (jnp.arange(steps) / max(steps - 1, 1))

    def move(b, rate):
        _, idx = lax.top_k(sig + b, k)
        load = jnp.zeros((E,), F32).at[idx.reshape(-1)].add(1.0)
        return b - rate * jnp.sign(load - T * k / E), None

    return lax.scan(move, jnp.zeros((E,), F32), rates)[0]


def balanced_bias(top: dict, layers: list[dict], cfg: dict, key) -> list[dict]:
    """``layers`` with every expert layer's ``router_bias`` calibrated: the
    layers above run over a seeded sample of sequences (at the default
    precision: the sample's load is all that is read), each expert layer's
    bias set from the scores of its own input (`balance`) before its output
    goes on to the next."""
    c = {**CALIBRATION, **cfg.get("bias_calibration", {})}
    eps, D, q = cfg["rms_norm_eps"], cfg["hidden_size"], _round_through(None)
    sample = jax.random.randint(key, (c["sequences"], c["tokens"]), 0, cfg["vocab_size"])
    h = math.sqrt(D) * top["wte"][sample].astype(F32)
    out = []
    for kind, lp in zip(cfg["layer_types"], layers):
        h = lax.map(lambda hs, lp=lp, kind=kind: _mix(hs, lp, kind, cfg, q), h)
        u = _rms_norm(h, lp["ln2"], eps)
        flat = u.reshape(-1, D)
        if "router" in lp:
            lp = {**lp, "router_bias": balance(
                _route(flat, lp, cfg, q)[0], cfg["num_experts_per_tok"], steps=c["steps"],
                first_step=c["first_step"], last_step=c["last_step"])}
        out.append(lp)
        if len(out) == len(layers):
            return out         # nothing reads what the last layer adds
        if "ff_in" in lp:
            f = lax.map(lambda us, lp=lp: _gated(us, lp["ff_in"], lp["ff_out"], q), u)
        else:
            shared = lax.map(lambda us, lp=lp: _gated(us, lp["shared_in"], lp["shared_out"], q), u)
            f = _routed(flat, lp, cfg, q).reshape(u.shape) + shared
        h = h + _rms_norm(f, lp["ln2_out"], eps)
