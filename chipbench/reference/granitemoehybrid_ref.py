"""A hybrid Mamba-2 / attention decoder over routed experts, plainly: the
forward pass in float32 `jax.numpy`.

Written from the published description of ``model_type:
granitemoehybrid`` (the released `config.json` keys name the sizes; the
mixer is Mamba-2, Dao & Gu 2024) and importing nothing of the program
under test.  ``x`` is a row of the residual stream:

- stream: ``h0 = embedding_multiplier * E[token]``; for each layer ``h <-
  h + residual_multiplier * mixer(RMSNorm(h))``, then ``h <- h +
  residual_multiplier * (routed(u) + shared(u))`` with ``u = RMSNorm(h)``;
  logits ``= RMSNorm(h) @ E^T / logits_scaling`` (tied table).  No
  positional encoding.  RMSNorm: ``x / sqrt(mean(x^2) + rms_norm_eps) * g``.
- Mamba-2 mixer (``layer_types[l] == "mamba"``; one group): ``[z | xBC |
  dt] = x @ W_in``; ``xBC <- silu(conv(xBC))``, a causal depthwise
  convolution of width ``mamba_d_conv`` over time, zero-padded on the left,
  with a bias; ``[xs | B | C] = xBC``; ``dt <- softplus(dt + dt_bias)`` per
  head; ``A = -exp(A_log)`` per head; per head a state ``S (d_head,
  d_state)``, ``S_t = exp(dt_t A) S_{t-1} + dt_t xs_t (outer) B_t``,
  ``y_t = S_t C_t + D xs_t``: a plain `lax.scan` over time, no chunks, no
  cache; ``y <- RMSNorm_g(y * silu(z))`` over all inner channels (the gate
  before the norm); ``y @ W_out``.
- attention (``"attention"``): bias-free q/k/v to ``num_attention_heads`` /
  ``num_key_value_heads`` heads, each key/value head repeated over its
  group of query heads, scores ``attention_multiplier * q k^T``, causal
  softmax, bias-free output projection.
- experts: ``r = u @ W_r`` (``router_experts`` logits), ``(v, idx) =
  top_k(r, num_experts_per_tok)``, ``g = softmax(v)`` over the picked
  logits, ``routed(u) = sum_j g_j expert_{idx_j}(u)`` with ``expert(u) =
  (silu(a) * b) @ W_out``, ``[a | b] = u @ W_in``: a loop over the experts
  HELD here (``held_experts = [lo, hi)``, ``num_local_experts`` of them),
  each computing every token and masked by the picks.  What the absent
  experts would add is left out; the gates stay normalised over all the
  picks.  ``shared(u)`` is the same form at ``shared_intermediate_size``.

Every matrix product runs under ``default_matmul_precision("highest")``.
Weights keep the dtype they are given in and are cast to float32 where
they are used (4.76 B parameters in float32 would not fit one chip beside
anything).  ``quant`` rounds both operands of every matrix product (the
linear maps, the router, the attention's two products, and the scan's
inputs ``xs``, ``B``, ``C``) through a lower-precision type: the control
of `correct`.  The carried state stays float32 in the control too: the
configuration fixes it at float32 whatever the compute dtype.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def sizes(cfg: dict) -> dict:
    """The derived sizes, by the configuration's own keys."""
    inner = cfg["mamba_expand"] * cfg["hidden_size"]
    assert inner == cfg["mamba_n_heads"] * cfg["mamba_d_head"], "mamba heads x d_head"
    lo, hi = cfg["held_experts"]
    assert hi - lo == cfg["num_local_experts"] and hi <= cfg["router_experts"]
    return {
        "inner": inner,
        "channels": inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
    }


def init_parts(key, cfg: dict, dtype=F32) -> tuple[dict, list[dict]]:
    """Seeded weights (the configuration file's ``assumed`` says why each):
    matrices normal(0, initializer_range); the four projections back into
    the residual stream normal(0, initializer_range * embedding_multiplier),
    so that ten layers outweigh the embedded token and the tied head does
    not just echo it; unit norm gains; the convolution uniform(+-1/sqrt(K))
    with a bias of the same; ``A = exp(A_log)`` uniform in [1, 16], ``dt``
    log-uniform in [1e-3, 1e-1] with ``dt_bias`` its inverse softplus, ``D``
    one, as Mamba-2 initialises them.  The router and the scan's three
    per-head vectors are float32 whatever ``dtype``."""
    D, V, sz = cfg["hidden_size"], cfg["vocab_size"], sizes(cfg)
    std = cfg.get("initializer_range", 0.02)
    back = std * cfg["embedding_multiplier"]
    nh, N, K = cfg["mamba_n_heads"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    H, W, Ws = cfg["num_local_experts"], cfg["intermediate_size"], cfg["shared_intermediate_size"]
    hd, kvh = sz["head_dim"], cfg["num_key_value_heads"]
    n = lambda k, shape, s, dt=dtype: (jax.random.normal(k, shape, F32) * s).astype(dt)  # noqa: E731
    ones = lambda d: jnp.ones((d,), dtype)  # noqa: E731

    def mixer(kind, k):
        ks = jax.random.split(k, 6)
        if kind == "attention":
            return {"wq": n(ks[0], (D, D), std), "wk": n(ks[1], (D, kvh * hd), std),
                    "wv": n(ks[2], (D, kvh * hd), std), "wo": n(ks[3], (D, D), back)}
        u = lambda k_, shape: jax.random.uniform(k_, shape, F32, -1.0, 1.0) / math.sqrt(K)  # noqa: E731
        dt = jnp.exp(jax.random.uniform(ks[4], (nh,), F32) * math.log(100.0) + math.log(1e-3))
        return {
            "in_proj": n(ks[0], (D, sz["inner"] + sz["channels"] + nh), std),
            "conv_w": u(ks[1], (sz["channels"], K)).astype(dtype),
            "conv_b": u(ks[2], (sz["channels"],)).astype(dtype),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(ks[5], (nh,), F32, 1.0, 16.0)),
            "D": jnp.ones((nh,), F32),
            "norm": ones(sz["inner"]),
            "out_proj": n(ks[3], (sz["inner"], D), back),
        }

    def layer(kind, k):
        ks = jax.random.split(k, 6)
        return {
            "ln1": ones(D), "mixer": mixer(kind, ks[0]), "ln2": ones(D),
            "router": n(ks[1], (D, cfg["router_experts"]), std, F32),
            "experts_in": n(ks[2], (H, D, 2 * W), std),
            "experts_out": n(ks[3], (H, W, D), back),
            "shared_in": n(ks[4], (D, 2 * Ws), std),
            "shared_out": n(ks[5], (Ws, D), back),
        }

    kinds = cfg["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"]
    k_wte, *k_layers = jax.random.split(key, len(kinds) + 1)
    top = {"wte": n(k_wte, (V, D), std), "lnf": ones(D)}
    return top, [layer(kind, k) for kind, k in zip(kinds, k_layers)]


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


def _mamba(x, p, cfg, q):
    B_, S, _ = x.shape
    sz = sizes(cfg)
    nh, hd, N, K = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    zxbcdt = q(x) @ q(p["in_proj"].astype(F32))
    z, xbc, dt = jnp.split(zxbcdt, [sz["inner"], sz["inner"] + sz["channels"]], axis=-1)
    padded = jnp.pad(xbc, [(0, 0), (K - 1, 0), (0, 0)])
    w = p["conv_w"].astype(F32)
    xbc = p["conv_b"].astype(F32) + sum(padded[:, j:j + S] * w[:, j] for j in range(K))
    xs, Bm, Cm = jnp.split(jax.nn.silu(xbc), [sz["inner"], sz["inner"] + N], axis=-1)
    xs = q(xs).reshape(B_, S, nh, hd)
    Bm, Cm = q(Bm), q(Cm)
    dt = jax.nn.softplus(dt + p["dt_bias"])          # (B, S, nh)
    A = -jnp.exp(p["A_log"])

    def step(state, t):
        xs_t, b_t, c_t, dt_t = t
        decay = jnp.exp(dt_t * A)                     # (B, nh)
        state = decay[..., None, None] * state + (
            (dt_t[..., None] * xs_t)[..., None] * b_t[:, None, None, :])
        return state, (state * c_t[:, None, None, :]).sum(-1)

    over_time = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    _, y = jax.lax.scan(step, jnp.zeros((B_, nh, hd, N), F32),
                        tuple(over_time(a) for a in (xs, Bm, Cm, dt)))
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * xs
    y = _rms_norm(y.reshape(B_, S, sz["inner"]) * jax.nn.silu(z), p["norm"], cfg["rms_norm_eps"])
    return q(y) @ q(p["out_proj"].astype(F32))


def _attention(x, p, cfg, q):
    B_, S, D = x.shape
    H, kvh, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], sizes(cfg)["head_dim"]
    heads = lambda w, n: (q(x) @ q(w.astype(F32))).reshape(B_, S, n, hd).transpose(0, 2, 1, 3)  # noqa: E731
    qh, kh, vh = heads(p["wq"], H), heads(p["wk"], kvh), heads(p["wv"], kvh)
    kh, vh = (jnp.repeat(t, H // kvh, axis=1) for t in (kh, vh))
    scores = cfg["attention_multiplier"] * jnp.einsum("bhqd,bhkd->bhqk", q(qh), q(kh))
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", q(jax.nn.softmax(scores, axis=-1)), q(vh))
    return q(o.transpose(0, 2, 1, 3).reshape(B_, S, D)) @ q(p["wo"].astype(F32))


def _gated(u, w_in, w_out, q):
    ab = q(u) @ q(w_in.astype(F32))
    a, b = jnp.split(ab, 2, axis=-1)
    return q(jax.nn.silu(a) * b) @ q(w_out.astype(F32))


def _experts(u, p, cfg, q):
    lo, _ = cfg["held_experts"]
    r = q(u) @ q(p["router"])
    v, idx = jax.lax.top_k(r, cfg["num_experts_per_tok"])
    g = jax.nn.softmax(v, axis=-1)

    def one(acc, e):
        w_in, w_out, number = e
        gate = (g * (idx == number)).sum(-1)          # 0 where not picked
        return acc + gate[..., None] * _gated(u, w_in, w_out, q), None

    held = (p["experts_in"], p["experts_out"], lo + jnp.arange(p["experts_in"].shape[0]))
    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), held)
    return routed + _gated(u, p["shared_in"], p["shared_out"], q)


def forward(p: dict, tokens, cfg: dict, *, quant=None):
    """``tokens`` (B, S) int -> logits (B, S, vocab), float32."""
    q = _round_through(quant)
    rm, eps = cfg["residual_multiplier"], cfg["rms_norm_eps"]
    mixers = {"mamba": _mamba, "attention": _attention}
    with jax.default_matmul_precision("highest"):
        h = cfg["embedding_multiplier"] * p["wte"][tokens].astype(F32)
        for kind, lp in zip(cfg["layer_types"], p["layers"]):
            h = h + rm * mixers[kind](_rms_norm(h, lp["ln1"], eps), lp["mixer"], cfg, q)
            h = h + rm * _experts(_rms_norm(h, lp["ln2"], eps), lp, cfg, q)
        h = _rms_norm(h, p["lnf"], eps)
        return q(h) @ q(p["wte"].astype(F32)).T / cfg["logits_scaling"]
