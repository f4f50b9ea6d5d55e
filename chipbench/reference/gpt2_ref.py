"""GPT-2, plainly: forward, loss, gradients and Adam in float32 `jax.numpy`.

Written from the published description (Radford et al. 2019, "Language
Models are Unsupervised Multitask Learners"; the released `config.json`
keys name the sizes) and importing nothing of the program under test.
Pre-norm blocks, learned positions, fused q/k/v projection split in the
order q|k|v with heads laid out contiguously, causal softmax attention
scaled by 1/sqrt(head), GELU in its tanh form (`gelu_new`), final
LayerNorm, output head tied to the token embedding.  Every matrix product
runs under ``default_matmul_precision("highest")``: on a TPU a float32
product otherwise runs in bfloat16 passes.

Departure, noted: the LayerNorm epsilon is a parameter.  The published
value is 1e-5; the configuration files state the value that is run.

Parameters are one flat dict of arrays with the blocks stacked on a
leading layer axis (``lax.scan`` walks them, so a 48-layer model compiles
as one block).  ``quant`` rounds both operands of every matrix product
through a lower-precision type: that is the control of `correct`, the
reference put in the program's place one precision step down.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

STACKED = (
    "ln1_g", "ln1_b", "attn_w", "attn_b", "proj_w", "proj_b",
    "ln2_g", "ln2_b", "fc_w", "fc_b", "fc2_w", "fc2_b",
)


def init_parts(key, cfg: dict, dtype=jnp.float32) -> tuple[dict, list[dict]]:
    """Seeded weights as GPT-2 initialises them: normal(0, initializer_range)
    for every matrix and embedding (0.02 as published), the two residual
    projections scaled by 1/sqrt(2 * n_layer), zero biases, unit LayerNorm
    gains.  Returned as the model-wide arrays and one dict per block, each
    block from a key of its own."""
    L, D, V, S = cfg["n_layer"], cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    std = cfg.get("initializer_range", 0.02)
    rstd = std / math.sqrt(2 * L)
    n = lambda k, shape, s: (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)  # noqa: E731
    ones = lambda *shape: jnp.ones(shape, dtype)  # noqa: E731
    zeros = lambda *shape: jnp.zeros(shape, dtype)  # noqa: E731
    k_wte, k_wpe, k_blocks = jax.random.split(key, 3)

    def block(k):
        ks = jax.random.split(k, 4)
        return {
            "ln1_g": ones(D), "ln1_b": zeros(D),
            "attn_w": n(ks[0], (D, 3 * D), std), "attn_b": zeros(3 * D),
            "proj_w": n(ks[1], (D, D), rstd), "proj_b": zeros(D),
            "ln2_g": ones(D), "ln2_b": zeros(D),
            "fc_w": n(ks[2], (D, 4 * D), std), "fc_b": zeros(4 * D),
            "fc2_w": n(ks[3], (4 * D, D), rstd), "fc2_b": zeros(D),
        }

    top = {
        "wte": n(k_wte, (V, D), std), "wpe": n(k_wpe, (S, D), std),
        "lnf_g": ones(D), "lnf_b": zeros(D),
    }
    return top, [block(k) for k in jax.random.split(k_blocks, L)]


def init(key, cfg: dict, dtype=jnp.float32) -> dict:
    """`init_parts` in the layout `forward` takes: blocks stacked."""
    top, blocks = init_parts(key, cfg, dtype)
    return {**top, **{k: jnp.stack([b[k] for b in blocks]) for k in STACKED}}


def _round_through(dtype):
    if dtype is None:
        return lambda x: x
    # straight-through: the value is rounded, the gradient passes as it is
    # (a cotangent cast to an 8-bit float would underflow to zero)
    return lambda x: x + jax.lax.stop_gradient(x.astype(dtype).astype(jnp.float32) - x)


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def _block(h, lp, cfg, q):
    B, S, D = h.shape
    H = cfg["n_head"]
    hd = D // H
    eps = cfg["layer_norm_epsilon"]
    x = _layer_norm(h, lp["ln1_g"], lp["ln1_b"], eps)
    qkv = q(x) @ q(lp["attn_w"]) + lp["attn_b"]
    qh, kh, vh = (
        qkv[..., i * D:(i + 1) * D].reshape(B, S, H, hd).transpose(0, 2, 1, 3)
        for i in range(3)
    )
    scores = jnp.einsum("bhqd,bhkd->bhqk", q(qh), q(kh)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", q(att), q(vh))
    o = o.transpose(0, 2, 1, 3).reshape(B, S, D)
    h = h + q(o) @ q(lp["proj_w"]) + lp["proj_b"]
    x = _layer_norm(h, lp["ln2_g"], lp["ln2_b"], eps)
    x = _gelu_new(q(x) @ q(lp["fc_w"]) + lp["fc_b"])
    return h + q(x) @ q(lp["fc2_w"]) + lp["fc2_b"]


def forward(p: dict, tokens, cfg: dict, *, quant=None, remat: bool = False):
    """``tokens`` (B, S) int -> logits (B, S, vocab), float32."""
    q = _round_through(quant)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        S = tokens.shape[1]
        h = p["wte"][tokens] + p["wpe"][:S]
        block = (lambda h_, lp: _block(h_, lp, cfg, q))
        if remat:
            block = jax.checkpoint(block)
        h, _ = jax.lax.scan(
            lambda h_, lp: (block(h_, lp), None), h, {k: p[k] for k in STACKED}
        )
        h = _layer_norm(h, p["lnf_g"], p["lnf_b"], cfg["layer_norm_epsilon"])
        return q(h) @ q(p["wte"]).T


def loss(p: dict, tokens, cfg: dict, *, quant=None, remat: bool = False):
    """Mean next-token cross-entropy over every row and position."""
    logits = forward(p, tokens, cfg, quant=quant, remat=remat)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean()


def loss_and_grad(p: dict, rows, cfg: dict, *, quant=None):
    """Loss and gradient of one batch given as ``(blocks, rows, S)``: block
    by block with rematerialised layers, so that a batch the program takes
    whole fits beside float32 state.  The loss is the mean over blocks of
    equal size, the gradient the mean of the blocks' gradients."""
    vg = jax.value_and_grad(lambda p_, t: loss(p_, t, cfg, quant=quant, remat=True))

    def body(acc, blk):
        l, g = vg(p, blk)
        return jax.tree.map(jnp.add, acc, g), l

    gsum, losses = jax.lax.scan(body, jax.tree.map(jnp.zeros_like, p), rows)
    n = rows.shape[0]
    return losses.mean(), jax.tree.map(lambda g: g / n, gsum)


def adam_init(p: dict) -> dict:
    z = lambda: jax.tree.map(jnp.zeros_like, p)  # noqa: E731
    return {"t": jnp.zeros((), jnp.int32), "m": z(), "v": z()}


def adam_update(p: dict, g: dict, st: dict, *, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Adam (Kingma & Ba 2015, algorithm 1), no weight decay."""
    t = st["t"] + 1
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, st["m"], g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, st["v"], g)
    c1 = 1 - b1 ** t.astype(jnp.float32)
    c2 = 1 - b2 ** t.astype(jnp.float32)
    new = jax.tree.map(
        lambda p_, m_, v_: p_ - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps), p, m, v
    )
    return new, {"t": t, "m": m, "v": v}


def leaf_norms(tree: dict) -> dict:
    """Euclidean norm of every leaf, one per layer for the stacked ones:
    name -> (layers,) or () array.  The unit in which gradients and
    parameter changes are compared "by the worst leaf"."""
    out = {}
    for k, a in tree.items():
        a = a.astype(jnp.float32)
        axes = tuple(range(1, a.ndim)) if k in STACKED else None
        out[k] = jnp.sqrt(jnp.sum(a * a, axis=axes))
    return out
