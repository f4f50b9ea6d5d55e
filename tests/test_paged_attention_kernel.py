"""`ops.paged_attention_decode` against the gathered view.

The decode programs lowered for a TPU attend in the pool through the
Pallas kernel; the plain read side, `serve.paged_kv._gathered_attention`
(what prefill runs, and decode on any other platform), is the reference:
same pools, same shuffled block tables, one query a slot at place
``length - 1``.  Interpreted on the CPU here; tests/test_hlo_structure.py
compiles it for the v5e.

What the kernel must not do is READ what its slot does not hold: the
kernel's pools carry NaN in the scratch block and in every block and
row past a slot's length that the kernel has no business fetching,
while the reference reads a clean copy (0 * NaN is NaN in both).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_dist import nn, ops
from tpu_dist.ops import paged_attention
from tpu_dist.serve import paged_kv
from tpu_dist.serve.paged_kv import _gathered_attention

BS, MB = 8, 6  # block_size, blocks a slot may hold: 48 places

LAYOUTS = {
    "mha2x64_row128": dict(heads=2, head_dim=64),
    "mha3x64_row192": dict(heads=3, head_dim=64),  # no multiple of 128 lanes
    "gqa8to2x128": dict(heads=8, kv_heads=2, head_dim=128),
    "window11_4x64": dict(heads=4, head_dim=64, sliding_window=11),
}

# 0 (inactive), 1, one block, one past a block edge, everything, ragged
LENGTHS = [0, 1, BS, BS + 1, MB * BS, 29, 0, 2 * BS]

CASES = [
    # (layout, dtype, lengths, CHUNK_TOKENS)
    *[(name, dt, LENGTHS, 256) for name in LAYOUTS
      for dt in ("float32", "bfloat16")],
    # several chunks a slot: two blocks a grid step, then one
    *[(name, "float32", LENGTHS, 2 * BS) for name in LAYOUTS],
    ("mha3x64_row192", "bfloat16", LENGTHS, BS),
    ("gqa8to2x128", "float32", [0] * 8, 256),            # nothing held
    ("mha2x64_row128", "float32", [MB * BS] * 8, 2 * BS),  # all full
    ("window11_4x64", "float32", [10, 11, 12, 24, 25, 40, 47, 48], BS),
]


def _case_id(case):
    name, dt, lengths, chunk = case
    kind = ("ragged" if lengths is LENGTHS else
            "empty" if not any(lengths) else
            "full" if len(set(lengths)) == 1 else "edges")
    return f"{name}-{dt}-{kind}-chunk{chunk}"


def _pools(rng, row, lengths, dtype, window=None):
    """Shuffled tables over a pool with one block a (slot, j); -> the
    clean pools, and the pools with NaN in every block that holds no
    place its slot can see."""
    S, N = len(lengths), len(lengths) * MB
    tables = rng.permutation(N).reshape(S, MB).astype(np.int32)
    clean = rng.normal(size=(2, N + 1, BS, row)).astype(np.float32)
    clean = np.asarray(jnp.asarray(clean, dtype).astype(jnp.float32))
    dirty = clean.copy()
    dirty[:, N] = np.nan  # the scratch block
    for s, n in enumerate(lengths):
        for j in range(-(-n // BS), MB):  # blocks past the slot's last
            dirty[:, tables[s, j]] = np.nan
        for j in range(max(n - window, 0) // BS if window else 0):
            dirty[:, tables[s, j]] = np.nan  # blocks before the window
    return tables, clean, dirty


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_kernel_matches_the_gathered_view(case, monkeypatch):
    name, dtype, lengths, chunk = case
    monkeypatch.setattr(paged_attention, "CHUNK_TOKENS", chunk)
    layout = dict(LAYOUTS[name])
    heads, hd = layout.pop("heads"), layout.pop("head_dim")
    attn = nn.MultiHeadAttention(heads * hd, heads, causal=True, **layout)
    rng = np.random.default_rng(len(name) + chunk)
    S, row = len(lengths), attn.kv_heads * hd
    tables, clean, dirty = _pools(rng, row, lengths, dtype,
                                  attn.sliding_window)
    q = jnp.asarray(rng.normal(size=(S, heads, 1, hd)), dtype)
    n = jnp.asarray(lengths, jnp.int32)

    got = jax.jit(lambda q, k, v: ops.paged_attention_decode(
        (q * attn.scale)[:, :, 0], k, v, tables, n,
        sliding_window=attn.sliding_window, interpret=True,
    ))(q, *jnp.asarray(dirty, dtype))
    assert got.shape == (S, heads, hd) and got.dtype == q.dtype
    got = np.asarray(got.astype(jnp.float32))

    def view(dt):
        o = _gathered_attention(
            (q * attn.scale).astype(dt), *jnp.asarray(clean, dt), tables,
            jnp.maximum(n, 1)[:, None] - 1,
            sliding_window=attn.sliding_window)
        return np.asarray(o.astype(jnp.float32))[:, :, 0]

    held = np.asarray(lengths) > 0
    assert np.isfinite(got).all()
    assert (got[~held] == 0).all()  # read nothing, wrote zeros
    exact = view(jnp.float32)  # on the same (rounded) numbers
    if dtype == "float32":
        np.testing.assert_allclose(got[held], exact[held], rtol=1e-5, atol=1e-5)
    elif held.any():
        # the dense path's own tolerance: what the gathered view loses in
        # bfloat16 (its statistics included; the kernel's are float32)
        dense = np.abs(view(jnp.bfloat16) - exact)[held].max()
        assert np.abs(got - exact)[held].max() <= max(dense, 2.0 ** -7)


@pytest.mark.parametrize("name", LAYOUTS)
def test_decode_step_is_the_same_through_either_read_side(name, monkeypatch):
    """`_paged_attention`'s decode step (``s == 1``) as lowered here, for
    the CPU, takes the gathered view; the same call with the kernel put
    in `_attend_in_pool`'s place, as a program lowered for the TPU has
    it: same output rows for the slots that write, same pools."""
    layout = dict(LAYOUTS[name])
    heads, hd = layout.pop("heads"), layout.pop("head_dim")
    attn = nn.MultiHeadAttention(heads * hd, heads, causal=True, **layout)
    rng = np.random.default_rng(len(name))
    S = len(LENGTHS)
    params, _ = attn.init(jax.random.PRNGKey(0), (S, 1, attn.dim))
    tables, clean, _ = _pools(rng, attn.kv_heads * hd, LENGTHS, "float32")
    x = jnp.asarray(rng.normal(size=(S, 1, attn.dim)), jnp.float32)
    n = np.asarray(LENGTHS)
    positions = jnp.asarray(np.maximum(n, 1)[:, None] - 1, jnp.int32)

    def step():
        return paged_kv._paged_attention(
            attn, params, x, *jnp.asarray(clean), tables, positions,
            jnp.asarray(n > 0)[:, None], BS)

    def lowered():  # a new function each time: jit's cache is by function
        return jax.jit(lambda: step()).lower().as_text()

    # the interpreter runs the kernel's grid as a loop; the view has none
    assert "stablehlo.while" not in lowered()
    y_view, k_view, v_view = step()
    monkeypatch.setattr(
        paged_kv, "_attend_in_pool",
        lambda *a, sliding_window: ops.paged_attention_decode(
            *a, sliding_window=sliding_window, interpret=True))
    assert "stablehlo.while" in lowered()
    y, k_pool, v_pool = step()
    np.testing.assert_allclose(np.asarray(y)[n > 0], np.asarray(y_view)[n > 0],
                               rtol=1e-5, atol=1e-5)
    assert (k_pool == k_view).all() and (v_pool == v_view).all()


@pytest.mark.parametrize("bad", ["pool_rows", "groups"])
def test_shapes_that_hold_no_heads_are_refused(bad):
    q = jnp.zeros((2, 3, 64))
    pool = jnp.zeros((5, BS, 100 if bad == "pool_rows" else 128))
    with pytest.raises(ValueError, match="heads"):
        ops.paged_attention_decode(q, pool, pool, jnp.zeros((2, 2), jnp.int32),
                                   jnp.ones((2,), jnp.int32), interpret=True)
