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
from jax import lax

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


def _assert_is_the_view(got, attn, q, clean, tables, lengths, dtype):
    """The kernel's rows ``got`` against the gathered view over the clean
    pools: zeros for a slot that holds nothing, else the view's numbers."""
    n = jnp.asarray(lengths, jnp.int32)

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
    _assert_is_the_view(got, attn, q, clean, tables, lengths, dtype)


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


def _plain_schedule(block_tables, lengths, bs, G, window):
    """The grid's tables laid out one (step, operand) place at a time, over
    ``slots * ceil(max_blocks / G)`` steps whatever a window can reach: the
    form `paged_attention._schedule` had before it was laid out by rows,
    kept as its plain reference.  Also -> which places are held."""
    S, MB = block_tables.shape
    first = (jnp.maximum(lengths - window, 0) // bs if window is not None
             else jnp.zeros_like(lengths))
    last = (lengths + bs - 1) // bs
    chunks = (last - first + G - 1) // G
    ends = jnp.cumsum(chunks)
    t = jnp.arange(S * -(-MB // G), dtype=jnp.int32)
    slot = jnp.minimum(
        (t[:, None] >= ends[None, :]).sum(axis=1, dtype=jnp.int32), S - 1)
    chunk = t - (ends - chunks)[slot]
    j = (first[slot][:, None] + chunk[:, None] * G
         + jnp.arange(G, dtype=jnp.int32))
    held = (j < last[slot][:, None]) & (t < ends[-1])[:, None]
    ids = block_tables[slot[:, None], jnp.minimum(j, MB - 1)]
    fetched = lax.cummax(jnp.where(held, t[:, None], -1), axis=0)
    ids = jnp.where(
        fetched >= 0,
        jnp.take_along_axis(ids, jnp.maximum(fetched, 0), axis=0),
        ids[0, 0])
    return (jnp.maximum(ends[-1], 1), slot, chunk, ids.reshape(-1), first,
            chunks), held


def _assert_same_grid(tables, lengths, bs, G, window):
    """`_schedule`'s tables are the plain ones for every step of the grid,
    and an un-held place names what its operand named one step earlier
    (the pipeline then fetches nothing for it)."""
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    want, held = _plain_schedule(tables, lengths, bs, G, window)
    got = paged_attention._schedule(tables, lengths, bs, G, window)
    steps = int(want[0])
    assert int(got[0]) == steps <= got[1].shape[0] == got[2].shape[0]
    assert got[3].shape[0] == got[1].shape[0] * G
    for name, a, b, n in (("slot", got[1], want[1], steps),
                          ("chunk", got[2], want[2], steps),
                          ("ids", got[3], want[3], steps * G),
                          ("first", got[4], want[4], None),
                          ("chunks", got[5], want[5], None)):
        np.testing.assert_array_equal(np.asarray(a)[:n], np.asarray(b)[:n],
                                      err_msg=name)
    ids = np.asarray(got[3])[:steps * G].reshape(steps, G)
    before = np.concatenate([np.full((1, G), ids[0, 0]), ids[:-1]])
    loose = ~np.asarray(held)[:steps]
    np.testing.assert_array_equal(ids[loose], before[loose])
    return got


SCHEDULE_BS = 4
# (G, max_blocks): the table narrower than a chunk, one chunk wide, no
# whole number of chunks
SCHEDULE_SHAPES = [(G, MB) for G in (1, 2, 8, 16)
                   for MB in sorted({max(G - 1, 1), G, 2 * G + 1})]


def _window(kind, G, MB):
    return {"none": None, "in_a_block": SCHEDULE_BS - 1,
            "in_a_chunk": G * SCHEDULE_BS - 1 if G > 1 else SCHEDULE_BS + 1,
            "in_the_table": max(MB * SCHEDULE_BS - 2 * SCHEDULE_BS, 1),
            }[kind]


@pytest.mark.parametrize("lengths", ["ragged", "empty", "one_slot"])
@pytest.mark.parametrize("window", ["none", "in_a_block", "in_a_chunk",
                                    "in_the_table"])
@pytest.mark.parametrize("G,MB", SCHEDULE_SHAPES)
def test_schedule_is_the_plain_one(G, MB, window, lengths):
    rng = np.random.default_rng(G * 100 + MB)
    S = 1 if lengths == "one_slot" else 7
    tables = rng.permutation(S * MB).reshape(S, MB)
    places = MB * SCHEDULE_BS
    for _ in range(3):
        n = rng.integers(0, places + 1, S)
        if lengths == "empty":
            n[:] = 0
        elif lengths == "ragged":  # zeros among them, a full one, edges
            n[rng.integers(S)] = places
            n[rng.random(S) < 0.3] = 0
            n[rng.integers(S)] = min(SCHEDULE_BS + 1, places)
        _assert_same_grid(tables, n, SCHEDULE_BS, G, _window(window, G, MB))


RING = dict(window=4096, chunk=256, bs=16, max_blocks=1536)  # a long-context server's


@pytest.mark.parametrize("lengths", [
    "ragged", "empty", "full", "at_the_wrap"])
def test_schedule_of_a_ring_is_bounded_by_its_window(lengths):
    """A ring's table is as wide as the pool's (writes index it by
    position), 272 blocks repeating over 1,536 columns; its schedule is
    laid out over what the window can reach, 17 chunks a slot, and is the
    plain one's for every step of the grid."""
    bs, W, MB = RING["bs"], RING["window"], RING["max_blocks"]
    blocks = paged_kv.ring_blocks(W, RING["chunk"], bs)
    assert blocks == 272
    S, G = 5, paged_attention.CHUNK_TOKENS // bs
    tables = paged_kv.ring_tables(None, S, blocks, MB)
    n = {"ragged": np.array([0, 3, W - 1, 9000, MB * bs]),
         "empty": np.zeros(S, int),
         "full": np.full(S, MB * bs),
         "at_the_wrap": blocks * bs + np.arange(-2, 3) * (bs - 1),
         }[lengths]
    got = _assert_same_grid(tables, n, bs, G, W)
    assert got[1].shape[0] == S * 17 < S * MB // G


# before, at and past the ring's wrap (its 4,352 rows), and a table's end
RING_LENGTHS = [0, 1, 4095, 4096, 4097, 4351, 4352, 4353, 4352 + 4096 + 7, 24576]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_reads_a_ring_as_the_gathered_view_does(dtype):
    """The kernel under a ring's wrapping table and the window-bounded
    schedule, against the gathered view through the same table: the rings
    carry NaN wherever a slot's window does not reach."""
    bs, W, MB = RING["bs"], RING["window"], RING["max_blocks"]
    blocks = paged_kv.ring_blocks(W, RING["chunk"], bs)
    attn = nn.MultiHeadAttention(256, 2, causal=True, kv_heads=1, head_dim=128,
                                 sliding_window=W)
    S, row = len(RING_LENGTHS), attn.kv_heads * attn.head_dim
    rng = np.random.default_rng(11)
    tables = np.asarray(paged_kv.ring_tables(None, S, blocks, MB))
    clean = rng.normal(size=(2, S * blocks + 1, bs, row)).astype(np.float32)
    clean = np.asarray(jnp.asarray(clean, dtype).astype(jnp.float32))
    dirty = np.full_like(clean, np.nan)
    for s, n in enumerate(RING_LENGTHS):
        for j in range(max(n - W, 0) // bs, -(-n // bs)):  # the visible blocks
            dirty[:, tables[s, j]] = clean[:, tables[s, j]]
    q = jnp.asarray(rng.normal(size=(S, 2, 1, 128)), dtype)
    n = jnp.asarray(RING_LENGTHS, jnp.int32)

    got = jax.jit(lambda q, k, v: ops.paged_attention_decode(
        (q * attn.scale)[:, :, 0], k, v, tables, n, sliding_window=W,
        interpret=True))(q, *jnp.asarray(dirty, dtype))
    _assert_is_the_view(np.asarray(got.astype(jnp.float32)), attn, q, clean,
                        tables, RING_LENGTHS, dtype)


# ------------------------------------------------------- the latent pool


def _latent_case(rng, lengths, dtype, *, heads=4, kv_rank=128, nope=16, rope=8):
    """A `nn.LatentAttention` of ``kv_rank`` (the value: whole 128-lane
    tiles) and a pool of its rows padded to 256 lanes, with NaN in every
    block a slot does not hold; -> what the kernel and `absorbed` take."""
    from tpu_dist.nn.latent_attention import LatentAttention

    attn = LatentAttention(64, heads, q_rank=32, kv_rank=kv_rank, nope_dim=nope, rope_dim=rope,
                           v_dim=16, rope_base=1e4, gated=False)
    p = jax.tree.map(lambda a: jnp.asarray(a, dtype), attn.init(jax.random.key(3))[0])
    S = len(lengths)
    width = paged_kv._whole_tiles(attn.row)
    tables, clean, dirty = _pools(rng, width, lengths, dtype)
    clean, dirty = clean[0].copy(), dirty[0].copy()
    clean[..., attn.row:] = 0.0    # the pad lanes of a written row are zero
    dirty[..., attn.row:] = np.where(np.isnan(dirty[..., attn.row:]), np.nan, 0.0)
    q_n = jnp.asarray(rng.normal(size=(S, 1, heads, nope)), dtype)
    q_r = jnp.asarray(rng.normal(size=(S, 1, heads, rope)), dtype)
    return attn, p, tables, clean, dirty, q_n, q_r


def _selection(rng, lengths):
    """A mask ``(S, MB * BS)`` as a selecting layer's picks would give:
    over `LENGTHS`, a slot shorter than the selection (everything kept), one
    that keeps nothing, one whose first chunks keep nothing, and places
    kept past a slot's length, which get no weight all the same."""
    keep = rng.random((len(lengths), MB * BS)) < 0.4
    keep[[1, 6, 7]] = True
    keep[2] = False
    keep[4, :20] = False
    keep[5, 29:] = True
    return keep


@pytest.mark.parametrize("selected", [False, True], ids=["held", "selected"])
@pytest.mark.parametrize("chunk", [256, 2 * BS, BS])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_kernel_matches_absorbed_over_the_gathered_view(dtype, chunk, selected,
                                                               monkeypatch):
    """`ops.paged_latent.paged_latent_decode` (interpreted) against
    `LatentAttention.absorbed` over the gathered view: ragged lengths, an
    empty slot, a length that ends mid-block, several chunks a slot; the
    kernel's pool carries NaN wherever a slot holds nothing.  ``selected``:
    under a selection's mask (`_selection`), which the absorbed form takes
    as its visible places; a slot that keeps nothing reads zeros.  float32
    to rounding; bfloat16 within what the absorbed form itself loses there."""
    from tpu_dist.ops import paged_latent

    monkeypatch.setattr(paged_latent, "CHUNK_TOKENS", chunk)
    rng = np.random.default_rng(chunk)
    attn, p, tables, clean, dirty, q_n, q_r = _latent_case(rng, LENGTHS, dtype)
    n = jnp.asarray(LENGTHS, jnp.int32)
    S, L = len(LENGTHS), MB * BS
    keep = _selection(rng, LENGTHS) if selected else None
    q = jnp.concatenate([jnp.einsum("shd,hdr->shr", q_n[:, 0], p["w_uk"]), q_r[:, 0]], axis=-1)
    got = jax.jit(lambda q, pool: paged_latent.paged_latent_decode(
        paged_kv._padded(q, pool.shape[-1]), pool, tables, n, v_width=attn.kv_rank,
        scale=attn.scale, keep=None if keep is None else jnp.asarray(keep),
        interpret=True))(q, jnp.asarray(dirty, dtype))
    got = np.asarray(jnp.einsum("shr,hrd->shd", got, p["w_uv"]).astype(jnp.float32))

    def absorbed(dt):
        pd = jax.tree.map(lambda a: a.astype(dt), p)
        rows = jnp.asarray(clean, dt)[tables].reshape(S, L, -1)[..., :attn.row]
        visible = jnp.arange(L)[None, None, :] < jnp.maximum(n, 1)[:, None, None]
        if selected:
            visible &= keep[:, None]
        return np.asarray(attn.absorbed(pd, q_n.astype(dt), q_r.astype(dt), rows, visible)
                          .astype(jnp.float32))[:, 0]

    held = np.asarray(LENGTHS) > 0
    if selected:
        held &= (keep & (np.arange(L) < np.asarray(LENGTHS)[:, None])).any(axis=1)
        assert held.sum() == 5
    assert np.isfinite(got).all() and (got[~held] == 0).all()
    exact = absorbed(jnp.float32)
    if dtype == "float32":
        np.testing.assert_allclose(got[held], exact[held], rtol=1e-5, atol=1e-5)
    else:
        lost = np.abs(absorbed(jnp.bfloat16) - exact)[held].max()
        assert np.abs(got - exact)[held].max() <= max(2 * lost, 2.0 ** -7)
    assert np.abs(exact[held]).max() > 0.05


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_kernel_without_a_selection_is_the_kernel_it_was(dtype, monkeypatch):
    """With ``keep=None`` the `pallas_call` has the operands it had before
    the kernel took a selection (the grid's bound, six tables, the queries
    and ``G`` blocks: no mask) and its result is, bit for bit, that under a
    mask that keeps every place: the same arithmetic on the same values."""
    from tpu_dist.ops import paged_latent

    monkeypatch.setattr(paged_latent, "CHUNK_TOKENS", 2 * BS)
    rng = np.random.default_rng(5)
    attn, p, tables, _, dirty, q_n, q_r = _latent_case(rng, LENGTHS, dtype)
    q = jnp.concatenate([jnp.einsum("shd,hdr->shr", q_n[:, 0], p["w_uk"]), q_r[:, 0]], axis=-1)
    q, pool = paged_kv._padded(q, dirty.shape[-1]), jnp.asarray(dirty, dtype)
    n = jnp.asarray(LENGTHS, jnp.int32)

    def run(keep, **how):
        return lambda q, pool: paged_latent.paged_latent_decode(
            q, pool, tables, n, v_width=attn.kv_rank, scale=attn.scale, keep=keep, **how)

    everything = jnp.ones((len(LENGTHS), MB * BS), bool)
    plain, masked = (np.asarray(jax.jit(run(keep, interpret=True))(q, pool).astype(jnp.float32))
                     for keep in (None, everything))
    assert np.array_equal(plain, masked) and np.abs(plain).max() > 0.05

    def operands(keep):
        text = jax.jit(run(keep)).trace(q, pool).lower(lowering_platforms=("tpu",)).as_text()
        call, = [line for line in text.splitlines() if "tpu_custom_call" in line]
        return call.split("@tpu_custom_call(")[1].split(")")[0].count("%")

    G = 2
    assert (operands(None), operands(everything)) == (1 + 6 + 1 + G, 1 + 6 + 1 + 1 + G)


def test_latent_decode_step_is_the_same_through_either_read_side(monkeypatch):
    """`serve.paged_kv._whole_latent_attention`'s decode step through the
    interpreted kernel and through the gathered view, pool and all."""
    from tpu_dist.ops import paged_latent

    rng = np.random.default_rng(11)
    attn, p, tables, clean, _, _, _ = _latent_case(rng, LENGTHS, "float32")
    x = jnp.asarray(rng.normal(size=(len(LENGTHS), 1, 64)), jnp.float32)
    n = np.asarray(LENGTHS)
    pos, mask = jnp.asarray(np.maximum(n - 1, 0)[:, None]), jnp.asarray(n[:, None] > 0)
    step = lambda: paged_kv._whole_latent_attention(  # noqa: E731
        attn, p, x, jnp.asarray(clean), jnp.asarray(tables), pos, mask, BS)
    y_view, pool_view, rows = step()
    monkeypatch.setattr(
        paged_kv, "_attend_rows_in_pool",
        lambda *a, **how: paged_latent.paged_latent_decode(*a, interpret=True, **how))
    y_kernel, pool_kernel, _ = step()
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_view), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(pool_kernel), np.asarray(pool_view))
    assert int(rows) == int(n.sum())


def test_latent_kernel_refuses_a_pool_of_other_rows():
    from tpu_dist.ops.paged_latent import paged_latent_decode

    q, pool = jnp.zeros((2, 4, 256)), jnp.zeros((5, BS, 128))
    with pytest.raises(ValueError, match="over a pool of rows of 128"):
        paged_latent_decode(q, pool, jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32),
                            v_width=128, interpret=True)
