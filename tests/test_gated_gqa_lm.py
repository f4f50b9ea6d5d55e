"""`HybridLM` with gated grouped-query mixers of two kinds (a windowed one
with rope whose K/V live in a per-slot ring, a full one with no positions in
the paged pool), sandwich norms, a leading dense layer, sigmoid-routed
experts with a route scale and an untied head, against the plain reference
`chipbench/reference/afmoe_ref.py`: at the family's rehearsal size on the
CPU, float32, seeded random weights.

Tolerances.  Program and reference are both float32 here, so what separates
them is the order of additions (the reference's attention walks keys in
blocks under a running maximum, its experts sum a token's picks in expert
order).  Logits are of order 1 and read 1e-5 apart at most; ``ATOL`` leaves a
factor of ten.  A pick of the router that a rounding flips would read 1e-2:
none does at these seeds.  The program one precision step down (bfloat16
weights) misses ``ATOL`` by two orders:
`test_one_precision_step_down_is_told_apart`.
"""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.families import afmoe as family
from chipbench.reference import afmoe_ref as ref
from tests.test_hybrid_lm import _serve_logits
from tpu_dist import ops
from tpu_dist.models.hybrid_lm import HybridLM
from tpu_dist.parallel.moe import routed_experts
from tpu_dist.serve import ServeConfig, ServeEngine, paged_kv

REPO = Path(__file__).resolve().parents[1]
PUBLISHED = json.loads((REPO / "chipbench/configs/Trinity-Large-Preview.json").read_text())
CFG = dict(PUBLISHED, **family.tiny(PUBLISHED))
CFG["serve"] = dict(PUBLISHED["serve"], prefill_chunk=16)
ATOL = 2e-4
KEY = jax.random.key(7)
FULL, SWA = (CFG["layer_types"].index(k) for k in ("full_attention", "sliding_attention"))


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """The reference walks its keys, and an expert its picks, in several
    blocks at these lengths too."""
    monkeypatch.setattr(ref, "BLOCK", 16)
    monkeypatch.setattr(ref, "EXPERT_ROWS", 16)


@pytest.fixture(scope="module")
def model():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ref, "BLOCK", 16)
        lm = family.make_lm(CFG, KEY, "float32")
        params, _ = lm.init()
        return lm, params, family.make_init(CFG, "float32", layout="reference")(KEY)


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(0, CFG["vocab_size"], shape, dtype=np.int32)


# ------------------------------------------------------------ (a) dense


def test_dense_apply_is_the_references_forward(model):
    lm, params, p_ref = model
    toks = _tokens((2, 53))          # past the window (9), three blocks and a part
    got, _ = jax.jit(lambda t: lm.apply(params, {}, t))(toks)
    want = ref.forward(p_ref, toks, CFG)
    assert got.dtype == jnp.float32 and got.shape == (2, 53, CFG["vocab_size"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    # the logits are not flat and the served tokens vary: there is something to see
    assert float(np.asarray(want).std()) > 0.3
    assert len(set(np.asarray(want.argmax(-1)).ravel().tolist())) > 40
    back = family.to_reference(params)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(p_ref)))


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_each_mixers_dense_form_is_the_references_layer(model, kind):
    """One layer alone, and each of its parts told apart from the layer
    without it: the gate, the norm of queries and keys, the window."""
    lm, params, p_ref = model
    at = CFG["layer_types"].index(kind)
    x = jax.random.normal(jax.random.key(3), (1, 48, CFG["hidden_size"]))
    attn = lm.mixers[family.KINDS[kind]].attn
    assert (attn.head_dim, attn.inner, attn.dim) == (24, 96, 64)      # not hidden / heads
    got = attn.apply(params["blocks"][at]["mixer"], {}, x)[0][0]
    p = p_ref["layers"][at]["mixer"]
    layer = lambda p, cfg=CFG: ref._attention(  # noqa: E731
        x[0], p, cfg, lambda a: a, window=ref._window(kind, cfg))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(layer(p)), atol=2e-5)
        ungated = dict(p, wg=jnp.zeros_like(p["wg"]))              # sigmoid(0): a half everywhere
        assert float(jnp.abs(got - layer(ungated)).max()) > 1e-2
        assert float(jnp.abs(got - layer(dict(p, q_norm=3.0 * p["q_norm"]))).max()) > 1e-2
        if kind == "sliding_attention":
            assert float(jnp.abs(got - layer(p, dict(CFG, sliding_window=48))).max()) > 1e-2


def test_a_full_layer_has_no_positions_and_a_sliding_one_has(model):
    """With the tokens before it in another order the last token's output
    is the same on a full layer (attention over a set) and not on a sliding
    one (rope), the window wide enough to see them all."""
    lm, params, _ = model
    x = jax.random.normal(jax.random.key(4), (1, 9, CFG["hidden_size"]))
    shuffled = x[:, jnp.array([3, 0, 7, 5, 1, 6, 2, 4, 8])]
    last = lambda kind, at, x: lm.mixers[family.KINDS[kind]].attn.apply(  # noqa: E731
        params["blocks"][at]["mixer"], {}, x)[0][0, -1]
    np.testing.assert_allclose(np.asarray(last("full_attention", FULL, x)),
                               np.asarray(last("full_attention", FULL, shuffled)), atol=1e-5)
    assert float(jnp.abs(last("sliding_attention", SWA, x)
                         - last("sliding_attention", SWA, shuffled)).max()) > 1e-2


def test_both_residual_forms_are_one_block_function(model):
    """The sandwich norms are the layer's two further gains: with them at
    one and the sublayers' outputs of unit scale already they change little,
    scaled they scale what a sublayer adds, and a model without them has no
    such weights."""
    lm, params, _ = model
    assert all({"ln1", "ln1_out", "ln2", "ln2_out"} <= set(b) for b in params["blocks"])
    plain = HybridLM(vocab=64, dim=32, layer_types=["attention"], heads=4, kv_heads=2,
                     n_experts=4, experts_per_token=2, expert_width=16, shared_width=16)
    assert "ln1_out" not in plain.init(KEY)[0]["blocks"][0]
    toks = _tokens((1, 12))
    base = lm.apply(params, {}, toks)[0]
    doubled = jax.tree.map(lambda a: a, params)
    doubled["blocks"][0]["ln1_out"] = {"scale": 2.0 * params["blocks"][0]["ln1_out"]["scale"]}
    assert float(jnp.abs(lm.apply(doubled, {}, toks)[0] - base).max()) > 1e-2


# ------------------------------------------------- (b) through the caches


@pytest.mark.parametrize("chunk", [16, 12])
def test_prefill_then_decode_logits_are_the_references(model, chunk):
    """Through the pool and the rings: prompts of unequal length in slots
    that are not the rows' own, chunk boundaries that leave ``real_len <
    chunk``, a slot idle beside them; lengths past the window (9) and past
    the ring's 24 rows more than once, in prefill (53 > 48) and in decode
    (41 + 20 > 48), so that a ring wraps twice."""
    lm, params, p_ref = model
    prompts = [_tokens((n,), seed=n) for n in (41, 8, 53)]
    new = 64 - 53
    got = _serve_logits(lm, params, prompts, new=new, chunk=chunk, slots=[2, 0, 3])
    for prompt, mine in zip(prompts, got):
        seq = np.concatenate([prompt, mine.argmax(-1)[:-1].astype(np.int32)])
        want = np.asarray(ref.forward(p_ref, seq[None], CFG)[0, prompt.size - 1:])
        np.testing.assert_allclose(mine, want, atol=ATOL)


def test_the_walked_view_is_the_gathered_view(model, monkeypatch):
    """What prefill walks in parts at the cell's contexts gives what the
    whole gathered view gives: rows at unequal places, through the engine's
    tables and through a ring's wrapping ones, with and without a window,
    a pad query among the real ones."""
    rng = np.random.default_rng(5)
    S, s, heads, kv, hd, bs, MB = 3, 6, 4, 2, 8, 4, 16
    q = jnp.asarray(rng.normal(size=(S, heads, s, hd)), jnp.float32)
    start = np.array([0, 23, 41])
    positions = jnp.asarray(start[:, None] + np.arange(s), jnp.int32)
    monkeypatch.setattr(paged_kv, "WALK_TOKENS", 2 * bs)
    for tables, blocks, window in [
            (rng.permutation(S * MB).reshape(S, MB).astype(np.int32), S * MB, None),
            (rng.permutation(S * MB).reshape(S, MB).astype(np.int32), S * MB, 7),
            (np.asarray(paged_kv.ring_tables(jnp.array([2, 0, 1]), S, 3, MB)), S * 3, 7)]:
        k, v = (jnp.asarray(rng.normal(size=(blocks + 1, bs, kv * hd)), jnp.float32)
                for _ in range(2))
        whole = paged_kv._gathered_attention(q, k, v, tables, positions, sliding_window=window)
        parts = paged_kv._walked_attention(q, k, v, tables, positions, sliding_window=window)
        np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), atol=1e-5)


def test_prefill_walks_where_a_views_scores_would_not_fit(model, monkeypatch):
    """`_paged_attention` takes the walked form by the size of the scores;
    the logits are the same either way."""
    lm, params, _ = model
    prompts = [_tokens((n,), seed=n) for n in (37, 20)]
    whole = _serve_logits(lm, params, prompts, new=3, chunk=16)
    called = []
    real = paged_kv._walked_attention
    monkeypatch.setattr(paged_kv, "_walked_attention",
                        lambda *a, **kw: called.append(kw["sliding_window"]) or real(*a, **kw))
    monkeypatch.setattr(paged_kv, "SCORE_BYTES", 1024)
    monkeypatch.setattr(paged_kv, "WALK_TOKENS", 16)
    parts = _serve_logits(lm, params, prompts, new=3, chunk=16)
    assert set(called) == {None, CFG["sliding_window"]}
    for a, b in zip(whole, parts):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_the_kernel_reads_a_ring_through_its_wrapping_table(model):
    """`ops.paged_attention_decode`, interpreted here, over rings laid out
    as a pool (`init_ring_cache`) under `ring_tables`, against the gathered
    view through the same tables: contexts short of the window, past it and
    past the ring's rows more than once; 48 query heads over 8 K/V heads of
    128 as the published layer has them."""
    rng = np.random.default_rng(9)
    heads, kv, hd, bs, window, chunk = 48, 8, 128, 16, 40, 8
    ring_blocks = -(-(window - 1 + chunk) // bs)          # 3 blocks: 48 rows
    lengths = np.array([0, 5, 40, 47, 48, 49, 97, 150], np.int32)
    S, MB = len(lengths), 10
    tables = paged_kv.ring_tables(None, S, ring_blocks, MB)
    k, v = (jnp.asarray(rng.normal(size=(S * ring_blocks + 1, bs, kv * hd)), jnp.float32)
            for _ in range(2))
    q = jnp.asarray(rng.normal(size=(S, heads, hd)), jnp.float32) * hd ** -0.5
    got = ops.paged_attention_decode(q, k, v, tables, jnp.asarray(lengths),
                                     sliding_window=window, interpret=True)
    want = paged_kv._gathered_attention(q[:, :, None], k, v, tables,
                                        jnp.maximum(lengths, 1)[:, None] - 1,
                                        sliding_window=window)[:, :, 0]
    held = lengths > 0
    np.testing.assert_allclose(np.asarray(got)[held], np.asarray(want)[held], atol=1e-5)
    assert (np.asarray(got)[~held] == 0).all()


def test_a_ring_too_short_for_the_chunk_is_refused(model):
    lm, params, _ = model
    with pytest.raises(ValueError, match="16 new tokens a call, not 17"):
        _serve_logits(lm, params, [_tokens((20,))], new=2, chunk=17)


def _reference_gap(p_ref, prompt, served):
    """The widest gap by which a served token's logit lies below the
    reference's best at its position (the harness's comparison)."""
    seq = np.concatenate([prompt, served]).astype(np.int32)
    logits = np.asarray(ref.forward(p_ref, seq[None], CFG)[0])
    at = np.arange(prompt.size - 1, seq.size - 1)
    return float((logits[at].max(-1) - logits[at, seq[at + 1]]).max())


def test_the_engine_serves_the_references_tokens(model):
    """The normal path: submit, step.  More requests than slots, so every
    slot has a second tenant whose ring and pool rows lie where the first
    one's lay (nothing is reset at admission); prompts that end inside a
    chunk, prefill chunks beside decode, eviction and refill; answers long
    enough that every ring wraps."""
    from tpu_dist.observe.registry import REGISTRY

    total = lambda name: REGISTRY.counter(f"tpu_dist_serve_{name}_total").value()  # noqa: E731
    before = {name: total(name) for name in ("attn_rows_attended", "swa_rows_unwindowed", "swa_rows_attended", "moe_picks", "moe_picks_held")}   # the registry is the process's
    lm, params, p_ref = model
    eng = ServeEngine(lm, params, ServeConfig(
        max_batch=3, block_size=8, num_blocks=36, max_seq=96, prefill_chunk=16, prefill_batch=2))
    prompts = [_tokens((n,), seed=100 + n) for n in (5, 16, 23, 40, 17, 33, 9, 48)]
    ids = [eng.submit(p, 30) for p in prompts]
    results = eng.run_until_drained()
    assert len({results[i].tokens.tolist()[-1] for i in ids}) > 4, "the answers differ"
    for p, i in zip(prompts, ids):
        assert results[i].tokens.size == 30
        assert _reference_gap(p_ref, p, results[i].tokens) < ATOL
    assert eng.allocator.used == 0
    # the model's own counters rode the decode readback into the registry
    count = lambda name: total(name) - before[name]  # noqa: E731
    n_full = CFG["layer_types"].count("full_attention")
    n_swa = CFG["layer_types"].count("sliding_attention")
    # every position of every request but its last token was a query once
    lengths = [p.size + 29 for p in prompts]
    assert count("attn_rows_attended") == n_full * sum(n * (n + 1) // 2 for n in lengths)
    assert count("swa_rows_unwindowed") == n_swa * sum(n * (n + 1) // 2 for n in lengths)
    assert count("swa_rows_attended") >= n_swa * sum(
        sum(min(t + 1, CFG["sliding_window"]) for t in range(n)) for n in lengths)
    # and each step's counts are on its `engine.decode_apply` span and the metrics page
    from tpu_dist.observe import spans

    steps = [s.attrs for s in spans.recent()
             if s.name == "engine.decode_apply" and "swa_rows_unwindowed" in s.attrs]
    assert steps and all(0 < a["swa_rows_attended"] <= a["swa_rows_unwindowed"] for a in steps)
    assert any(a["swa_rows_attended"] < a["swa_rows_unwindowed"] for a in steps)
    assert all(f"tpu_dist_serve_{name}_total" in REGISTRY.render()
               for name in ("attn_rows_attended", "swa_rows_attended", "swa_rows_unwindowed"))
    experts = CFG["num_hidden_layers"] - CFG["num_dense_layers"]
    assert count("moe_picks") >= experts * CFG["num_experts_per_tok"] * sum(lengths)
    assert 0 < count("moe_picks_held") < count("moe_picks")


def test_the_two_kinds_of_cache_are_accounted(model):
    """A full layer keeps a pool under the engine's tables and no state; a
    windowed one no pool and, as state, a ring of ``window - 1 + chunk``
    rows a slot in whole blocks (here 24 rows = 3 blocks of 8) and one
    scratch block."""
    lm, params, _ = model
    eng = ServeEngine(lm, params, ServeConfig(
        max_batch=3, block_size=8, num_blocks=36, max_seq=96, prefill_chunk=16))
    n_full, n_swa = (CFG["layer_types"].count(k) for k in ("full_attention", "sliding_attention"))
    row = 2 * CFG["num_key_value_heads"] * CFG["head_dim"] * 4
    assert eng.kv_pool_bytes == n_full * 37 * 8 * row == 37 * 8 * family.kv_bytes_per_token(CFG, 4)
    held = CFG["held_experts"][1] - CFG["held_experts"][0]
    # and the running counts: the experts' 3 + one a held expert, the mixers' 3
    assert eng.state_bytes == n_swa * (3 * 3 + 1) * 8 * row + 4 * (3 + held + 3)
    kinds = [(sorted(kv), sorted(st)) for kv, st in zip(eng.cache["kv"], eng.cache["state"]["layers"])]
    assert kinds == [([], ["k", "v"]), ([], ["k", "v"]), (["k", "v"], []),
                     ([], ["k", "v"]), ([], ["k", "v"])]


def test_one_precision_step_down_is_told_apart(model):
    lm, params, p_ref = model
    toks = _tokens((2, 37))
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a, params)
    got, _ = jax.jit(lambda t: lm.apply(low, {}, t))(toks)
    assert float(jnp.abs(got - ref.forward(p_ref, toks, CFG)).max()) > 20 * ATOL


@pytest.mark.parametrize("fault", ["rope on the full layer", "a ring one block short"])
def test_a_fault_in_the_program_alone_is_seen(model, fault, monkeypatch):
    """What the chip run plants to see that the limit has teeth, here at the
    rehearsal's size: the served logits leave the reference's by far more
    than ``ATOL``."""
    lm, params, p_ref = model
    if fault == "rope on the full layer":
        monkeypatch.setattr(lm.mixers["gated_attention"].attn, "use_rope", True)
    else:   # rings that wrap after 16 rows where the window and a chunk want 24
        monkeypatch.setattr(paged_kv, "ring_tables", lambda slots, rows, blocks, max_blocks: (
            (jnp.arange(rows) if slots is None else slots)[:, None] * blocks
            + jnp.arange(max_blocks) % (blocks - 1)))
    prompt = _tokens((41,), seed=3)
    got = _serve_logits(lm, params, [prompt], new=12, chunk=16)[0]
    seq = np.concatenate([prompt, got.argmax(-1)[:-1].astype(np.int32)])
    want = np.asarray(ref.forward(p_ref, seq[None], CFG)[0, prompt.size - 1:])
    assert np.abs(got - want).max() > 100 * ATOL


# ---------------------------------------------------------- (c) experts


def test_the_gates_scale_multiplies_the_routed_part_alone():
    k = jax.random.split(jax.random.key(21), 5)
    x = jax.random.normal(k[0], (13, 16))
    router = jax.random.normal(k[1], (16, 8)) * 0.5
    w_in, w_out = jax.random.normal(k[2], (8, 16, 24)) * 0.3, jax.random.normal(k[3], (8, 12, 16)) * 0.3
    kw = dict(top_k=3, scoring="sigmoid_normalised", bias=jax.random.normal(k[4], (8,)) * 0.3)
    with jax.default_matmul_precision("highest"):
        one, c1 = routed_experts(x, router, w_in, w_out, **kw)
        scaled, c2 = routed_experts(x, router, w_in, w_out, scale=2.448, **kw)
        np.testing.assert_allclose(np.asarray(scaled), 2.448 * np.asarray(one), rtol=1e-5)
        assert int(c1["picks_held"]) == int(c2["picks_held"]) == 39
        soft, _ = routed_experts(x, router, w_in, w_out, top_k=3)
        soft2, _ = routed_experts(x, router, w_in, w_out, top_k=3, scale=2.0)
        np.testing.assert_allclose(np.asarray(soft2), 2.0 * np.asarray(soft), rtol=1e-5)


@pytest.mark.parametrize("case", ["few_land_here", "all_land_here", "half_are_held"])
def test_the_grouped_product_is_handed_the_held_picks_lead(case, monkeypatch):
    """Where a small share of the experts is held the grouped product gets
    twice that share of the sorted picks (the held ones lead), and all of
    them when more landed here; the result is the uncut call's either way.
    With half the experts held there is nothing to cut and no branch."""
    from tpu_dist.parallel import moe

    T, d, E, W, k = 64, 16, 16, 12, 2
    H = 8 if case == "half_are_held" else 2
    ks = jax.random.split(jax.random.key(31), 4)
    x = jax.random.normal(ks[0], (T, d))
    router = jax.random.normal(ks[1], (d, E)) * 0.5
    if case == "all_land_here":      # every token picks the two held experts
        x = jnp.abs(x) + 0.1
        router = jnp.zeros((d, E)).at[:, :2].set(5.0)
    w_in, w_out = jax.random.normal(ks[2], (H, d, 2 * W)) * 0.3, jax.random.normal(ks[3], (H, W, d)) * 0.3
    call = lambda x: routed_experts(x, router, w_in, w_out, top_k=k, held=(0, H),  # noqa: E731
                                    scoring="sigmoid_normalised", scale=2.448)
    with jax.default_matmul_precision("highest"):
        whole, c0 = call(x)                      # 128 picks: under four tiles of 128, uncut
        monkeypatch.setattr(moe, "LEAD_ROWS", 8)
        cut, c1 = call(x)                        # 2 of 16 held: 2 * 128 / 8 = 32 rows lead
        branches = str(jax.make_jaxpr(lambda x: call(x)[0])(x)).count("cond[")
    np.testing.assert_allclose(np.asarray(cut), np.asarray(whole), atol=1e-6)
    assert int(c0["picks_held"]) == int(c1["picks_held"])
    assert branches == (0 if case == "half_are_held" else 1)
    assert (int(c1["picks_held"]) > 32) == (case != "few_land_here")


def test_the_references_experts_are_a_loop_over_the_picks(model):
    """The reference's sorted walk against a loop over tokens and picks,
    the scale on the routed part and not on the shared expert."""
    _, _, p_ref = model
    lp = p_ref["layers"][1]
    u = jax.random.normal(jax.random.key(12), (37, CFG["hidden_size"]))
    lo, hi = CFG["held_experts"]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref._routed(u, lp, CFG, lambda a: a))
        sig = jax.nn.sigmoid(u @ lp["router"])
        _, idx = jax.lax.top_k(sig + lp["router_bias"], CFG["num_experts_per_tok"])
        want = np.zeros_like(got)
        for t in range(u.shape[0]):
            total = float(sig[t, idx[t]].sum())
            for e in idx[t].tolist():
                if lo <= e < hi:
                    a, b = np.split(np.asarray(u[t] @ lp["experts_in"][e - lo]), 2)
                    want[t] += (CFG["route_scale"] * float(sig[t, e]) / total
                                * np.asarray((jax.nn.silu(a) * b) @ lp["experts_out"][e - lo]))
        np.testing.assert_allclose(got, want, atol=1e-5)
        both = ref._experts(u, lp, CFG, lambda a: a)
        shared = ref._gated(u, lp["shared_in"], lp["shared_out"], lambda a: a)
        np.testing.assert_allclose(np.asarray(both - shared), want, atol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 chips share an expert layer: each gives the routed part of its 4
    experts of a router of 32, the shared expert is counted once, and the
    sum is what the UNCUT reference gives for the whole layer."""
    cfg = dict(CFG, router_experts=32, num_experts=32, held_experts=[0, 32],
               num_experts_per_tok=4, num_dense_layers=0, layer_types=["sliding_attention"],
               num_hidden_layers=1)
    layer = ref.init(jax.random.key(31), cfg)["layers"][0]
    assert float(jnp.abs(layer["router_bias"]).max()) > 0     # calibrated: it decides picks
    u = jax.random.normal(jax.random.key(32), (19, cfg["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        whole = ref._experts(u, layer, cfg, lambda a: a)
        shared = ref._gated(u, layer["shared_in"], layer["shared_out"], lambda a: a)
        total, held_picks = shared, 0
        for chip in range(8):
            held = (4 * chip, 4 * chip + 4)
            part, counts = routed_experts(
                u, layer["router"], layer["experts_in"][held[0]:held[1]],
                layer["experts_out"][held[0]:held[1]], top_k=4, held=held,
                scoring="sigmoid_normalised", bias=layer["router_bias"], scale=cfg["route_scale"])
            # and the reference given the same share gives the same part
            mine = dict(layer, experts_in=layer["experts_in"][held[0]:held[1]],
                        experts_out=layer["experts_out"][held[0]:held[1]])
            theirs = ref._routed(u, mine, dict(cfg, held_experts=list(held)), lambda a: a)
            np.testing.assert_allclose(np.asarray(part), np.asarray(theirs), atol=1e-5)
            total, held_picks = total + part, held_picks + int(counts["picks_held"])
    assert held_picks == 19 * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=1e-5)


def test_the_bias_calibration_balances_a_tiny_router(model):
    """Scores with a common direction favour some experts threefold; under
    the calibrated bias every expert's load is within a few per cent of the
    mean, and the model's own layers were left so by `init_parts`."""
    k = jax.random.split(jax.random.key(41), 3)
    lean = jax.random.normal(k[0], (16,)) * 0.8
    sig = jax.nn.sigmoid(jax.random.normal(k[1], (8192, 16)) + lean)
    load = lambda b: np.bincount(  # noqa: E731
        np.asarray(jax.lax.top_k(sig + b, 4)[1]).ravel(), minlength=16) / (8192 * 4 / 16)
    assert load(jnp.zeros(16)).max() > 2.0
    b = ref.balance(sig, 4, steps=300, first_step=0.02, last_step=2e-4)
    assert np.abs(load(b) - 1).max() < 0.03
    # the seeded model: fresh tokens, not the calibration's sample
    _, _, p_ref = model
    toks = _tokens((16, 256), seed=77)
    h = np.sqrt(CFG["hidden_size"]) * p_ref["wte"][toks]
    spreads = []     # by expert layer: the load furthest from the mean, with the bias | without
    for kind, lp in zip(CFG["layer_types"], p_ref["layers"]):
        h = jax.vmap(lambda hs: ref._mix(hs, lp, kind, CFG, lambda a: a))(h)
        u = ref._rms_norm(h, lp["ln2"], CFG["rms_norm_eps"]).reshape(-1, CFG["hidden_size"])
        if "router" not in lp:
            f = ref._gated(u, lp["ff_in"], lp["ff_out"], lambda a: a)
        else:
            _, idx, _ = ref._route(u, lp, CFG, lambda a: a)
            share = lambda idx: np.bincount(np.asarray(idx).ravel(), minlength=8) / idx.size * 8  # noqa: E731
            unbiased = dict(lp, router_bias=jnp.zeros_like(lp["router_bias"]))
            plain = share(ref._route(u, unbiased, CFG, lambda a: a)[1])
            spreads.append((np.abs(share(idx) - 1).max(), np.abs(plain - 1).max()))
            f = ref._experts(u, lp, CFG, lambda a: a)
        h = h + ref._rms_norm(f, lp["ln2_out"], CFG["rms_norm_eps"]).reshape(h.shape)
    with_bias, without = np.asarray(spreads).T
    # 1,536 picks an expert here, +-2.5 %, but only 16 sequences: what a layer adds
    # to the stream leans the same way all through a sequence, more so the deeper
    assert with_bias.max() < 0.3 and with_bias.mean() < 0.6 * without.mean(), spreads


def test_the_two_ranges_a_configuration_may_set_reach_the_weights():
    """`sandwich_out_gain` is the output norms' gain and no other's;
    `expert_out_initializer_range` the routed experts' output projections'
    and not the shared expert's (the published configuration sets both:
    `assumed.weights`)."""
    cfg = dict(CFG, sandwich_out_gain=0.25, expert_out_initializer_range=0.01)
    layer = ref.init(jax.random.key(5), cfg)["layers"][1]
    assert {float(layer[k][0]) for k in ("ln1_out", "ln2_out")} == {0.25}
    assert {float(layer[k][0]) for k in ("ln1", "ln2")} == {1.0}
    assert float(layer["experts_out"].std()) == pytest.approx(0.01, rel=0.05)
    assert float(layer["shared_out"].std()) == pytest.approx(0.1, rel=0.05)
    assert (PUBLISHED["sandwich_out_gain"], PUBLISHED["expert_out_initializer_range"]) == (0.129, 0.0025)


def test_both_layouts_are_given_the_same_bias(model):
    _, params, p_ref = model
    for b, lp in zip(params["blocks"], p_ref["layers"]):
        if "moe" in b:
            assert np.array_equal(np.asarray(b["moe"]["bias"]), np.asarray(lp["router_bias"]))


# ------------------------------------------------------- (d) sizes and table


def test_param_count_is_the_trees_leaves_at_published_widths():
    shapes = jax.eval_shape(family.make_init(PUBLISHED, "bfloat16", layout="program"),
                            jax.random.key(0))
    leaves = jax.tree.leaves(shapes)
    assert family.param_count(PUBLISHED) == sum(x.size for x in leaves) == 4_321_903_872
    tiny = jax.eval_shape(family.make_init(CFG, "float32", layout="reference"), jax.random.key(0))
    assert family.param_count(CFG) == sum(x.size for x in jax.tree.leaves(tiny)) < 5e6
    # the model `HybridLM` draws for itself has the benchmark's tree
    own = jax.eval_shape(lambda: HybridLM.init(family.make_lm(CFG, KEY, "float32"), KEY)[0])
    seeded = jax.eval_shape(family.make_init(CFG, "float32", layout="program"), KEY)
    assert jax.tree.structure(own) == jax.tree.structure(seeded)
    assert [a.shape for a in jax.tree.leaves(own)] == [a.shape for a in jax.tree.leaves(seeded)]
    # what the cell plans: the one full layer's pool and the four layers' rings
    serve = PUBLISHED["serve"]
    assert serve["num_blocks"] * serve["block_size"] == serve["max_batch"] * serve["max_seq"]
    assert family.kv_bytes_per_token(PUBLISHED, 2) == family.attended_row_bytes(PUBLISHED, 2) == 4096
    counts = {"moe_experts_hit": 40, "attn_rows_attended": 228_000, "swa_rows_attended": 364_000}
    floor = family.decode_required_bytes(PUBLISHED, counts, 2)
    outside = 4_321_903_872 - 4 * 32 * 28_311_552
    assert floor == 2 * (outside + 40 * 28_311_552) + 4096 * 592_000


def test_the_configuration_keeps_every_published_width():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if '"Trinity-Large-Preview"' in line)
    differs = {k for k, v in row["config"].items() if PUBLISHED.get(k) != v}
    assert differs == set(PUBLISHED["reduced"])
    assert PUBLISHED["layer_types"] == row["config"]["layer_types"][5:10]
    assert PUBLISHED["published"]["num_experts"] == row["config"]["num_experts"] == 256
    assert PUBLISHED["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert PUBLISHED["num_experts"] * 8 == row["config"]["num_experts"]
    assert PUBLISHED["source"] == row["source_url"]
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == PUBLISHED["name"])
    assert entry["reduced"] == PUBLISHED["reduced"] and entry["source"] == PUBLISHED["source"]


def test_a_form_of_a_key_the_program_does_not_compute_is_refused():
    for key, value in (("score_func", "softmax"), ("n_group", 8), ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match="one form of these keys"):
            family.make_lm(dict(CFG, **{key: value}), KEY, "float32")
    with pytest.raises(ValueError, match="base 10000"):
        family.make_lm(dict(CFG, rope_theta=500000), KEY, "float32")
