"""`HybridLM` with latent-attention mixers (one kind selecting its keys by a
learned index, one windowed), a leading dense layer, sigmoid-routed experts
and an untied head, against the plain reference
`chipbench/reference/dots3_note_ref.py`: at the family's rehearsal size on
the CPU, float32, seeded random weights.

Tolerances.  Program and reference are both float32 here, so what separates
them is the order of additions (the absorbed form sums over the latent
where the reference sums over a head's values; the reference's attention
walks keys in blocks with a running maximum).  Logits are of order 1 and
read 1e-5 apart at most; ``ATOL`` leaves a factor of ten.  A pick of the
indexer or of the router that a rounding flips would read 1e-2: none does
at these seeds, and the picks are compared outright below.  The program one
precision step down (bfloat16 weights) misses ``ATOL`` by two orders:
`test_one_precision_step_down_is_told_apart`.
"""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.families import dots3_note as family
from chipbench.reference import dots3_note_ref as ref
from tests.test_hybrid_lm import _serve_logits
from tpu_dist.models.hybrid_lm import MIXERS, HybridLM
from tpu_dist.nn.latent_attention import LatentAttention, top_visible
from tpu_dist.parallel.moe import routed_experts
from tpu_dist.serve import ServeConfig, ServeEngine

REPO = Path(__file__).resolve().parents[1]
PUBLISHED = json.loads((REPO / "chipbench/configs/dots3-note-prev.json").read_text())
CFG = dict(PUBLISHED, **family.tiny(PUBLISHED))
CFG["serve"] = dict(PUBLISHED["serve"], prefill_chunk=16)
ATOL = 2e-4
KEY = jax.random.key(7)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """The reference walks its keys in several blocks at these lengths too."""
    monkeypatch.setattr(ref, "BLOCK", 16)


@pytest.fixture(scope="module")
def model():
    lm = family.make_lm(CFG, KEY, "float32")
    params, _ = lm.init()
    return lm, params, family.make_init(CFG, "float32", layout="reference")(KEY)


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(0, CFG["vocab_size"], shape, dtype=np.int32)


# ------------------------------------------------------------ (a) dense


def test_dense_apply_is_the_references_forward(model):
    lm, params, p_ref = model
    toks = _tokens((2, 53))          # past the selection (8), the window (9), three blocks
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
    """One layer alone, gate and rescale on, and each of the two told apart
    from the layer without it."""
    lm, params, p_ref = model
    at = CFG["layer_types"].index(kind)
    x = jax.random.normal(jax.random.key(3), (1, 48, CFG["hidden_size"]))
    attn = lm.mixers[kind].attn
    got = attn.apply(params["blocks"][at]["mixer"], {}, x)[0][0]
    full = kind == "full_attention"
    layer = lambda cfg: ref._attention(  # noqa: E731
        x[0], p_ref["layers"][at]["mixer"], ref.sizes(cfg)[kind], cfg, lambda a: a,
        window=None if full else cfg["sliding_window_size"], select=full)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(layer(CFG)), atol=2e-5)
        for key, off in (("attention_gate_type", "none"), ("apply_mla_qkv_lora_rescale", False)):
            assert float(jnp.abs(got - layer(dict(CFG, **{key: off}))).max()) > 1e-2


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_absorbed_is_expanded(model, kind):
    lm, params, _ = model
    attn = lm.mixers[kind].attn
    p = params["blocks"][CFG["layer_types"].index(kind)]["mixer"]
    x = jax.random.normal(jax.random.key(5), (2, 21, CFG["hidden_size"]))
    pos = jnp.broadcast_to(jnp.arange(21), (2, 21))
    _, q_n, q_r = attn.queries(p, x, pos)
    rows = attn.rows(p, x, pos)
    visible = jax.random.bernoulli(jax.random.key(6), 0.6, (2, 21, 21)) | jnp.eye(21, dtype=bool)
    a, e = attn.absorbed(p, q_n, q_r, rows, visible), attn.expanded(p, q_n, q_r, rows, visible)
    np.testing.assert_allclose(np.asarray(a), np.asarray(e), atol=2e-5)
    assert float(jnp.abs(e).max()) > 0.1


@pytest.mark.parametrize("held", [None, 12, 7])
def test_absorbed_and_index_scores_walk_the_keys_in_parts(model, monkeypatch, held):
    """What is walked in blocks at the cell's sizes gives what the whole
    gives, also where the walk stops at the places the call's contexts
    hold (``held``), and a query that sees nothing gets zeros."""
    from tpu_dist.nn import latent_attention as la

    lm, params, _ = model
    attn = lm.mixers["full_attention"].attn
    p = params["blocks"][0]["mixer"]
    x = jax.random.normal(jax.random.key(8), (2, 12, CFG["hidden_size"]))
    pos = jnp.broadcast_to(jnp.arange(12), (2, 12))
    c_q, q_n, q_r = attn.queries(p, x, pos)
    rows, keys = attn.rows(p, x, pos), attn.index_keys(p, x, pos)
    q_i, w = attn.index_queries(p, x, c_q, pos)
    seen = jnp.broadcast_to(jnp.tril(jnp.ones((12, 12), bool)), (2, 12, 12))
    seen = seen & (jnp.arange(12) < (held or 12)) & (jnp.arange(12)[:, None] != 3)   # query 3: a pad
    whole = attn.absorbed(p, q_n, q_r, rows, seen), attn.index_scores(q_i, w, keys)
    monkeypatch.setattr(la, "SCORE_BYTES", 4 * 2 * 4 * 12 * 3)   # three keys at once
    parts = attn.absorbed(p, q_n, q_r, rows, seen, held), attn.index_scores(q_i, w, keys, held)
    keep = jnp.arange(12)[:, None, None] != 3
    np.testing.assert_allclose(np.asarray(parts[0] * keep), np.asarray(whole[0] * keep), atol=1e-5)
    assert float(jnp.abs(parts[0][:, 3]).max()) == 0.0
    walked = -(-(held or 12) // 3) * 3
    np.testing.assert_allclose(np.asarray(parts[1][..., :walked]),
                               np.asarray(whole[1][..., :walked]), atol=1e-5)
    assert float(jnp.abs(parts[1][..., walked:]).max()) == 0.0 if walked < 12 else True


# ----------------------------------------------------------- (b) the indexer


def test_the_indexers_picks_are_the_references(model):
    lm, params, p_ref = model
    attn = lm.mixers["full_attention"].attn
    x = jax.random.normal(jax.random.key(9), (1, 48, CFG["hidden_size"]))
    pos = jnp.arange(48)[None]
    p = params["blocks"][0]["mixer"]
    c_q, _, _ = attn.queries(p, x, pos)
    got = attn.visible(p, x, c_q, pos, pos, attn.index_keys(p, x, pos))[0]
    with jax.default_matmul_precision("highest"):
        ref_cq = np.sqrt(CFG["hidden_size"] / CFG["q_lora_rank"]) * ref._rms_norm(
            x[0] @ p_ref["layers"][0]["mixer"]["w_dq"], p_ref["layers"][0]["mixer"]["q_norm"], 1e-5)
        want = ref._picks(x[0], ref_cq, p_ref["layers"][0]["mixer"], CFG, lambda a: a)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    per_query = np.asarray(got).sum(-1)
    assert per_query.tolist() == [min(t + 1, CFG["index_topk"]) for t in range(48)]
    assert not np.array_equal(np.asarray(got), np.tril(np.ones((48, 48), bool)))


def test_top_visible_takes_ties_at_the_lower_place():
    scores = jnp.array([[1.0, 3.0, 3.0, 3.0, 0.5, 3.0]])
    causal = jnp.array([[True, True, True, True, True, False]])
    assert top_visible(scores, causal, 2).tolist() == [[False, True, True, False, False, False]]
    assert top_visible(scores, causal, 4).tolist() == [[True, True, True, True, False, False]]
    assert top_visible(scores, causal, 6).tolist() == causal.tolist()
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), 2)
    assert sorted(idx[0].tolist()) == [1, 2]


def test_a_selection_no_smaller_than_the_length_is_unselected_attention(model):
    lm, params, _ = model
    toks = _tokens((1, 40), seed=4)
    wide = family.make_lm(dict(CFG, index_topk=40), KEY, "float32")
    full_cfg = {**family.mixer_sizes(CFG)["full_attention"], "index_topk": 0}
    plain = family.make_lm(CFG, KEY, "float32")
    plain.mixers["full_attention"].attn = LatentAttention(
        CFG["hidden_size"], **{k: v for k, v in full_cfg.items()
                               if k not in ("index_heads", "index_dim")})
    a = wide.apply(params, {}, toks)[0]
    b = plain.apply(params, {}, toks)[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert float(jnp.abs(a - lm.apply(params, {}, toks)[0]).max()) > 1e-2


# ------------------------------------------------- (c) through the caches


@pytest.mark.parametrize("chunk", [16, 12])
def test_prefill_then_decode_logits_are_the_references(model, chunk):
    """Through the latent pool, the index-key pool and the ring: prompts of
    unequal length in slots that are not the rows' own, chunk boundaries
    that leave ``real_len < chunk``, a slot idle beside them; lengths past
    the selection (8) and the window (9), and past the ring's 24 rows, so
    that it wraps in prefill (33 > 24) and in decode (21 + 10 > 24)."""
    lm, params, p_ref = model
    prompts = [_tokens((n,), seed=n) for n in (21, 8, 33)]
    got = _serve_logits(lm, params, prompts, new=10, chunk=chunk, slots=[2, 0, 3])
    for prompt, mine in zip(prompts, got):
        seq = np.concatenate([prompt, mine.argmax(-1)[:-1].astype(np.int32)])
        want = np.asarray(ref.forward(p_ref, seq[None], CFG)[0, prompt.size - 1:])
        np.testing.assert_allclose(mine, want, atol=ATOL)


@pytest.fixture
def searched(monkeypatch):
    """-> the shapes every selection's search (`ops.kth_score`) was handed."""
    from tpu_dist.nn import latent_attention
    from tpu_dist.serve import paged_kv

    shapes, kth_and_last = [], latent_attention.kth_and_last

    def search(visible, k):
        shapes.append(visible.shape)
        return kth_and_last(visible, k)

    monkeypatch.setattr(latent_attention, "kth_and_last", search)
    paged_kv._attend_picks_in_pool.clear_cache()     # traced before, it would not call `search`
    yield shapes
    paged_kv._attend_picks_in_pool.clear_cache()     # nor keep the trace that does


@pytest.mark.parametrize("chunk", [16, 12])
def test_the_searched_selection_serves_the_whole_sequences_tokens(model, searched, chunk):
    """Chunked prefill and decode against the whole-sequence forward, all
    three selecting through the search: the same tokens, the same logits.
    (Decode's calls hold little over what they select here, so they read
    their pools in place: the arm that fetches its picks, and sorts for
    them, is traced and never taken.)"""
    lm, params, _ = model
    prompts = [_tokens((n,), seed=n) for n in (21, 8, 33)]
    got = _serve_logits(lm, params, prompts, new=10, chunk=chunk, slots=[2, 0, 3])
    paged = set(searched)
    assert paged == {(4, 64), (3 * chunk, 64), (2 * chunk, 64), (chunk, 64)}
    for prompt, mine in zip(prompts, got):
        seq = np.concatenate([prompt, mine.argmax(-1)[:-1].astype(np.int32)])
        whole = np.asarray(lm.apply(params, {}, seq[None])[0][0, prompt.size - 1:])
        assert mine.argmax(-1).tolist() == whole.argmax(-1).tolist()
        np.testing.assert_allclose(mine, whole, atol=ATOL)
        assert (seq.size, seq.size) in set(searched) - paged


def test_a_ring_too_short_for_the_chunk_is_refused(model):
    lm, params, _ = model
    with pytest.raises(ValueError, match="a ring of 24 rows"):
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
    chunk, prefill chunks beside decode, eviction and refill."""
    from tpu_dist.observe.registry import REGISTRY

    total = lambda name: REGISTRY.counter(f"tpu_dist_serve_{name}_total").value()  # noqa: E731
    before = {name: total(name) for name in ("dsa_keys_scored", "dsa_rows_selected", "swa_rows_attended", "moe_picks", "moe_picks_held")}   # the registry is the process's
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
    assert count("dsa_keys_scored") == n_full * sum(n * (n + 1) // 2 for n in lengths)
    assert count("dsa_rows_selected") == n_full * sum(
        sum(min(t + 1, CFG["index_topk"]) for t in range(n)) for n in lengths)
    assert count("swa_rows_attended") == n_swa * sum(
        sum(min(t + 1, CFG["sliding_window_size"]) for t in range(n)) for n in lengths)
    experts = CFG["num_hidden_layers"] - CFG["first_k_dense_replace"]
    assert count("moe_picks") == experts * CFG["num_experts_per_tok"] * sum(lengths)
    assert 0 < count("moe_picks_held") < count("moe_picks")


def test_the_three_kinds_of_cache_are_accounted(model):
    lm, params, _ = model
    eng = ServeEngine(lm, params, ServeConfig(
        max_batch=3, block_size=8, num_blocks=36, max_seq=96, prefill_chunk=16))
    n_full, n_swa = (CFG["layer_types"].count(k) for k in ("full_attention", "sliding_attention"))
    # a latent row (24 and 40 values here) is stored as whole 128-lane tiles
    assert eng.kv_pool_bytes == n_full * 37 * 8 * (128 + CFG["index_head_dim"]) * 4
    assert 37 * 8 * family.kv_bytes_per_token(CFG, 4) == n_full * 37 * 8 * (24 + 16) * 4
    ring = (CFG["sliding_window_size"] - 1 + 16) * 128 * 4
    held = CFG["held_experts"][1] - CFG["held_experts"][0]
    assert eng.state_bytes == n_swa * 3 * ring + 4 * (3 + held + 4)
    kinds = [(sorted(kv), sorted(st)) for kv, st in zip(eng.cache["kv"], eng.cache["state"]["layers"])]
    assert kinds == [(["ckv", "ik"], []), ([], ["ring"]), ([], ["ring"]), (["ckv", "ik"], [])]


def test_one_precision_step_down_is_told_apart(model):
    lm, params, p_ref = model
    toks = _tokens((2, 37))
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a, params)
    got, _ = jax.jit(lambda t: lm.apply(low, {}, t))(toks)
    assert float(jnp.abs(got - ref.forward(p_ref, toks, CFG)).max()) > 20 * ATOL


# ---------------------------------------------------------- (d), (e) experts


def _loop_over_experts(x, router, bias, w_in, w_out, top_k):
    sig = jax.nn.sigmoid(x @ router)
    _, idx = jax.lax.top_k(sig + bias, top_k)
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        total = float(sig[t, idx[t]].sum())
        for e in idx[t].tolist():
            a, b = np.split(np.asarray(x[t] @ w_in[e]), 2)
            y[t] += float(sig[t, e]) / total * np.asarray((jax.nn.silu(a) * b) @ w_out[e])
    return y, idx


def test_sigmoid_scoring_is_a_loop_over_the_experts_and_the_bias_only_selects():
    k = jax.random.split(jax.random.key(21), 5)
    x = jax.random.normal(k[0], (13, 16))
    router = jax.random.normal(k[1], (16, 8)) * 0.5
    w_in, w_out = jax.random.normal(k[2], (8, 16, 24)) * 0.3, jax.random.normal(k[3], (8, 12, 16)) * 0.3
    bias = jax.random.normal(k[4], (8,)) * 0.3
    kw = dict(top_k=3, scoring="sigmoid_normalised")
    with jax.default_matmul_precision("highest"):
        want, idx = _loop_over_experts(x, router, bias, w_in, w_out, 3)
        got, counts = routed_experts(x, router, w_in, w_out, bias=bias, **kw)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
        _, unbiased = _loop_over_experts(x, router, 0 * bias, w_in, w_out, 3)
        assert not np.array_equal(np.asarray(idx), np.asarray(unbiased)), "the bias decides picks"
        assert int(counts["picks"]) == 39 == int(counts["picks_held"])
        # a bias that changes no pick changes nothing: it is in no gate
        same, _ = routed_experts(x, router, w_in, w_out, bias=bias + 7.0, **kw)
        np.testing.assert_allclose(np.asarray(same), np.asarray(got), atol=1e-6)
        with pytest.raises(ValueError, match="scoring"):
            routed_experts(x, router, w_in, w_out, top_k=3, scoring="tanh")


def test_the_older_scoring_is_bit_for_bit_what_it_was():
    """`softmax_of_picks`, the default, against the arithmetic it had."""
    k = jax.random.split(jax.random.key(22), 4)
    x, router = jax.random.normal(k[0], (11, 16)), jax.random.normal(k[1], (16, 6))
    w_in, w_out = jax.random.normal(k[2], (6, 16, 8)), jax.random.normal(k[3], (6, 4, 16))
    got, _ = routed_experts(x, router, w_in, w_out, top_k=2)
    named, _ = routed_experts(x, router, w_in, w_out, top_k=2, scoring="softmax_of_picks")
    scores = jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST)
    v, e = jax.lax.top_k(scores, 2)
    g = jax.nn.softmax(v, axis=-1)
    want = jnp.zeros_like(x)
    for j in range(2):
        ab = jnp.einsum("td,tdw->tw", x, w_in[e[:, j]])
        out = jnp.einsum("tw,twd->td", jax.nn.silu(ab[:, :4]) * ab[:, 4:], w_out[e[:, j]])
        want = want + g[:, j:j + 1] * out
    assert np.array_equal(np.asarray(got), np.asarray(named))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_the_shares_add_up_to_the_uncut_layer():
    """32 chips share an expert layer: each gives the routed part of its one
    expert of a router of 32, the shared expert is counted once, and the sum
    is what the UNCUT reference gives for the whole layer."""
    cfg = dict(CFG, router_experts=32, n_routed_experts=32, held_experts=[0, 32],
               num_experts_per_tok=8, first_k_dense_replace=0,
               layer_types=["sliding_attention"], num_hidden_layers=1)
    layer = ref.init(jax.random.key(31), cfg)["layers"][0]
    layer["router_bias"] = layer["router_bias"] * 20.0     # wide enough to decide picks
    u = jax.random.normal(jax.random.key(32), (19, cfg["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        whole = ref._experts(u, layer, cfg, lambda a: a)
        shared = ref._gated(u, layer["shared_in"], layer["shared_out"], lambda a: a)
        total, held_picks = shared, 0
        for chip in range(32):
            part, counts = routed_experts(
                u, layer["router"], layer["experts_in"][chip:chip + 1],
                layer["experts_out"][chip:chip + 1], top_k=8, held=(chip, chip + 1),
                scoring="sigmoid_normalised", bias=layer["router_bias"])
            # and the reference given the same share gives the same part
            mine = dict(layer, experts_in=layer["experts_in"][chip:chip + 1],
                        experts_out=layer["experts_out"][chip:chip + 1])
            theirs = ref._experts(u, mine, dict(cfg, held_experts=[chip, chip + 1]), lambda a: a)
            np.testing.assert_allclose(np.asarray(part), np.asarray(theirs - shared), atol=1e-5)
            total, held_picks = total + part, held_picks + int(counts["picks_held"])
    assert held_picks == 19 * 8
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=1e-5)


# ------------------------------------------------------- (f) sizes and table


def test_param_count_is_the_trees_leaves_at_published_widths():
    shapes = jax.eval_shape(family.make_init(PUBLISHED, "bfloat16", layout="program"),
                            jax.random.key(0))
    leaves = jax.tree.leaves(shapes)
    assert family.param_count(PUBLISHED) == sum(x.size for x in leaves) == 3_451_123_968
    tiny = jax.eval_shape(family.make_init(CFG, "float32", layout="reference"), jax.random.key(0))
    assert family.param_count(CFG) == sum(x.size for x in jax.tree.leaves(tiny)) < 5e6
    # the model `HybridLM` draws for itself has the benchmark's tree
    own = jax.eval_shape(lambda: HybridLM.init(family.make_lm(CFG, KEY, "float32"), KEY)[0])
    seeded = jax.eval_shape(family.make_init(CFG, "float32", layout="program"), KEY)
    assert jax.tree.structure(own) == jax.tree.structure(seeded)
    assert [a.shape for a in jax.tree.leaves(own)] == [a.shape for a in jax.tree.leaves(seeded)]


def test_the_configuration_keeps_every_published_width():
    row = next(json.loads(line) for line in
               Path("/opt/skills/guides/model-configs/architectures.jsonl").read_text().splitlines()
               if '"dots3-note-prev"' in line) if Path(
        "/opt/skills/guides/model-configs/architectures.jsonl").exists() else None
    if row is None:
        pytest.skip("no catalog here")
    differs = {k for k, v in row["config"].items() if PUBLISHED.get(k) != v}
    assert differs == set(PUBLISHED["reduced"])
    assert PUBLISHED["layer_types"] == row["config"]["layer_types"][:10]
    assert PUBLISHED["published"]["n_routed_experts"] == row["config"]["n_routed_experts"] == 256
    assert PUBLISHED["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert PUBLISHED["source"] == row["source_url"]


def test_an_unknown_layer_kind_is_refused_by_listing_the_tables():
    with pytest.raises(ValueError) as err:
        family.make_lm(dict(CFG, layer_types=["full_attention", "linear_attention"] * 2),
                       KEY, "float32")
    assert "linear_attention" in str(err.value)
    assert all(kind in str(err.value) for kind in MIXERS)


@pytest.mark.parametrize("key,other", [("routed_scaling_factor", 2.5),
                                       ("apply_mla_qkv_lora_rescale", False),
                                       ("attention_gate_type", "none"), ("scoring_func", "softmax")])
def test_the_one_form_the_program_computes_is_the_one_the_file_must_name(key, other):
    """The program has no knob for these: a configuration that names
    another form is refused, not served as if it had named this one."""
    with pytest.raises(ValueError, match="one form"):
        family.make_lm(dict(CFG, **{key: other}), KEY, "float32")


def test_experts_hit_counts_the_held_experts_a_call_gave_a_token(model):
    """`moe_experts_hit`, what `decode_required_bytes` charges the routed
    experts by: the last of the experts' counts, against the tokens an
    expert."""
    lm, params, _ = model
    p = params["blocks"][1]
    held = CFG["held_experts"][1] - CFG["held_experts"][0]
    seen = set()
    for rows, seed in ((1, 0), (1, 1), (2, 2), (40, 3)):
        u = jax.random.normal(jax.random.key(seed), (rows, 1, CFG["hidden_size"]))
        _, counts = lm._experts(p, u, None)
        counts = np.asarray(counts)
        assert counts.shape == (3 + held,)
        assert counts[-1] == (counts[2:-1] > 0).sum() <= min(held, counts[1])
        seen.add(int(counts[-1]))
    assert len(seen) > 1 and max(seen) == held   # few tokens leave experts unread


def test_the_family_counts_what_a_decode_step_reads():
    full, swa = (ref.sizes(PUBLISHED)[k] for k in ("full_attention", "sliding_attention"))
    assert family.kv_bytes_per_token(PUBLISHED, 2) == 4 * (576 + 128) * 2
    counts = {"moe_experts_hit": 72, "dsa_keys_scored": 1000, "dsa_rows_selected": 100,
              "swa_rows_attended": 10}
    every = 2 * 3_451_123_968 + 256 * 1000 + 1152 * 100 + 2176 * 10
    assert family.decode_required_bytes(PUBLISHED, counts, 2) == every
    # an expert no token picked is not read: 9 expert layers x 8 held, 28 of them hit
    some = family.decode_required_bytes(PUBLISHED, dict(counts, moe_experts_hit=28), 2)
    assert every - some == 44 * 3 * 5120 * 1536 * 2
    assert (full["heads"], swa["heads"]) == (128, 64)
    # operations: a selecting layer's attention stops growing at index_topk
    near, far = (family.forward_flops_per_token(PUBLISHED, n) for n in (8192, 16384))
    index_only = 4 * 2 * 64 * 128 * (16384 - 8192) / 2
    assert far - near == pytest.approx(index_only)


# --------------------------------- (f) a decode call's two ways to its picks


SLOTS, PLACES, BLOCK = 3, 64, 8


def _filled_pools(model, lengths):
    """A selecting layer's two pools after slot ``i`` prefilled
    ``lengths[i]`` tokens of its own seeded activations (one call, the whole
    table a chunk); -> (attn, the layer's weights, pools, tables)."""
    from tpu_dist.serve import paged_kv

    lm, params, _ = model
    attn, p = lm.mixers["full_attention"].attn, params["blocks"][0]["mixer"]
    pools, _ = paged_kv.init_latent_cache(attn, SLOTS, SLOTS * PLACES // BLOCK, BLOCK, jnp.float32)
    tables = jnp.arange(SLOTS * PLACES // BLOCK, dtype=jnp.int32).reshape(SLOTS, -1)
    x = jnp.stack([jax.random.normal(jax.random.key(100 + i), (PLACES, CFG["hidden_size"]))
                   for i in range(SLOTS)])
    pos = jnp.broadcast_to(jnp.arange(PLACES), (SLOTS, PLACES))
    _, pools, _ = paged_kv._paged_latent_attention(
        attn, p, x, pools, tables, pos, pos < jnp.asarray(lengths)[:, None], BLOCK)
    return attn, p, pools, tables


def _decode_call(attn, p, pools, tables, lengths):
    """One decode step: slot ``i``'s next token (seeded by the slot alone)
    at place ``lengths[i]``; -> (y, pools after, counts, x, positions)."""
    from tpu_dist.serve import paged_kv

    x = jnp.stack([jax.random.normal(jax.random.key(200 + i), (1, CFG["hidden_size"]))
                   for i in range(SLOTS)])
    pos = jnp.asarray(lengths, jnp.int32)[:, None]
    y, after, counts = jax.jit(lambda pools: paged_kv._paged_latent_attention(
        attn, p, x, pools, tables, pos, jnp.ones_like(pos, bool), BLOCK))(pools)
    return y, after, tuple(int(c) for c in counts), x, pos


def _the_parents_decode_read(attn, p, pools, tables, x, pos):
    """The decode step as it was before a call could read its pool in place
    (pools already written): `lax.top_k`, the picked rows fetched through
    the table one by one, `absorbed` over the fetched rows."""
    L = tables.shape[1] * BLOCK
    c_q, q_n, q_r = attn.queries(p, x, pos)
    q_i, w = attn.index_queries(p, x, c_q, pos)
    scores = attn.index_scores(q_i, w, pools["ik"][tables].reshape(SLOTS, L, -1))
    causal = jnp.arange(L)[None, None, :] <= pos[:, :, None]
    best, picks = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf)[:, 0], CFG["index_topk"])
    blk = jnp.take_along_axis(tables, picks // BLOCK, axis=1)
    seen = pools["ckv"][blk, picks % BLOCK][..., :attn.row]
    o = attn.absorbed(p, q_n, q_r, seen, (best > -jnp.inf)[:, None])
    return attn.output(p, x, o), picks


# slot 0 holds 50 either way; what the call's other two hold decides how it reads
FEW, MANY = [50, 7, 7], [50, 62, 62]


def test_a_decode_call_reads_in_place_or_fetches_by_what_it_holds(model):
    """The one `lax.cond` of a selecting layer's decode step, each arm
    forced by the call's own held / selected: three slots that hold 51 + 8
    + 8 places for 8 + 8 + 8 selected (2.8 a selected row: the pool is read
    in place under the picks' mask) and 51 + 63 + 63 for the same 24 (7.4:
    the picked rows are fetched).  Slot 0 holds the same rows and brings
    the same token in both calls and gets the same output through either
    arm; each call is the parent's step; `dsa_rows_read` says which arm ran."""
    from tpu_dist.serve import paged_kv

    topk = CFG["index_topk"]
    ys = {}
    for name, lengths in (("few", FEW), ("many", MANY)):
        attn, p, pools, tables = _filled_pools(model, lengths)
        y, after, (scored, selected, read), x, pos = _decode_call(attn, p, pools, tables, lengths)
        held = sum(n + 1 for n in lengths)
        assert (scored, selected) == (held, sum(min(n + 1, topk) for n in lengths))
        in_place = held <= paged_kv.READ_ALL_UNDER * selected
        assert in_place == (name == "few"), (held, selected, paged_kv.READ_ALL_UNDER)
        assert read == (held if in_place else selected)
        want, _ = _the_parents_decode_read(attn, p, after, tables, x, pos)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
        ys[name] = np.asarray(y[0])
    np.testing.assert_allclose(ys["few"], ys["many"], atol=1e-5)
    assert np.abs(ys["few"]).max() > 1e-2


def test_a_prefill_chunk_counts_the_rows_it_holds_as_read(model):
    from tpu_dist.serve import paged_kv

    attn, p, pools, tables = _filled_pools(model, [0] * SLOTS)
    x = jax.random.normal(jax.random.key(5), (SLOTS, 16, CFG["hidden_size"]))
    pos = jnp.broadcast_to(jnp.arange(16), (SLOTS, 16))
    real = pos < jnp.asarray([16, 9, 0])[:, None]
    _, _, (scored, selected, read) = paged_kv._paged_latent_attention(
        attn, p, x, pools, tables, pos, real, BLOCK)
    assert (int(scored), int(read)) == (16 * 17 // 2 + 9 * 10 // 2,) * 2
    assert int(selected) == sum(min(t + 1, CFG["index_topk"]) for n in (16, 9) for t in range(n))


def test_ties_at_the_kth_index_score_are_top_ks_set_in_place(model, monkeypatch):
    """Planted ties: every place a slot held before this step carries ONE
    index key, so all of them score alike and the k-th value is a tie
    between dozens of places.  The mask the pool is read under keeps what
    `lax.top_k` picks, the tied places of lowest index, and the step is the
    parent's, whose rows differ place by place."""
    from tpu_dist.serve import paged_kv

    topk = CFG["index_topk"]
    attn, p, pools, tables = _filled_pools(model, FEW)
    ik = pools["ik"]
    pools["ik"] = jnp.broadcast_to(ik[0, 0], ik.shape)
    masks = []
    attend = paged_kv._attend_rows_in_pool

    def seen(*a, keep, **how):   # the mask is the cond's own: a callback hands it out
        jax.debug.callback(lambda k: masks.append(np.asarray(k)), keep)
        return attend(*a, keep=keep, **how)

    monkeypatch.setattr(paged_kv, "_attend_rows_in_pool", seen)
    paged_kv._attend_picks_in_pool.clear_cache()     # traced before, it would not call `seen`
    x = jnp.stack([jax.random.normal(jax.random.key(200 + i), (1, CFG["hidden_size"]))
                   for i in range(SLOTS)])
    pos = jnp.asarray(FEW, jnp.int32)[:, None]
    y, after, (_, selected, read) = paged_kv._paged_latent_attention(
        attn, p, x, pools, tables, pos, jnp.ones_like(pos, bool), BLOCK)
    assert int(read) > int(selected)     # the pool was read in place
    want, picks = _the_parents_decode_read(attn, p, after, tables, x, pos)
    jax.effects_barrier()
    keep, = masks
    for s, n in enumerate(FEW):
        assert sorted(np.flatnonzero(keep[s]).tolist()) == sorted(
            {int(j) for j in np.asarray(picks[s]) if j <= n})
    # slot 0: 50 tied places and its own: the 7 or 8 lowest of the tie
    assert keep[0].sum() == topk and keep[0, :topk - 1].all() and (keep[0, topk - 1] or keep[0, 50])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    paged_kv._attend_picks_in_pool.clear_cache()     # nor keep the trace that does


@pytest.mark.parametrize("steps,want", [
    # every step read its pool in place | every step fetched its picks | the parent: no such count
    ([dict(dsa_rows_selected=100, dsa_rows_read=270), dict(dsa_rows_selected=50, dsa_rows_read=135)], 2.7),
    ([dict(dsa_rows_selected=100, dsa_rows_read=100)], 1.0),
    ([dict(dsa_keys_scored=270, dsa_rows_selected=100)], None),
    ([], None),
], ids=["in_place", "fetched", "no_counter", "no_steps"])
def test_the_benchmarks_reader_of_rows_read_over_selected(steps, want, monkeypatch):
    import types

    from chipbench.layer_metrics import dsa_read_over_selected as reader

    spans = {"engine.decode_apply": [types.SimpleNamespace(attrs=a) for a in steps]}
    monkeypatch.setattr(reader, "window_spans", lambda run: spans)
    assert reader.read(None) == (want if want is None else pytest.approx(want))
