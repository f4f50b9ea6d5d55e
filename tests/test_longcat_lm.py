"""`HybridLM` as LongCat-Flash's language model (sublayers of latent
attention over every causal row, a dense feed-forward each, one routed
branch a layer that leaves after the first sublayer's attention and joins
at the layer's end, zero-compute experts among the router's outputs, an
untied head) against the plain reference
`chipbench/reference/longcat_flash_ref.py`: at the family's rehearsal size
on the CPU, float32, seeded random weights.

Tolerances.  Program and reference are both float32 here, so what separates
them is the order of additions (the absorbed form sums over the latent
where the reference sums over a head's values; the reference's attention
walks keys in blocks with a running maximum; the grouped product against a
loop over an expert's picks).  Logits are of order 1 and read 2e-6 apart;
``ATOL`` leaves a factor of a hundred, as `tests/test_latent_lm.py` does.  A
pick of the router that a rounding flips would read 1e-2: none does at these
seeds.  The program one precision step down (bfloat16 weights) misses
``ATOL`` by two orders: `test_one_precision_step_down_is_told_apart`; so do
the two planted faults of the cell's check (the zero experts' part dropped,
the branch joined a sublayer early): `test_the_faults_the_cell_plants_are_told_apart`.
"""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.families import longcat_flash as family
from chipbench.reference import longcat_flash_ref as ref
from tests.test_hybrid_lm import _serve_logits
from tpu_dist.serve import ServeConfig, ServeEngine

REPO = Path(__file__).resolve().parents[1]
PUBLISHED = json.loads((REPO / "chipbench/configs/LongCat-Flash-Omni.json").read_text())
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
    toks = _tokens((2, 53))
    got, _ = jax.jit(lambda t: lm.apply(params, {}, t))(toks)
    want = ref.forward(p_ref, toks, CFG)
    assert got.dtype == jnp.float32 and got.shape == (2, 53, CFG["vocab_size"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    # the logits are not flat and the served tokens vary: there is something to see
    assert float(np.asarray(want).std()) > 0.3
    assert len(set(np.asarray(want.argmax(-1)).ravel().tolist())) > 40
    back = family.to_reference(params)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(p_ref)))
    assert sum(a.size for a in jax.tree.leaves(params)) == family.param_count(CFG)


def test_the_configuration_states_the_published_widths_and_the_cut():
    c = PUBLISHED
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"]) == (
        6144, 64, 1536, 512)
    assert (c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]) == (128, 64, 128)
    assert (c["ffn_hidden_size"], c["expert_ffn_hidden_size"], c["moe_topk"]) == (12288, 2048, 12)
    assert (c["router_experts"], c["zero_expert_num"], c["routed_scaling_factor"]) == (512, 256, 6)
    assert c["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert c["published"] == {"num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072}
    # ISSUE 43's arithmetic, as the tree counts it
    assert family.param_count(c) == 5_172_749_312
    assert family.param_count(c) - family._routed_params(c) == 4 * 638_874_368 + 2 * 16384 * 6144 + 6144
    assert family.attended_row_bytes(c, 2) == 1280 and family.kv_bytes_per_token(c, 2) == 8 * 576 * 2
    counts = {"moe_experts_hit": 10, "mla_rows_attended": 1000}
    assert family.decode_required_bytes(c, counts, 2) == (
        2 * (family.param_count(c) - 64 * 37_748_736 + 10 * 37_748_736) + 1280 * 1000)


def test_the_mixer_has_no_gate_and_attends_every_row(model):
    """One sublayer's attention alone against the reference's, and told
    apart from a windowed one."""
    lm, params, p_ref = model
    x = jax.random.normal(jax.random.key(3), (1, 48, CFG["hidden_size"]))
    attn = lm.mixers["latent_attention"].attn
    assert "w_gate" not in params["blocks"][0]["mixer"] and not attn.gated
    got = attn.apply(params["blocks"][0]["mixer"], {}, x)[0][0]
    with jax.default_matmul_precision("highest"):
        want = ref._attention(x[0], p_ref["layers"][0]["sub"][0]["attn"], CFG, lambda a: a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert float(jnp.abs(want).max()) > 0.1


# ------------------------------------------------- (b) through the caches


@pytest.mark.parametrize("chunk", [16, 12])
def test_prefill_then_decode_logits_are_the_references(model, chunk):
    """Through the latent pools: prompts of unequal length in slots that are
    not the rows' own, chunk boundaries that leave ``real_len < chunk``, a
    slot idle beside them."""
    lm, params, p_ref = model
    prompts = [_tokens((n,), seed=n) for n in (21, 8, 33)]
    got = _serve_logits(lm, params, prompts, new=10, chunk=chunk, slots=[2, 0, 3])
    for prompt, mine in zip(prompts, got):
        seq = np.concatenate([prompt, mine.argmax(-1)[:-1].astype(np.int32)])
        want = np.asarray(ref.forward(p_ref, seq[None], CFG)[0, prompt.size - 1:])
        np.testing.assert_allclose(mine, want, atol=ATOL)


def _reference_gap(p_ref, prompt, served):
    """The widest gap by which a served token's logit lies below the
    reference's best at its position (the harness's comparison)."""
    seq = np.concatenate([prompt, served]).astype(np.int32)
    logits = np.asarray(ref.forward(p_ref, seq[None], CFG)[0])
    at = np.arange(prompt.size - 1, seq.size - 1)
    return float((logits[at].max(-1) - logits[at, seq[at + 1]]).max())


def test_the_engine_serves_the_references_tokens(model):
    """The normal path: submit, step.  More requests than slots, so every
    slot has a second tenant whose pool rows lie where the first one's lay;
    prompts that end inside a chunk, prefill chunks beside decode, eviction
    and refill.  The model's own counters ride the decode readback."""
    from tpu_dist.observe.registry import REGISTRY

    lm, params, p_ref = model
    eng = ServeEngine(lm, params, ServeConfig(
        max_batch=3, block_size=8, num_blocks=36, max_seq=96, prefill_chunk=16, prefill_batch=2))
    names = ("mla_rows_attended", "moe_picks", "moe_picks_held", "moe_picks_zero")
    total = lambda name: REGISTRY.counter(f"tpu_dist_serve_{name}_total").value()  # noqa: E731
    before = {name: total(name) for name in names}   # the registry is the process's
    prompts = [_tokens((n,), seed=100 + n) for n in (5, 16, 23, 40, 17, 33, 9, 48)]
    ids = [eng.submit(p, 30) for p in prompts]
    results = eng.run_until_drained()
    assert len({results[i].tokens.tolist()[-1] for i in ids}) > 4, "the answers differ"
    for p, i in zip(prompts, ids):
        assert results[i].tokens.size == 30
        assert _reference_gap(p_ref, p, results[i].tokens) < ATOL
    assert eng.allocator.used == 0
    count = lambda name: total(name) - before[name]  # noqa: E731
    # every position of every request but its last token was a query once, in every sublayer
    lengths = [p.size + 29 for p in prompts]
    sublayers = 2 * CFG["num_layers"]
    assert count("mla_rows_attended") == sublayers * sum(n * (n + 1) // 2 for n in lengths)
    assert count("moe_picks") == CFG["num_layers"] * CFG["moe_topk"] * sum(lengths)
    assert 0 < count("moe_picks_held") < count("moe_picks")
    # twelve outputs balanced by the calibrated bias, four of them zero experts: near a third
    assert 0.2 < count("moe_picks_zero") / count("moe_picks") < 0.5
    assert count("moe_picks_held") + count("moe_picks_zero") < count("moe_picks")


def test_a_layer_keeps_two_pools_and_nothing_else(model):
    lm, params, _ = model
    eng = ServeEngine(lm, params, ServeConfig(
        max_batch=3, block_size=8, num_blocks=36, max_seq=96, prefill_chunk=16))
    sublayers = 2 * CFG["num_layers"]
    # a latent row (24 values here) is stored as whole 128-lane tiles; no index keys
    assert [sorted(kv) for kv in eng.cache["kv"]] == [["ckv"]] * sublayers
    assert eng.kv_pool_bytes == sublayers * 37 * 8 * 128 * 4
    assert all(not st for st in eng.cache["state"]["layers"])
    held = CFG["held_experts"][1] - CFG["held_experts"][0]
    assert eng.state_bytes == 4 * (3 + held + 1 + 1)   # the experts' counts, zero picks, rows attended
    names = [name for name, _, _ in lm.serve_counters]
    assert names == ["moe_picks", "moe_picks_held", "moe_expert_tokens", "moe_experts_hit",
                     "moe_picks_zero", "mla_rows_attended"]


def test_one_precision_step_down_is_told_apart(model):
    lm, params, p_ref = model
    toks = _tokens((2, 37))
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a, params)
    got, _ = jax.jit(lambda t: lm.apply(low, {}, t))(toks)
    assert float(jnp.abs(got - ref.forward(p_ref, toks, CFG)).max()) > 20 * ATOL


def test_the_faults_the_cell_plants_are_told_apart(model, monkeypatch):
    """The zero experts' part dropped, and the branch joined one sublayer
    early: each moves the logits by orders more than ``ATOL``."""
    from tpu_dist.models import hybrid_lm

    lm, params, p_ref = model
    toks = _tokens((1, 37), seed=5)
    want = ref.forward(p_ref, toks, CFG)
    sound = float(jnp.abs(lm.apply(params, {}, toks)[0] - want).max())
    real = hybrid_lm.routed_experts
    monkeypatch.setattr(hybrid_lm, "routed_experts",
                        lambda *a, **kw: _without_zero_part(real, a, kw))
    dropped = float(jnp.abs(lm.apply(params, {}, toks)[0] - want).max())
    monkeypatch.setattr(hybrid_lm, "routed_experts", real)
    monkeypatch.setattr(type(lm), "_joins", lambda self, at: at % 2 == 0)   # where it was launched
    early = float(jnp.abs(lm.apply(params, {}, toks)[0] - want).max())
    assert sound < ATOL and dropped > 100 * ATOL and early > 100 * ATOL


def _without_zero_part(real, args, kw):
    """`routed_experts` with the picks on zero experts adding nothing (with
    the held experts' weights at zero it gives the zero part alone)."""
    y, counts = real(*args, **kw)
    x, router, w_in, w_out = args
    return y - real(x, router, 0 * w_in, 0 * w_out, **kw)[0], counts


# ------------------------------------------------ (c) the shares add up


def test_the_shares_add_up_to_the_uncut_layer():
    """At 16 experts that have weights and 8 that have none: the routed
    parts the four shares of 4 give (the program's `HybridLM._experts`, each
    told its range), with the zero experts' part, which every chip computes
    alike, counted ONCE, are what the uncut reference gives for the branch;
    and since the branch joins the stream by an addition at the layer's end,
    for the whole layer."""
    cfg = dict(CFG, num_layers=1, router_experts=16, zero_expert_num=8, n_routed_experts=16,
               held_experts=[0, 16], moe_topk=4)
    p_ref = family.make_init(cfg, "float32", layout="reference")(KEY)
    lp = p_ref["layers"][0]
    h = jax.random.normal(jax.random.key(11), (48, cfg["hidden_size"]))
    q = lambda a: a  # noqa: E731
    with jax.default_matmul_precision("highest"):
        a1, u = ref._leave(h, lp, cfg, q)
        whole = ref._moe(u, lp, cfg, q)
        zero_once = whole - ref._moe(u, lp, cfg, q, zero_part=False)
        assert float(jnp.abs(zero_once).max()) > 0.05, "some picks are zero experts"
        parts = []
        for lo in range(0, 16, 4):
            share = dict(cfg, n_routed_experts=4, held_experts=[lo, lo + 4])
            lm = family.make_lm(share, KEY, "float32")
            moe = {"router": lp["router"], "bias": lp["router_bias"],
                   "w_in": lp["experts_in"][lo:lo + 4], "w_out": lp["experts_out"][lo:lo + 4]}
            y, counts = lm._experts({"moe": moe}, u[None], None)
            parts.append(y[0] - zero_once)               # its routed part alone
            assert 0 < int(counts[1]) < int(counts[0])  # some picks land here, not all
        summed = sum(parts) + zero_once
        np.testing.assert_allclose(np.asarray(summed), np.asarray(whole), atol=2e-5)
        layer = lambda m: ref._rejoin(a1, u, m, lp, cfg, q)  # noqa: E731
        np.testing.assert_allclose(np.asarray(layer(summed)), np.asarray(layer(whole)), atol=2e-5)
        assert float(jnp.abs(layer(whole) - layer(parts[0] + zero_once)).max()) > 1e-2


def test_the_calibrated_bias_gives_the_zero_experts_their_share():
    """Over fresh tokens the reference's router, under the calibrated bias,
    spreads the picks evenly over ALL its outputs: the zero experts take
    ``zero / outputs`` of them (the published third), the held experts
    their share of the rest."""
    p_ref = family.make_init(CFG, "float32", layout="reference")(KEY)
    lp = p_ref["layers"][0]
    assert float(jnp.abs(lp["router_bias"]).max()) > 0
    toks = _tokens((4, 64), seed=9)
    with jax.default_matmul_precision("highest"):
        h = p_ref["wte"][toks.reshape(-1)][:256]
        _, u = ref._leave(h, lp, CFG, lambda a: a)
        _, idx, _ = ref._route(u, lp, CFG, lambda a: a)
    outputs = CFG["router_experts"] + CFG["zero_expert_num"]
    zero = float((idx >= CFG["router_experts"]).mean())
    assert abs(zero - CFG["zero_expert_num"] / outputs) < 0.08
    held = float((idx < CFG["held_experts"][1]).mean())
    assert abs(held - family.picks_held_per_token(CFG) / CFG["moe_topk"]) < 0.08
