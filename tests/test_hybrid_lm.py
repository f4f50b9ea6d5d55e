"""`HybridLM` (Mamba-2 and attention mixers over routed experts) against
the plain reference `chipbench/reference/granitemoehybrid_ref.py`, at the
family's rehearsal size on the CPU, float32, seeded random weights.

Tolerances.  Program and reference are both float32 here, so what
separates them is the order of additions: the chunked scan sums a chunk's
tokens by a matrix product where the reference walks them one by one, and
the grouped expert product sums a token's picks after a sort.  Logits are
of order 1 and read 1e-5 apart; ``ATOL`` leaves a factor of ten.  The same
comparisons with the program's products one precision step down
(bfloat16 weights and activations, the step below the float32 stated
here) read 1e-2 and fail it: `test_one_precision_step_down_is_told_apart`.
"""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.families import granitemoehybrid as family
from chipbench.reference import granitemoehybrid_ref as ref
from tpu_dist.ops.ssm_scan import causal_conv, ssd_chunked, ssm_step
from tpu_dist.parallel.moe import routed_experts
from tpu_dist.serve import ServeConfig, ServeEngine

REPO = Path(__file__).resolve().parents[1]
PUBLISHED = json.loads((REPO / "chipbench/configs/granite-4.0-h-small.json").read_text())
CFG = dict(PUBLISHED, **family.tiny(PUBLISHED))
ATOL = 2e-4
KEY = jax.random.key(7)


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
    toks = _tokens((2, 37))
    got, _ = jax.jit(lambda t: lm.apply(params, {}, t))(toks)
    want = ref.forward(p_ref, toks, CFG)
    assert got.dtype == jnp.float32 and got.shape == (2, 37, CFG["vocab_size"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    # the logits are not flat: the comparison has something to see
    assert float(np.asarray(want).std()) > 0.03
    back = family.to_reference(params)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(p_ref)))


def test_one_precision_step_down_is_told_apart(model):
    """(f) bfloat16 is the step below the float32 this test states: the
    program run in it misses `ATOL` by two orders."""
    lm, params, p_ref = model
    toks = _tokens((2, 37))
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a, params)
    got, _ = jax.jit(lambda t: lm.apply(low, {}, t))(toks)
    assert float(jnp.abs(got - ref.forward(p_ref, toks, CFG)).max()) > 20 * ATOL


# ------------------------------------------------- (b) through the cache


def _serve_logits(lm, params, prompts, *, new, chunk, slots=None, dtype=jnp.float32,
                  block_size=8, max_batch=4):
    """Prefill each prompt chunk by chunk, rows of unequal real length side
    by side as the engine packs them, then decode ``new`` greedy tokens a
    row, all through `apply_paged`; -> per prompt the ``new`` logit rows
    that chose its tokens (the last prompt position's first)."""
    slots = list(range(len(prompts))) if slots is None else slots
    blocks = -(-64 // block_size)
    cache = lm.init_serve_cache(max_batch, max_batch * blocks, block_size, dtype)
    tables = np.full((max_batch, blocks), max_batch * blocks, np.int32)
    for s in slots:
        tables[s] = np.arange(s * blocks, (s + 1) * blocks)
    step = jax.jit(lambda c, t, bt, pos, m, sl: lm.apply_paged(params, t, c, bt, pos, m, sl, block_size))
    first = {}
    for start in range(0, max(p.size for p in prompts), chunk):
        rows = [i for i, p in enumerate(prompts) if p.size > start]
        toks = np.zeros((len(rows), chunk), np.int32)
        real = np.zeros((len(rows),), np.int32)
        for r, i in enumerate(rows):
            part = prompts[i][start:start + chunk]
            toks[r, :part.size], real[r] = part, part.size
        sl = np.asarray([slots[i] for i in rows], np.int32)
        pos = start + np.arange(chunk, dtype=np.int32)[None].repeat(len(rows), 0)
        logits, cache, _ = step(cache, toks, tables[sl], pos, np.arange(chunk)[None] < real[:, None], sl)
        for r, i in enumerate(rows):
            if start + real[r] == prompts[i].size:
                first[i] = np.asarray(logits[r, real[r] - 1])
    out = [[first[i]] for i in range(len(prompts))]
    active = np.zeros((max_batch,), bool)
    active[slots] = True
    last = np.zeros((max_batch,), np.int32)
    index = np.zeros((max_batch,), np.int32)
    for i, s in enumerate(slots):
        last[s], index[s] = int(out[i][0].argmax()), prompts[i].size
    for _ in range(new - 1):
        logits, cache, _ = step(cache, last[:, None], tables, index[:, None], active[:, None], None)
        for i, s in enumerate(slots):
            out[i].append(np.asarray(logits[s, 0]))
            last[s] = int(out[i][-1].argmax())
        index[slots] += 1
    return [np.stack(rows) for rows in out]


@pytest.mark.parametrize("chunk", [16, 12])
def test_prefill_then_decode_logits_are_the_references(model, chunk):
    """Prompts of unequal length in slots that are not the rows' own, chunk
    boundaries that leave ``real_len < chunk`` (two scan chunks of 8 to a
    prefill chunk; at 12 the second is half pads), a slot idle beside them."""
    lm, params, p_ref = model
    prompts = [_tokens((n,), seed=n) for n in (21, 8, 33)]
    got = _serve_logits(lm, params, prompts, new=6, chunk=chunk, slots=[2, 0, 3])
    for prompt, mine in zip(prompts, got):
        seq = np.concatenate([prompt, mine.argmax(-1)[:-1].astype(np.int32)])
        want = np.asarray(ref.forward(p_ref, seq[None], CFG)[0, prompt.size - 1:])
        np.testing.assert_allclose(mine, want, atol=ATOL)


def _dense_greedy(apply, prompt, new, pad_to=64):
    """Greedy tokens by the dense path: the whole sequence again for every
    token (padded behind: the model is causal)."""
    toks = np.zeros((pad_to,), np.int32)
    toks[:prompt.size] = prompt
    for n in range(prompt.size, prompt.size + new):
        toks[n] = int(np.asarray(apply(toks[None])[0, n - 1]).argmax())
    return toks[prompt.size:prompt.size + new].tolist()


def test_the_engine_serves_the_dense_paths_tokens(model):
    """More requests than slots, so every slot is reused after an eviction
    (a state not reset at admission would carry the last request's over);
    prompts that end inside a chunk; the normal path: submit, step."""
    from tpu_dist.observe.registry import REGISTRY

    lm, params, _ = model
    eng = ServeEngine(lm, params, ServeConfig(
        max_batch=3, block_size=8, num_blocks=36, max_seq=96, prefill_chunk=16, prefill_batch=2))
    # the registry is the process's: what other tests' engines counted is taken off
    total = lambda name, **kw: REGISTRY.counter(f"tpu_dist_serve_{name}_total").value(**kw)  # noqa: E731
    experts = [str(e) for e in range(*CFG["held_experts"])]
    before = {name: total(name) for name in ("moe_picks", "moe_picks_held", "moe_experts_hit")}
    before_expert = [total("moe_expert_tokens", expert=e) for e in experts]
    prompts = [_tokens((n,), seed=100 + n) for n in (5, 16, 23, 40, 17, 33, 9, 48)]
    ids = [eng.submit(p, 7) for p in prompts]
    results = eng.run_until_drained()
    served = {results[i].tokens.tolist()[0] for i in ids}
    assert len(served) > 2, "the weights make the answers differ"
    apply = jax.jit(lambda t: lm.apply(params, {}, t)[0])
    for p, i in zip(prompts, ids):
        assert results[i].tokens.tolist() == _dense_greedy(apply, p, 7)
    assert eng.allocator.used == 0
    # the model's own counters rode the decode readback into the registry
    picks = total("moe_picks") - before["moe_picks"]
    held = total("moe_picks_held") - before["moe_picks_held"]
    per_expert = [total("moe_expert_tokens", expert=e) - b for e, b in zip(experts, before_expert)]
    assert picks > 0 and 0 < held < picks and sum(per_expert) == held
    # a held expert given a token in a layer's call is one whose weights were read
    hit = total("moe_experts_hit") - before["moe_experts_hit"]
    assert 0 < hit <= held
    assert picks % (CFG["num_experts_per_tok"] * CFG["num_hidden_layers"]) == 0


def test_state_bytes_are_accounted_beside_weights_and_pool(model):
    lm, params, _ = model
    eng = ServeEngine(lm, params, ServeConfig(
        max_batch=3, block_size=8, num_blocks=36, max_seq=96, prefill_chunk=16, bytes_limit=1))
    held = CFG["held_experts"][1] - CFG["held_experts"][0]
    assert eng.state_bytes == 3 * family.state_bytes_per_slot(CFG) + 4 * (3 + held)
    pool = 2 * 37 * 8 * CFG["num_key_value_heads"] * 16 * 4   # one attention layer, float32
    assert eng.kv_pool_bytes == pool * CFG["layer_types"].count("attention")
    bd = eng.memory_breakdown()
    assert bd["state_bytes"] == eng.state_bytes
    assert bd["activation_headroom_bytes"] == 1 - eng.weights_bytes - pool - eng.state_bytes
    from tpu_dist.observe.registry import REGISTRY

    assert REGISTRY.gauge("tpu_dist_serve_state_bytes").value() == eng.state_bytes
    assert {"class": "state", "bytes": eng.state_bytes} in eng._resident_rows()


# ------------------------------------------------------------- (c) scan


_SCAN = dict(R=2, L=37, H=4, P=8, N=16)


def _scan_case(carried: bool, pad: int):
    """Inputs, and what the recurrence gives walked token by token."""
    R, L, H, P, N = _SCAN.values()
    k = jax.random.split(jax.random.key(11), 7)
    xs = jax.random.normal(k[0], (R, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (R, L, H)) - 2.0)
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    B, C = jax.random.normal(k[3], (R, L, N)), jax.random.normal(k[4], (R, L, N))
    D = jax.random.normal(k[5], (H,))
    S0 = jax.random.normal(k[6], (R, H, P, N)) if carried else jnp.zeros((R, H, P, N))
    mask = jnp.arange(L)[None, :] < jnp.array([L, L - pad])[:, None]

    def walk(S, t):
        y, S = ssm_step(xs[:, t], dt[:, t], A, B[:, t], C[:, t], D, S, mask[:, t])
        return S, y

    S_end, ys = jax.lax.scan(walk, S0, jnp.arange(L))
    return (xs, dt, A, B, C, D, S0, mask), jnp.moveaxis(ys, 0, 1), S_end


@pytest.mark.parametrize("carried,pad", [(False, 0), (True, 0), (True, 9), (False, 37)])
def test_chunked_scan_is_the_recurrence(carried, pad):
    """Chunks that do and do not divide the length, with and without a
    carried state, with a pad tail (up to a row that is all pads): the
    outputs of the real tokens and the state after the last real one."""
    args, want, S = _scan_case(carried, pad)
    mask = args[-1]
    for chunk in (4, 8, 13, 37, 64):
        y, S_end = ssd_chunked(*args, chunk=chunk)
        np.testing.assert_allclose(np.asarray(S_end), np.asarray(S), atol=2e-5)
        np.testing.assert_allclose(*(np.asarray(jnp.where(mask[..., None, None], a, 0.0))
                                     for a in (y, want)), atol=5e-5)
        if pad == _SCAN["L"]:   # all pads: the state is exactly as it was found
            assert np.array_equal(np.asarray(S_end[1]), np.asarray(args[6][1]))


def test_the_convolution_carries_its_window_past_pads():
    k = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(k[0], (2, 11, 6))
    w, b = jax.random.normal(k[1], (6, 4)), jax.random.normal(k[2], (6,))
    whole, _ = causal_conv(x, w, b, jnp.zeros((2, 3, 6)))
    real = jnp.array([5, 2])
    y1, win = causal_conv(x[:, :5], w, b, jnp.zeros((2, 3, 6)), jnp.arange(5)[None] < real[:, None])
    # row 0 took 5 tokens, row 1 only 2: each goes on from its own last real token
    nxt = jnp.stack([x[0, 5:9], x[1, 2:6]])
    y2, _ = causal_conv(nxt, w, b, win)
    np.testing.assert_allclose(np.asarray(y1[0]), np.asarray(whole[0, :5]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(y2[0]), np.asarray(whole[0, 5:9]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(y2[1]), np.asarray(whole[1, 2:6]), atol=1e-6)


# ---------------------------------------------------------- (d), (e) experts


def test_the_shares_add_up_to_the_uncut_layer():
    """(d) experts [0, E/2) and [E/2, E) each give their routed part, the
    shared expert is counted once, and the sum is what the UNCUT reference
    gives for the whole layer."""
    E = CFG["router_experts"]
    whole = dict(CFG, num_local_experts=E, held_experts=[0, E])
    lp = ref.init_parts(KEY, whole)[1][0]
    u = jax.random.normal(jax.random.key(1), (29, CFG["hidden_size"]))
    identity = lambda x: x  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = ref._experts(u, lp, whole, identity)
        shared = ref._gated(u, lp["shared_in"], lp["shared_out"], identity)
        parts, counts = [], []
        for lo, hi in ((0, E // 2), (E // 2, E)):
            y, c = routed_experts(u, lp["router"], lp["experts_in"][lo:hi], lp["experts_out"][lo:hi],
                                  top_k=CFG["num_experts_per_tok"], held=(lo, hi))
            parts.append(y)
            counts.append(c)
            half = dict(CFG, held_experts=[lo, hi])
            cut = {**lp, "experts_in": lp["experts_in"][lo:hi], "experts_out": lp["experts_out"][lo:hi]}
            # the reference, given the same share, gives the same part
            np.testing.assert_allclose(np.asarray(y + shared),
                                       np.asarray(ref._experts(u, cut, half, identity)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared), np.asarray(want), atol=1e-5)
    assert float(jnp.abs(parts[0]).max()) > 0.01 and float(jnp.abs(parts[1]).max()) > 0.01
    k = CFG["num_experts_per_tok"]
    assert int(counts[0]["picks"]) == 29 * k
    assert int(counts[0]["picks_held"]) + int(counts[1]["picks_held"]) == 29 * k


def test_no_token_is_dropped_when_all_pick_the_same_experts():
    """(e) every token routed to the same three experts, all held: each
    gets every token, and the result is the plain sum, token by token."""
    T, d, E, W, k = 50, 16, 8, 12, 3
    ks = jax.random.split(jax.random.key(2), 4)
    x = jnp.abs(jax.random.normal(ks[0], (T, d))) + 0.1
    router = jnp.zeros((d, E)).at[:, jnp.array([1, 4, 6])].set(jnp.array([3.0, 2.0, 1.0]))
    w_in, w_out = jax.random.normal(ks[1], (E, d, 2 * W)) * 0.3, jax.random.normal(ks[2], (E, W, d)) * 0.3
    mask = jnp.arange(T) < 41   # and pads do no work
    with jax.default_matmul_precision("highest"):
        y, c = routed_experts(x, router, w_in, w_out, top_k=k, mask=mask)
        g = jax.nn.softmax(jax.lax.top_k(x @ router, k)[0], axis=-1)
        want = sum(g[:, j:j + 1] * ref._gated(x, w_in[e], w_out[e], lambda a: a)
                   for j, e in enumerate((1, 4, 6)))
    np.testing.assert_allclose(np.asarray(y[:41]), np.asarray(want[:41]), atol=1e-5)
    assert np.asarray(c["expert_tokens"]).tolist() == [0, 41, 0, 0, 41, 0, 41, 0]
    assert int(c["picks"]) == int(c["picks_held"]) == 41 * k
    assert not np.asarray(y[41:]).any()


# ----------------------------------------------- the family's own counts


def test_the_familys_counts_are_of_what_is_held(model):
    lm, params, _ = model
    leaves = sum(a.size for a in jax.tree.leaves(params))
    assert family.param_count(CFG) == leaves < 5_000_000
    # the published cut: 36 of 72 experts, half the vocabulary, one period
    assert family.param_count(PUBLISHED) == 4_757_211_776
    mamba, attention, experts = family._per_layer_params(PUBLISHED)
    assert (mamba, attention) == (102_291_072, 41_947_136)
    assert experts == 4096 * 72 + 36 * 9_437_184 + 18_874_368 + 4096
    assert family.state_bytes_per_slot(PUBLISHED) == 9 * (128 * 64 * 128 + 3 * 8448) * 4
    assert family.kv_bytes_per_token(PUBLISHED, 2) == 2 * 8 * 128 * 2
    assert family.vocab_size(PUBLISHED) == 50176 == PUBLISHED["published"]["vocab_size"] // 2
    # operations follow the picks that land here, not all ten
    assert family.picks_held_per_token(PUBLISHED) == 5.0
    all_ten = dict(PUBLISHED, num_local_experts=72, held_experts=[0, 72])
    more = family.forward_flops_per_token(all_ten, 512) - family.forward_flops_per_token(PUBLISHED, 512)
    assert more == 10 * 5 * 6 * 4096 * 768
    assert family.forward_flops_per_token(PUBLISHED, 512) > 2 * (
        family.param_count(PUBLISHED) - 10 * 31 * 9_437_184 - 50176 * 4096)


def test_the_configuration_keeps_every_published_width():
    widths = dict(hidden_size=4096, num_attention_heads=32, num_key_value_heads=8,
                  mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128, mamba_d_conv=4,
                  mamba_expand=2, mamba_n_groups=1, mamba_chunk_size=256, intermediate_size=768,
                  shared_intermediate_size=1536, num_experts_per_tok=10, router_experts=72,
                  embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=16,
                  attention_multiplier=0.0078125, rms_norm_eps=1e-5)
    assert {k: PUBLISHED[k] for k in widths} == widths
    assert PUBLISHED["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert PUBLISHED["reduced"] == ["num_hidden_layers", "layer_types", "num_local_experts", "vocab_size"]
    assert (PUBLISHED["num_hidden_layers"], PUBLISHED["num_local_experts"], PUBLISHED["held_experts"],
            PUBLISHED["vocab_size"]) == (10, 36, [0, 36], 50176)
    sv = PUBLISHED["serve"]
    assert sv["num_blocks"] * sv["block_size"] == sv["max_batch"] * sv["max_seq"]
