"""Expert parallelism: the distributed MoE must match a dense reference
implementation of the same routing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import spmd_run as run
from tpu_dist import comm
from tpu_dist.parallel.moe import capacity_for, moe_mlp, stack_expert_params

N = 4  # experts = ranks
D, H, T = 8, 16, 12  # dim, hidden, tokens per rank


def _setup(seed=0):
    key = jax.random.key(seed)
    kg, kx, *ke = jax.random.split(key, 2 + 2 * N)
    gate_w = jax.random.normal(kg, (D, N))
    experts = [
        {
            "up": jax.random.normal(ke[2 * i], (D, H)) / np.sqrt(D),
            "down": jax.random.normal(ke[2 * i + 1], (H, D)) / np.sqrt(H),
        }
        for i in range(N)
    ]
    xs = jax.random.normal(kx, (N, T, D))  # per-rank token shards
    return gate_w, experts, xs


def _dense_reference(gate_w, experts, xs, capacity_factor=1.25):
    """Same routing/capacity semantics, computed with plain numpy loops."""
    cap = capacity_for(T, N, capacity_factor)
    out = np.zeros_like(np.asarray(xs))
    for r in range(N):  # source rank
        x = np.asarray(xs[r])
        scores = x @ np.asarray(gate_w)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        assign = scores.argmax(-1)
        counts = {e: 0 for e in range(N)}
        for t in range(T):
            e = int(assign[t])
            if counts[e] < cap:
                up, down = np.asarray(experts[e]["up"]), np.asarray(experts[e]["down"])
                hidden = jax.nn.gelu(jnp.asarray(x[t] @ up))
                y = np.asarray(hidden) @ down
                out[r, t] = probs[t, e] * y
                counts[e] += 1
    return out


def test_moe_matches_dense_reference():
    gate_w, experts, xs = _setup()
    stacked = stack_expert_params(experts)

    def fn(gate_w, stacked, xs):
        r = comm.rank()
        x_local = jax.lax.dynamic_index_in_dim(xs, r, 0, keepdims=False)
        up = jax.lax.dynamic_index_in_dim(stacked["up"], r, 0, keepdims=False)
        down = jax.lax.dynamic_index_in_dim(stacked["down"], r, 0, keepdims=False)
        y, stats = moe_mlp(
            x_local, gate_w, up, down, axis_name=comm.DEFAULT_AXIS
        )
        return y, stats["dropped_fraction"]

    out, dropped = run(fn, gate_w, stacked, xs, world=N)
    expect = _dense_reference(gate_w, experts, xs)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4, atol=1e-5)
    assert float(np.asarray(dropped).max()) <= 1.0


def test_moe_differentiable():
    gate_w, experts, xs = _setup(1)
    stacked = stack_expert_params(experts)

    def fn(gate_w, stacked, xs):
        r = comm.rank()

        def loss(args):
            gw, st = args
            x_local = jax.lax.dynamic_index_in_dim(xs, r, 0, keepdims=False)
            up = jax.lax.dynamic_index_in_dim(st["up"], r, 0, keepdims=False)
            down = jax.lax.dynamic_index_in_dim(st["down"], r, 0, keepdims=False)
            y, _ = moe_mlp(x_local, gw, up, down, axis_name=comm.DEFAULT_AXIS)
            return jnp.sum(y**2)

        g = jax.grad(loss)((gate_w, stacked))
        return g

    g_gate, g_exp = run(fn, gate_w, stacked, xs, world=N)
    assert np.isfinite(np.asarray(g_gate)).all()
    assert any(
        float(np.abs(np.asarray(leaf)).max()) > 0
        for leaf in jax.tree.leaves(g_exp)
    ), "expert grads must be nonzero"


def test_capacity_drops_overflow():
    """With capacity_factor tiny, most tokens are dropped -> zeros in the
    output and a reported dropped fraction > 0."""
    gate_w, experts, xs = _setup(2)
    stacked = stack_expert_params(experts)

    def fn(gate_w, stacked, xs):
        r = comm.rank()
        x_local = jax.lax.dynamic_index_in_dim(xs, r, 0, keepdims=False)
        up = jax.lax.dynamic_index_in_dim(stacked["up"], r, 0, keepdims=False)
        down = jax.lax.dynamic_index_in_dim(stacked["down"], r, 0, keepdims=False)
        y, stats = moe_mlp(
            x_local, gate_w, up, down,
            axis_name=comm.DEFAULT_AXIS, capacity_factor=0.34,
        )
        return stats["dropped_fraction"]

    dropped = np.asarray(run(fn, gate_w, stacked, xs, world=N))
    assert dropped.max() > 0.0


def test_top2_equals_weighted_pair_of_experts_when_capacity_ample():
    """With 2 experts, top-2 routes EVERY token to both experts, so the
    output must equal g1*E1(x) + g2*E2(x) computed densely."""
    from tpu_dist.parallel.moe import moe_mlp_top2

    n, d, h, t = 2, 8, 16, 10
    key = jax.random.key(1)
    kg, kx, k1, k2, k3, k4 = jax.random.split(key, 6)
    gate_w = jax.random.normal(kg, (d, n))
    ups = jnp.stack([jax.random.normal(k1, (d, h)), jax.random.normal(k2, (d, h))]) / np.sqrt(d)
    downs = jnp.stack([jax.random.normal(k3, (h, d)), jax.random.normal(k4, (h, d))]) / np.sqrt(h)
    xs = jax.random.normal(kx, (n, t, d))

    def fn(gate_w, ups, downs, xs):
        r = comm.rank()
        x = jax.lax.dynamic_index_in_dim(xs, r, 0, keepdims=False)
        up = jax.lax.dynamic_index_in_dim(ups, r, 0, keepdims=False)
        down = jax.lax.dynamic_index_in_dim(downs, r, 0, keepdims=False)
        y, stats = moe_mlp_top2(
            x, gate_w, up, down, axis_name=comm.DEFAULT_AXIS,
            capacity_factor=float(n),  # ample: every token fits twice
        )
        return y, stats["balance_loss"], stats["dropped_fraction"]

    y, balance, dropped = run(fn, gate_w, ups, downs, xs, world=n)
    assert float(np.asarray(dropped).max()) == 0.0

    for r in range(n):
        x = np.asarray(xs[r])
        scores = x @ np.asarray(gate_w)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        order = np.argsort(-p, axis=-1)
        e1, e2 = order[:, 0], order[:, 1]
        p1 = np.take_along_axis(p, e1[:, None], 1)[:, 0]
        p2 = np.take_along_axis(p, e2[:, None], 1)[:, 0]
        g1, g2 = p1 / (p1 + p2), p2 / (p1 + p2)
        want = np.zeros_like(x)
        for i in range(t):
            def expert(e, v):
                hdn = np.asarray(jax.nn.gelu(jnp.asarray(v @ np.asarray(ups[e]))))
                return hdn @ np.asarray(downs[e])
            want[i] = g1[i] * expert(int(e1[i]), x[i]) + g2[i] * expert(int(e2[i]), x[i])
        np.testing.assert_allclose(np.asarray(y[r]), want, rtol=1e-4, atol=1e-5)


def test_top2_balance_loss_orders_routers():
    """A router that sends everything to one expert must score a higher
    balance loss than a near-uniform one."""
    from tpu_dist.parallel.moe import moe_mlp_top2

    n, d, h, t = 4, 8, 16, 16
    xs = jax.random.normal(jax.random.key(0), (n, t, d))
    ups = jnp.zeros((n, d, h))
    downs = jnp.zeros((n, h, d))
    skewed = jnp.zeros((d, n)).at[:, 0].set(5.0)  # everything -> expert 0
    mild = jax.random.normal(jax.random.key(2), (d, n)) * 0.01

    def fn(gate_w, xs):
        r = comm.rank()
        x = jax.lax.dynamic_index_in_dim(xs, r, 0, keepdims=False)
        up = jnp.zeros((d, h))
        down = jnp.zeros((h, d))
        _, stats = moe_mlp_top2(
            x, gate_w, up, down, axis_name=comm.DEFAULT_AXIS
        )
        return stats["balance_loss"]

    b_skew = float(np.asarray(run(fn, skewed, xs, world=n)).mean())
    b_mild = float(np.asarray(run(fn, mild, xs, world=n)).mean())
    assert b_skew > b_mild
    # near-uniform routing sits near the perfect-balance value of 1.0
    np.testing.assert_allclose(b_mild, 1.0, atol=0.2)


class TestMoELM:
    """The MoE TransformerLM (VERDICT r4 #7): top-2 experts inside the
    model, trained end-to-end with expert parallelism."""

    def _lm(self, experts=2, balance=0.0, cap=8.0):
        from tpu_dist import models

        return models.TransformerLM(
            vocab=32, dim=16, depth=2, heads=2, max_seq=16,
            moe_experts=experts, moe_balance_weight=balance,
            moe_capacity_factor=cap,  # ample: no token ever drops
        )

    def test_dense_moe_equals_mlp_when_experts_identical(self):
        """With every expert holding the SAME weights, top-2 combine
        (gates summing to 1) must reduce to the plain MLP block."""
        from tpu_dist import models

        lm = self._lm()
        params, _ = lm.init(jax.random.key(0))
        # make both experts identical
        for pb in params["blocks"]:
            pm = pb["moe"]
            pm["up"] = jnp.stack([pm["up"][0]] * 2)
            pm["down"] = jnp.stack([pm["down"][0]] * 2)
        tokens = models.synthetic_tokens(4, 8, 32)
        logits_moe, _ = lm.apply(params, {}, tokens)

        # the equivalent dense-MLP model: same non-moe params, mlp
        # weights = the (shared) expert weights.  The zoo MLP has
        # biases; zero them to mirror the bias-free expert math.
        mlp_lm = models.TransformerLM(
            vocab=32, dim=16, depth=2, heads=2, max_seq=16
        )
        mlp_params, _ = mlp_lm.init(jax.random.key(0))
        for pb_m, pb in zip(mlp_params["blocks"], params["blocks"]):
            pm = pb["moe"]
            pb_m["mlp"]["fc1"]["w"] = pm["up"][0]
            pb_m["mlp"]["fc1"]["b"] = jnp.zeros_like(pb_m["mlp"]["fc1"]["b"])
            pb_m["mlp"]["fc2"]["w"] = pm["down"][0]
            pb_m["mlp"]["fc2"]["b"] = jnp.zeros_like(pb_m["mlp"]["fc2"]["b"])
        for shared in ("embed", "ln", "pos"):
            mlp_params[shared] = params[shared]
        for pb_m, pb in zip(mlp_params["blocks"], params["blocks"]):
            for k in ("ln1", "attn", "ln2"):
                pb_m[k] = pb[k]
        logits_mlp, _ = mlp_lm.apply(mlp_params, {}, tokens)
        np.testing.assert_allclose(
            np.asarray(logits_moe), np.asarray(logits_mlp),
            rtol=2e-5, atol=2e-5,
        )

    def test_ep_forward_matches_dense_moe(self):
        """The expert-parallel path (all_to_all dispatch, one expert per
        rank) must equal the dense every-expert evaluation when capacity
        is ample — same routing, same combine, no drops."""
        from tpu_dist import models

        N = 2
        lm = self._lm(experts=N)
        params, _ = lm.init(jax.random.key(1))
        tokens = models.synthetic_tokens(4, 8, 32)
        dense, _ = lm.apply(params, {}, tokens)

        def fn(params, tokens):
            r = comm.rank()
            local = jax.lax.dynamic_slice_in_dim(tokens, r * 2, 2, 0)
            logits, bal = lm.apply_moe_ep(params, local, comm.DEFAULT_AXIS)
            return logits

        out = np.asarray(run(fn, params, tokens, world=N))
        gathered = np.concatenate([out[r] for r in range(N)], axis=0)
        np.testing.assert_allclose(
            gathered, np.asarray(dense), rtol=2e-4, atol=2e-4
        )

    def test_ep_training_matches_dense_trajectory(self):
        """One EP training step (uniform data-axis pmean) == one dense
        single-device step on the same global batch — the gradient
        contract of apply_moe_ep, end to end through the step builder."""
        from tpu_dist import models, parallel, train

        N = 2
        lm = self._lm(experts=N)
        params, _ = lm.init(jax.random.key(2))
        tokens = models.synthetic_tokens(8, 8, 32)
        lr = 0.1

        def dense_loss(p):
            logits, _ = lm.apply(p, {}, tokens)
            return models.lm_loss(logits, tokens)

        g = jax.grad(dense_loss)(params)
        expect = jax.tree.map(lambda p_, g_: p_ - lr * g_, params, g)

        mesh = comm.make_mesh(N, ("data",), platform="cpu")

        def loss_fn(p, batch, key):
            (tok,) = batch
            return lm.loss_moe_ep(p, tok, parallel.DATA_AXIS), {}

        step = parallel.make_train_step(
            loss_fn, train.sgd(lr), mesh, donate=False
        )
        p_rep = parallel.replicate(params, mesh)
        o_rep = parallel.replicate(train.sgd(lr).init(params), mesh)
        batch = parallel.shard_batch((tokens,), mesh)
        p_rep, _, loss, _ = step(p_rep, o_rep, batch, jax.random.key(0))
        assert np.isfinite(float(loss))
        for e, got in zip(
            jax.tree.leaves(expect), jax.tree.leaves(p_rep), strict=True
        ):
            np.testing.assert_allclose(
                np.asarray(e), np.asarray(got), rtol=2e-4, atol=2e-5
            )

    def test_moe_trainer_mode_trains(self):
        """LMTrainer(moe=True): loss falls over a few epochs and the
        balance regularizer keeps gradients flowing to the router."""
        from tpu_dist import models, train

        N = 2
        lm = self._lm(experts=N, balance=0.01)
        mesh = comm.make_mesh(N, ("data",), platform="cpu")
        cfg = train.LMTrainConfig(
            epochs=3, global_batch=8, moe=True, log=lambda *_: None
        )
        trainer = train.LMTrainer(lm, mesh, cfg, optimizer=train.sgd(0.3))
        windows = np.asarray(models.synthetic_tokens(16, 8, 32))
        hist = trainer.fit(windows)
        assert hist[-1].mean_loss < hist[0].mean_loss

    def test_moe_trainer_world_mismatch_raises(self):
        from tpu_dist import train
        import pytest

        lm = self._lm(experts=4)  # != data-axis size 2
        mesh = comm.make_mesh(2, ("data",), platform="cpu")
        with pytest.raises(ValueError, match="moe_experts"):
            train.LMTrainer(
                lm, mesh, train.LMTrainConfig(moe=True, log=lambda *_: None)
            )

    def test_moe_cached_decode_matches_dense_prefill(self):
        """Cached decode routes through the same dense-MoE feed-forward
        (`_mlp_or_moe`): prefill logits == the dense forward, and
        generate produces the right shape."""
        from tpu_dist import models

        lm = self._lm()
        params, _ = lm.init(jax.random.key(3))
        tokens = models.synthetic_tokens(2, 6, 32)
        dense, _ = lm.apply(params, {}, tokens)
        cache = lm.init_cache(2, 16)
        logits, _ = lm.apply_cached(params, tokens, cache, 0)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(dense), rtol=2e-5, atol=2e-5
        )
        out = lm.generate(params, tokens, steps=3)
        assert out.shape == (2, 3)
        assert np.isfinite(np.asarray(out)).all()


class TestExpertChoice:
    """Expert-choice routing: experts pick their top-C tokens globally —
    perfectly balanced by construction, no balance auxiliary needed."""

    def _dense_reference(self, x, gate_w, ups, downs, cap):
        """Single-device restatement of the same math: per-expert global
        top-cap picks, outputs combined weighted by the router gate."""
        import jax.nn as jnn

        probs = jnn.softmax(x @ gate_w, axis=-1)  # (T, E)
        E = gate_w.shape[1]
        y = jnp.zeros_like(x)
        for e in range(E):
            top_w, top_idx = jax.lax.top_k(probs[:, e], cap)
            out = jax.nn.gelu(x[top_idx] @ ups[e]) @ downs[e]
            y = y.at[top_idx].add(top_w[:, None] * out)
        return y

    def test_matches_dense_reference(self):
        N, T_local, d, h = 4, 8, 16, 32
        key = jax.random.key(0)
        ks = jax.random.split(key, 4)
        x = jax.random.normal(ks[0], (N * T_local, d))
        gate_w = jax.random.normal(ks[1], (d, N)) * 0.3
        ups = jax.random.normal(ks[2], (N, d, h)) / jnp.sqrt(d)
        downs = jax.random.normal(ks[3], (N, h, d)) / jnp.sqrt(h)
        cap = int(T_local * 2.0)
        expect = self._dense_reference(x, gate_w, ups, downs, cap)

        from tpu_dist.parallel.moe import moe_mlp_expert_choice

        def fn(x, gate_w, ups, downs):
            r = comm.rank()
            local = jax.lax.dynamic_slice_in_dim(x, r * T_local, T_local, 0)
            y, stats = moe_mlp_expert_choice(
                local, gate_w, ups[r], downs[r],
                axis_name=comm.DEFAULT_AXIS, capacity_factor=2.0,
            )
            return y, stats["mean_experts_per_token"]

        ys, cover = run(fn, x, gate_w, ups, downs, world=N)
        gathered = np.concatenate([np.asarray(ys)[r] for r in range(N)], 0)
        np.testing.assert_allclose(
            gathered, np.asarray(expect), rtol=2e-4, atol=2e-4
        )
        # perfect balance by construction: every expert processes
        # exactly cap tokens; total picks = N*cap over N*T_local tokens
        total = float(np.asarray(cover).mean()) * N * T_local
        assert abs(total - N * cap) < 1e-3

    def test_differentiable(self):
        """Grads flow through dispatch, expert MLP, and gates."""
        from tpu_dist.parallel.moe import moe_mlp_expert_choice

        N, T_local, d, h = 2, 4, 8, 16
        ks = jax.random.split(jax.random.key(1), 4)
        x = jax.random.normal(ks[0], (N * T_local, d))
        gate_w = jax.random.normal(ks[1], (d, N)) * 0.3
        ups = jax.random.normal(ks[2], (N, d, h)) / jnp.sqrt(d)
        downs = jax.random.normal(ks[3], (N, h, d)) / jnp.sqrt(h)

        def fn(x, gate_w, ups, downs):
            def loss(gate_w, ups, downs):
                r = comm.rank()
                local = jax.lax.dynamic_slice_in_dim(
                    x, r * T_local, T_local, 0
                )
                y, _ = moe_mlp_expert_choice(
                    local, gate_w, ups[r], downs[r],
                    axis_name=comm.DEFAULT_AXIS,
                )
                return jax.lax.pmean(jnp.sum(y**2), comm.DEFAULT_AXIS)

            return jax.grad(loss, argnums=(0, 1, 2))(gate_w, ups, downs)

        g_gate, g_up, g_down = run(fn, x, gate_w, ups, downs, world=N)
        for g in (g_gate, g_up, g_down):
            a = np.asarray(g)
            assert np.isfinite(a).all()
            assert np.abs(a).sum() > 0

    def test_capacity_clamps_to_global_pool(self):
        """capacity_factor > axis size must clamp to the n*T pool, not
        crash inside top_k (review finding)."""
        from tpu_dist.parallel.moe import moe_mlp_expert_choice

        d, h, T = 8, 16, 4
        ks = jax.random.split(jax.random.key(2), 4)
        x = jax.random.normal(ks[0], (2 * T, d))
        gate_w = jax.random.normal(ks[1], (d, 2)) * 0.3
        ups = jax.random.normal(ks[2], (2, d, h))
        downs = jax.random.normal(ks[3], (2, h, d))

        def fn(x, gate_w, ups, downs):
            r = comm.rank()
            local = jax.lax.dynamic_slice_in_dim(x, r * T, T, 0)
            y, _ = moe_mlp_expert_choice(
                local, gate_w, ups[r], downs[r],
                axis_name=comm.DEFAULT_AXIS, capacity_factor=100.0,
            )
            return y

        ys = run(fn, x, gate_w, ups, downs, world=2)
        assert np.isfinite(np.asarray(ys)).all()


class TestZeroComputeExperts:
    """`routed_experts(scoring="softmax", zero_experts=n)`: the router's last
    ``n`` outputs have no weights; a pick on one adds ``g * x``."""

    D, E, ZERO, WIDTH, K = 16, 6, 3, 8, 3

    def _weights(self, seed=31):
        k = jax.random.split(jax.random.key(seed), 5)
        return (jax.random.normal(k[0], (14, self.D)),
                jax.random.normal(k[1], (self.D, self.E + self.ZERO)) * 0.7,
                jax.random.normal(k[2], (self.E, self.D, 2 * self.WIDTH)) * 0.3,
                jax.random.normal(k[3], (self.E, self.WIDTH, self.D)) * 0.3,
                jax.random.normal(k[4], (self.E + self.ZERO,)) * 0.02)

    def _loop(self, x, router, w_in, w_out, bias, held=None, scale=1.0):
        """The definition, one token and one pick at a time."""
        from jax import lax

        lo, hi = held or (0, self.E)
        prob = jax.nn.softmax(jnp.dot(x, router, precision=lax.Precision.HIGHEST), axis=-1)
        _, idx = lax.top_k(prob + bias, self.K)
        y, zero = np.zeros(x.shape, np.float32), 0
        for t in range(x.shape[0]):
            for e in idx[t].tolist():
                g = scale * float(prob[t, e])
                if e >= self.E:
                    y[t] += g * np.asarray(x[t])
                    zero += 1
                elif lo <= e < hi:
                    a, b = np.split(np.asarray(x[t] @ w_in[e - lo]), 2)
                    y[t] += g * np.asarray((jax.nn.silu(a) * b) @ w_out[e - lo])
        return y, idx, zero

    @pytest.mark.parametrize("held", [None, (2, 5)])
    def test_is_a_loop_over_the_picks_with_the_scores_unnormalised(self, held):
        from tpu_dist.parallel.moe import routed_experts

        x, router, w_in, w_out, bias = self._weights()
        lo, hi = held or (0, self.E)
        with jax.default_matmul_precision("highest"):
            want, idx, zero = self._loop(x, router, w_in[lo:hi], w_out[lo:hi], bias, held, 6.0)
            got, c = routed_experts(x, router, w_in[lo:hi], w_out[lo:hi], top_k=self.K, held=held,
                                    scoring="softmax", bias=bias, scale=6.0,
                                    zero_experts=self.ZERO)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
        assert int(c["picks"]) == 14 * self.K and int(c["picks_zero"]) == zero > 0
        assert int(c["picks_held"]) == int(((idx >= lo) & (idx < hi)).sum())
        # the default range is the experts that HAVE weights, not the router's width
        if held is None:
            assert c["expert_tokens"].shape == (self.E,)

    def test_a_token_whose_picks_are_all_zero_gets_its_gates_times_itself(self):
        """... and hands the grouped product no row; `picks_zero` counts it."""
        from jax import lax

        from tpu_dist.parallel.moe import routed_experts

        x, router, w_in, w_out, _ = self._weights(seed=32)
        bias = jnp.where(jnp.arange(self.E + self.ZERO) >= self.E, 5.0, 0.0)   # the zero experts win
        got, c = routed_experts(x, router, w_in, w_out, top_k=self.K, scoring="softmax", bias=bias,
                                scale=6.0, zero_experts=self.ZERO)
        prob = jax.nn.softmax(jnp.dot(x, router, precision=lax.Precision.HIGHEST), axis=-1)
        gates = 6.0 * prob[:, self.E:].sum(-1, keepdims=True)     # the bias enters no gate
        np.testing.assert_allclose(np.asarray(got), np.asarray(gates * x), rtol=1e-5, atol=1e-6)
        assert int(c["picks_zero"]) == 14 * self.K == int(c["picks"])
        assert int(c["picks_held"]) == 0 and not np.asarray(c["expert_tokens"]).any()
        # a pad token's picks count nowhere and add nothing
        mask = jnp.arange(14) % 2 == 0
        _, c = routed_experts(x, router, w_in, w_out, top_k=self.K, scoring="softmax", bias=bias,
                              zero_experts=self.ZERO, mask=mask)
        assert int(c["picks_zero"]) == 7 * self.K == int(c["picks"])

    def test_without_zero_experts_nothing_is_counted_or_added(self):
        from tpu_dist.parallel.moe import routed_experts

        x, router, w_in, w_out, _ = self._weights()
        _, c = routed_experts(x, router[:, :self.E], w_in, w_out, top_k=self.K, scoring="softmax")
        assert "picks_zero" not in c
        with pytest.raises(ValueError, match="held experts"):
            routed_experts(x, router, w_in, w_out, top_k=self.K, scoring="softmax")  # 9 outputs, 6 weights
