"""Partition engine (parallel.partition): rule matching, the one
sharded train step across rule sets, trainer wiring, and the
composition the strategy builders refuse.

Parity discipline (the ISSUE acceptance bar): before any path is
re-routed, the rule-engine dp / fsdp / zero1 trajectories are pinned
against the PRE-EXISTING strategy implementations — params AND
optimizer state allclose over >= 3 steps on both trainers (SGD with
momentum, so the momentum buffer IS the running gradient record: buf_1
= g_1, and equality of (params, buf) per step implies gradient
equality).  Dropout-free models: the strategy builders fold the key per
rank while the global GSPMD step draws one global mask, so dropout is
the one intentional divergence.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tpu_dist import models, nn, parallel, train
from tpu_dist.models.transformer_lm import TransformerLM
from tpu_dist.parallel import partition as part

N = 8
ATOL = 2e-5
RTOL = 2e-4


def small_lm():
    return TransformerLM(vocab=64, dim=32, depth=2, heads=4, max_seq=32)


def conv_net():
    """mnist_net minus the Dropout layers (see module docstring)."""
    return nn.Sequential([
        nn.Conv2D(10, 5), nn.MaxPool2D(2), nn.relu(),
        nn.Conv2D(20, 5), nn.MaxPool2D(2), nn.relu(),
        nn.flatten(), nn.Dense(50), nn.relu(),
        nn.Dense(10), nn.log_softmax(),
    ])


def assert_trees_close(a, b, atol=ATOL, rtol=RTOL, what=""):
    fa = part.tree_paths(a)
    fb = jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for (path, x), y in zip(fa, fb):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), atol=atol, rtol=rtol,
            err_msg=f"{what}: {path}",
        )


# ------------------------------------------------------------ rule matching


class TestRuleMatching:
    def mesh(self):
        return part.build_mesh("dp=2,tp=4", platform="cpu")

    def test_first_match_wins_and_scalar_fallback(self):
        mesh = self.mesh()
        tree = {"a": {"w": jnp.zeros((8, 4)), "step": jnp.zeros(())}}
        rules = ((r"a/w$", P("dp", None)), (r".*", P(None, "tp")))
        specs = part.match_partition_rules(rules, tree, mesh)
        assert specs["a"]["w"] == P("dp")
        assert specs["a"]["step"] == P()  # scalars replicate, no rule hit

    def test_unmatched_leaf_raises(self):
        mesh = self.mesh()
        with pytest.raises(ValueError, match="no partition rule matched"):
            part.match_partition_rules(
                ((r"b/", P()),), {"a": jnp.zeros((4, 4))}, mesh
            )

    def test_non_divisible_axis_dropped(self):
        mesh = self.mesh()  # tp=4
        specs = part.match_partition_rules(
            ((r".*", P("tp")),), {"v": jnp.zeros((6,))}, mesh
        )
        assert specs["v"] == P()  # 6 % 4 != 0 -> replicated fallback

    def test_unknown_axis_raises(self):
        mesh = self.mesh()
        with pytest.raises(ValueError, match="mesh axis 'bogus'"):
            part.match_partition_rules(
                ((r".*", P("bogus")),), {"v": jnp.zeros((8,))}, mesh
            )

    def test_shard_over_picks_largest_divisible_dim(self):
        mesh = self.mesh()
        specs = part.match_partition_rules(
            ((r".*", part.shard_over("tp")),),
            {"w": jnp.zeros((3, 16)), "b": jnp.zeros((3,))}, mesh,
        )
        assert specs["w"] == P(None, "tp")
        assert specs["b"] == P()

    def test_same_rules_cover_optimizer_state_paths(self):
        """The opt tree nests params under m/v/buf — $-anchored param
        rules must still hit (the one-rule-set-for-both contract)."""
        mesh = self.mesh()
        opt_tree = {"m": {"mlp": {"fc1": {"w": jnp.zeros((8, 8))}}},
                    "step": jnp.zeros((), jnp.int32)}
        specs = part.match_partition_rules(
            ((r"mlp/fc1/w$", P(None, "tp")), (r".*", P())), opt_tree, mesh
        )
        assert specs["m"]["mlp"]["fc1"]["w"] == P(None, "tp")
        assert specs["step"] == P()

    def test_mesh_axes_parse_errors(self):
        with pytest.raises(ValueError, match="unknown mesh axis"):
            part.parse_mesh_axes("dp=2,banana=4")
        with pytest.raises(ValueError, match="no data axis"):
            part.parse_mesh_axes("tp=8")
        with pytest.raises(ValueError, match="prefix"):
            part.parse_mesh_axes("zero3:dp=8")
        with pytest.raises(ValueError, match="redundant"):
            part.parse_mesh_axes("zero1:fsdp=8")

    def test_resolve_rules_validates_mesh(self):
        mesh = part.build_mesh("dp=8", platform="cpu")
        with pytest.raises(ValueError, match="does not match the mesh"):
            part.resolve_rules("dp=2,fsdp=4", mesh)


# ------------------------------------------------- step parity vs strategies


def _mnist_batch(mesh, spec, gb=32):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(gb,) + models.IN_SHAPE).astype(np.float32)
    y = rng.integers(0, 10, gb).astype(np.int32)
    from jax.sharding import NamedSharding

    sh = NamedSharding(mesh, spec)
    return jax.device_put(x, sh), jax.device_put(y, sh)


def _run_steps(trainer, batches, steps=3):
    """Drive trainer.step directly; returns (params, opt_state) host
    trees after every step."""
    p, ms, os_ = trainer.params, trainer.model_state, trainer.opt_state
    out = []
    for i in range(steps):
        p, ms, os_, loss, _ = trainer.step(
            p, ms, os_, batches[i], jax.random.key(100 + i)
        )
        out.append((jax.tree.map(np.asarray, p), jax.tree.map(np.asarray, os_),
                    float(loss)))
    return out


def _legacy_logical(tree, template):
    """Legacy fsdp/zero1 (n, k) flat-row state -> logical shapes."""
    return parallel.fsdp_gather_params(tree, template)


class TestTrainerParity:
    """Rule-engine dp/zero1/fsdp == the strategy implementations, 3
    steps, params + opt state (MNIST-trainer half)."""

    def _trainers(self, legacy_cfg, engine_spec, cpu_devices):
        from tpu_dist import comm

        opt = lambda: train.sgd(0.05, momentum=0.9)  # noqa: E731
        mesh_l = comm.make_mesh(N, ("data",), mesh_devices=cpu_devices[:N])
        t_legacy = train.Trainer(
            conv_net(), models.IN_SHAPE, mesh_l,
            train.TrainConfig(**legacy_cfg), optimizer=opt(),
        )
        mesh_e = part.build_mesh(engine_spec, platform="cpu")
        t_engine = train.Trainer(
            conv_net(), models.IN_SHAPE, mesh_e,
            train.TrainConfig(mesh_axes=engine_spec), optimizer=opt(),
        )
        return t_legacy, t_engine, mesh_l, mesh_e

    def _compare(self, legacy_cfg, engine_spec, cpu_devices, template_of):
        t_l, t_e, mesh_l, mesh_e = self._trainers(
            legacy_cfg, engine_spec, cpu_devices
        )
        batches_l = [_mnist_batch(mesh_l, P("data")) for _ in range(3)]
        spec_e = t_e._ruleset.batch_spec()
        batches_e = [_mnist_batch(mesh_e, spec_e) for _ in range(3)]
        hist_l = _run_steps(t_l, batches_l)
        hist_e = _run_steps(t_e, batches_e)
        tmpl_p, tmpl_o = template_of(t_l)
        for i, ((pl, ol, ll), (pe, oe, le)) in enumerate(
            zip(hist_l, hist_e)
        ):
            assert ll == pytest.approx(le, rel=1e-5), f"step {i} loss"
            pl = _legacy_logical(pl, tmpl_p) if tmpl_p is not None else pl
            ol = _legacy_logical(ol, tmpl_o) if tmpl_o is not None else ol
            assert_trees_close(pe, pl, what=f"step {i} params")
            assert_trees_close(oe, ol, what=f"step {i} opt state")

    def test_engine_dp_matches_strategy_dp(self, cpu_devices):
        self._compare({}, f"dp={N}", cpu_devices, lambda t: (None, None))

    def test_engine_zero1_matches_strategy_zero1(self, cpu_devices):
        self._compare(
            {"zero1": True}, f"zero1:dp={N}", cpu_devices,
            lambda t: (None, {"buf": t._param_template}),
        )

    def test_engine_fsdp_matches_strategy_fsdp(self, cpu_devices):
        self._compare(
            {"fsdp": True}, f"fsdp={N}", cpu_devices,
            lambda t: (t._param_template, {"buf": t._param_template}),
        )


class TestLMTrainerParity:
    """Same bar on the LM trainer, plus the composed 2-D meshes the
    strategy builders cannot express: dp×fsdp and dp×tp must match the
    single-axis dp reference (same global batch => same gradients)."""

    def _lm_trainer(self, mesh, cfg_kw):
        return train.LMTrainer(
            small_lm(), mesh, train.LMTrainConfig(**cfg_kw),
            optimizer=train.sgd(0.05, momentum=0.9),
        )

    def _tokens(self, mesh, spec, gb=16, seq=32):
        from jax.sharding import NamedSharding

        rng = np.random.default_rng(1)
        t = rng.integers(0, 64, (gb, seq), dtype=np.int32)
        return (jax.device_put(t, NamedSharding(mesh, spec)),)

    def _run(self, trainer, mesh, steps=3):
        spec = (
            trainer._ruleset.batch_spec()
            if trainer._ruleset is not None
            else P(parallel.DATA_AXIS)
        )
        batches = [self._tokens(mesh, spec) for _ in range(steps)]
        p, os_ = trainer.params, trainer.opt_state
        out = []
        for i in range(steps):
            p, _, os_, loss, _ = trainer.step(
                p, {}, os_, batches[i], jax.random.key(7 + i)
            )
            out.append((jax.tree.map(np.asarray, p),
                        jax.tree.map(np.asarray, os_), float(loss)))
        return out

    def _engine_hist(self, spec, steps=3):
        mesh = part.build_mesh(spec, platform="cpu")
        t = self._lm_trainer(mesh, {"mesh_axes": spec})
        return self._run(t, mesh, steps), t

    @pytest.fixture(scope="class")
    def legacy_dp(self, cpu_devices):
        from tpu_dist import comm

        mesh = comm.make_mesh(N, ("data",), mesh_devices=list(cpu_devices)[:N])
        t = self._lm_trainer(mesh, {})
        return self._run(t, mesh), t

    def _check(self, hist_e, legacy, tmpl_of=None):
        hist_l, t_l = legacy
        for i, ((pl, ol, ll), (pe, oe, le)) in enumerate(
            zip(hist_l, hist_e)
        ):
            assert ll == pytest.approx(le, rel=1e-5), f"step {i} loss"
            if tmpl_of is not None:
                tp, to = tmpl_of(t_l)
                pl = _legacy_logical(pl, tp) if tp is not None else pl
                ol = _legacy_logical(ol, to) if to is not None else ol
            assert_trees_close(pe, pl, what=f"step {i} params")
            assert_trees_close(oe, ol, what=f"step {i} opt state")

    def test_engine_dp_matches_strategy_dp(self, legacy_dp):
        hist_e, _ = self._engine_hist(f"dp={N}")
        self._check(hist_e, legacy_dp)

    def test_engine_fsdp_matches_strategy_fsdp(self, cpu_devices):
        from tpu_dist import comm

        mesh = comm.make_mesh(N, ("data",), mesh_devices=list(cpu_devices)[:N])
        t_l = self._lm_trainer(mesh, {"fsdp": True})
        hist_l = self._run(t_l, mesh)
        hist_e, _ = self._engine_hist(f"fsdp={N}")
        self._check(
            hist_e, (hist_l, t_l),
            tmpl_of=lambda t: (t._param_template, {"buf": t._param_template}),
        )

    def test_engine_zero1_matches_strategy_zero1(self, cpu_devices):
        from tpu_dist import comm

        mesh = comm.make_mesh(N, ("data",), mesh_devices=list(cpu_devices)[:N])
        t_l = self._lm_trainer(mesh, {"zero1": True})
        hist_l = self._run(t_l, mesh)
        hist_e, _ = self._engine_hist(f"zero1:dp={N}")
        self._check(
            hist_e, (hist_l, t_l),
            tmpl_of=lambda t: (None, {"buf": t._param_template}),
        )

    def test_composed_dp_fsdp_matches_dp_reference(self, legacy_dp):
        hist_e, t = self._engine_hist("dp=2,fsdp=4")
        assert t._ruleset.name == "dp+fsdp"
        self._check(hist_e, legacy_dp)

    def test_composed_dp_tp_matches_dp_reference(self, legacy_dp):
        hist_e, t = self._engine_hist("dp=2,tp=4")
        assert t._ruleset.name == "dp+tp"
        self._check(hist_e, legacy_dp)

    def test_composed_mesh_state_is_actually_sharded(self):
        mesh = part.build_mesh("dp=2,fsdp=4", platform="cpu")
        t = self._lm_trainer(mesh, {"mesh_axes": "dp=2,fsdp=4"})
        qkv = t.opt_state["buf"]["blocks"][0]["attn"]["qkv"]["w"]
        full = int(np.prod(qkv.shape)) * qkv.dtype.itemsize
        shard = qkv.addressable_shards[0].data.nbytes
        assert shard * 8 == full  # 1/(dp*fsdp) of the momentum per chip


# ------------------------------------------------------------- user rules


class TestUserOverrides:
    @pytest.mark.parametrize("pin,want", [
        ("replicated", P()), ("None,fsdp", P(None, "fsdp")),
    ])
    def test_config_rules_pin_a_layer(self, pin, want):
        spec = f"fsdp={N}"
        mesh = part.build_mesh(spec, platform="cpu")
        rules = part.resolve_rules(
            spec, mesh, user_rules=[("embed/table$", pin)]
        )
        lm = small_lm()
        params, _ = lm.init(jax.random.key(0))
        specs = part.match_partition_rules(rules.param_rules, params, mesh)
        assert specs["embed"]["table"] == want  # pinned, as a spec string
        assert specs["blocks"][0]["mlp"]["fc1"]["w"] != P()  # builtin sharded

    def test_trainer_accepts_partition_rules(self):
        spec = f"fsdp={N}"
        mesh = part.build_mesh(spec, platform="cpu")
        t = train.LMTrainer(
            small_lm(), mesh,
            train.LMTrainConfig(
                mesh_axes=spec,
                partition_rules=[("embed/table$", "replicated")],
            ),
        )
        emb = t.params["embed"]["table"]
        assert emb.sharding.spec == P()  # pinned layer stayed replicated
        fc1 = t.params["blocks"][0]["mlp"]["fc1"]["w"]
        assert fc1.sharding.spec != P()


# ------------------------------------------------------ trainer validation


class TestTrainerValidation:
    def test_mesh_axes_excludes_strategy_flags(self):
        mesh = part.build_mesh(f"dp={N}", platform="cpu")
        with pytest.raises(ValueError, match="replaces the fsdp/zero1"):
            train.LMTrainer(
                small_lm(), mesh,
                train.LMTrainConfig(mesh_axes=f"dp={N}", fsdp=True),
            )
        with pytest.raises(ValueError, match="rule-set mode"):
            train.LMTrainer(
                small_lm(), mesh,
                train.LMTrainConfig(
                    mesh_axes=f"dp={N}", tensor_parallel="psum"
                ),
            )

    def test_compress_now_rides_the_engine(self):
        """ISSUE 12 lifts the old engine-mode refusals: grad_compress on
        a pure-dp AND on a model-sharded (dp×tp) engine config builds a
        working compressed step with the EF residual in the opt state."""
        for spec in (f"dp={N}", "dp=2,tp=4"):
            mesh = part.build_mesh(spec, platform="cpu")
            t = train.LMTrainer(
                small_lm(), mesh,
                train.LMTrainConfig(mesh_axes=spec, grad_compress="int8"),
            )
            assert t._partition.compress is not None
            assert "ef" in t.opt_state and "residual" in t.opt_state["ef"]
            assert t._compress_summary["wire"] == "int8"

    def test_compress_refusal_names_mode_in_legacy_trainer(self):
        from tpu_dist import comm

        mesh = comm.make_mesh((4, 2), ("data", "model"), platform="cpu")
        with pytest.raises(ValueError) as ei:
            train.LMTrainer(
                small_lm(), mesh,
                train.LMTrainConfig(
                    tensor_parallel="psum", grad_compress="int8"
                ),
            )
        msg = str(ei.value)
        assert "'model'" in msg
        assert "tensor_parallel" in msg


# -------------------------------------------------- checkpoint partition meta


class TestCheckpointPartitionMeta:
    def test_meta_roundtrip_and_mismatch_error(self, tmp_path):
        from tpu_dist.train import checkpoint

        spec = f"zero1:dp={N}"
        mesh = part.build_mesh(spec, platform="cpu")
        t = train.LMTrainer(
            small_lm(), mesh, train.LMTrainConfig(mesh_axes=spec)
        )
        path = tmp_path / "ck"
        checkpoint.save_sharded(
            path, {"params": t.params, "opt_state": t.opt_state},
            step=3, partition=t._partition_meta,
        )
        meta = checkpoint.read_meta(path)
        assert meta["partition"]["rules"] == "zero1"
        assert meta["partition"]["axes"] == {"dp": N}
        assert t.restore(path) == 3

        # a trainer on a DIFFERENT rule set / mesh elastically resumes:
        # restore() detects the provenance mismatch and redistributes
        # the saved shards onto this run's PartitionSpecs (PR 16)
        mesh2 = part.build_mesh("dp=2,fsdp=4", platform="cpu")
        t2 = train.LMTrainer(
            small_lm(), mesh2, train.LMTrainConfig(mesh_axes="dp=2,fsdp=4")
        )
        assert t2.restore(path) == 3
        for (kp, a), (_, b) in zip(
            checkpoint._flatten_with_paths(
                part.gather_replicated(t.params, mesh)
            )[0],
            checkpoint._flatten_with_paths(
                part.gather_replicated(t2.params, mesh2)
            )[0],
            strict=True,
        ):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=kp
            )

    def test_engine_fit_writes_meta_and_resumes(self, tmp_path):
        spec = "dp=2,fsdp=4"
        mesh = part.build_mesh(spec, platform="cpu")
        cfg = train.LMTrainConfig(
            mesh_axes=spec, epochs=1, global_batch=16, inflight_steps=0
        )
        t = train.LMTrainer(small_lm(), mesh, cfg)
        windows = np.random.default_rng(0).integers(
            0, 64, (32, 16), dtype=np.int32
        )
        t.fit(windows, checkpoint_dir=str(tmp_path))
        from tpu_dist.train import checkpoint

        ck = tmp_path / "lm_ckpt_0"
        assert checkpoint.read_meta(ck)["partition"]["rules"] == "dp+fsdp"
        t2 = train.LMTrainer(small_lm(), mesh, cfg)
        assert t2.restore(ck) == 1
        assert_trees_close(t2.params, t.params, what="resumed params")

    def test_checkpoint_without_meta_refused_in_engine_mode(self, tmp_path):
        from tpu_dist.train import checkpoint

        spec = f"zero1:dp={N}"
        mesh = part.build_mesh(spec, platform="cpu")
        t = train.LMTrainer(
            small_lm(), mesh, train.LMTrainConfig(mesh_axes=spec)
        )
        path = tmp_path / "bare"
        checkpoint.save_sharded(
            path, {"params": t.params, "opt_state": t.opt_state}, step=1
        )
        with pytest.raises(ValueError, match="no partition metadata"):
            t.restore(path)


# ------------------------------------------------------------- telemetry


class TestPartitionTelemetry:
    def test_manifest_and_epoch_carry_mesh_and_rules(self, tmp_path, monkeypatch):
        from tpu_dist.observe import events as ev_mod

        monkeypatch.setenv("TPU_DIST_TELEMETRY", str(tmp_path))
        monkeypatch.delenv("TPU_DIST_RUN_ID", raising=False)
        spec = "dp=2,fsdp=4"
        mesh = part.build_mesh(spec, platform="cpu")
        cfg = train.LMTrainConfig(
            mesh_axes=spec, epochs=1, global_batch=16, inflight_steps=0
        )
        t = train.LMTrainer(small_lm(), mesh, cfg)
        windows = np.random.default_rng(0).integers(
            0, 64, (32, 16), dtype=np.int32
        )
        t.fit(windows)
        count, errors = ev_mod.validate_dir(str(tmp_path))
        assert count > 0 and not errors, errors
        recs = ev_mod.read_events(str(tmp_path))
        man = next(r for r in recs if r["event"] == "manifest")
        assert man["partition"]["rules"] == "dp+fsdp"
        assert man["partition"]["axes"] == {"dp": 2, "fsdp": 4}
        ep = next(r for r in recs if r["event"] == "epoch")
        assert ep["mesh"]["rules"] == "dp+fsdp"
        assert ep["mesh"]["axes"] == {"dp": 2, "fsdp": 4}

    @pytest.mark.parametrize("spec,heads,found", [
        ("fsdp=4", 2, {"form": "flash", "axes": ["fsdp", None],
                       "per_device_shape": [1, 2, 1024, 16]}),
        ("dp=2,fsdp=2", 2, {"form": "flash", "axes": [["dp", "fsdp"], None],
                            "per_device_shape": [1, 2, 1024, 16]}),
        ("dp=2,tp=2", 2, {"form": "flash", "axes": ["dp", "tp"],
                          "per_device_shape": [2, 1, 1024, 16]}),
        # 3 heads over tp=2: what XLA can partition, the dense form
        ("dp=2,tp=2", 3, {"form": "dense", "axes": [],
                          "per_device_shape": [4, 3, 1024, 16]}),
    ])
    def test_the_first_step_says_what_attention_became(
            self, spec, heads, found, tmp_path, monkeypatch):
        """One line through the trainer's ``log`` and one `attention_form`
        event, after the step that traced the program and not again."""
        from tpu_dist.observe import events as ev_mod

        monkeypatch.setenv("TPU_DIST_TELEMETRY", str(tmp_path))
        monkeypatch.delenv("TPU_DIST_RUN_ID", raising=False)
        nn.attention._per_device_attention.cache_clear()
        logs = []
        mesh = part.build_mesh(spec, mesh_devices=jax.devices()[:4])
        lm = models.TransformerLM(vocab=64, dim=16 * heads, depth=2,
                                  heads=heads, max_seq=1024)
        t = train.LMTrainer(lm, mesh, train.LMTrainConfig(
            mesh_axes=spec, global_batch=4, log=logs.append))
        batch = (np.zeros((4, 1024), np.int32),)
        for i in range(2):
            (t.params, t._model_state, t.opt_state, loss, _) = t.step(
                t.params, t._model_state, t.opt_state, batch, jax.random.key(i))
        assert np.isfinite(float(loss))
        found = {**found, "calls": 2,
                 "per_device_traces": int(found["form"] == "flash")}
        recs = [r for r in ev_mod.read_events(str(tmp_path))
                if r["event"] == "attention_form"]
        assert [{k: r[k] for k in found} for r in recs] == [found]
        assert not ev_mod.validate_dir(str(tmp_path))[1]
        said = [m for m in logs if "attention under the partition engine" in m]
        assert len(said) == 1 and f": {found['form']}, " in said[0], logs

    def test_tpu_top_renders_mesh_column(self, tmp_path, monkeypatch):
        import importlib.util
        import sys as _sys

        spec = importlib.util.spec_from_file_location(
            "tpu_top", os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "tools", "tpu_top.py",
            ),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        state = mod.empty_state(str(tmp_path))
        state["manifest"] = {
            "event": "manifest", "run_id": "r1", "world": 8,
            "trainer": "LMTrainer", "platform": {"backend": "cpu"},
            "mesh": {"shape": {"dp": 2, "fsdp": 4}},
            "partition": {"rules": "dp+fsdp",
                          "axes": {"dp": 2, "fsdp": 4}},
            "time": 0.0,
        }
        txt = mod.render(state, now=1.0)
        assert "mesh dp=2,fsdp=4" in txt
        assert "rules dp+fsdp" in txt


# ------------------------------------------- engine compressed wire


class TestEngineCompressedWire:
    """ISSUE 12 acceptance: the int8 engine step tracks the uncompressed
    engine step within EF tolerance on dp, dp×fsdp, and dp×tp meshes —
    the quantized wire INSIDE the GSPMD program."""

    CCFG = "int8,bucket_bytes=32768,block=64"

    def _run(self, spec, compress, steps=8, lm=False):
        mesh = part.build_mesh(spec, platform="cpu")
        rules = part.resolve_rules(spec, mesh)
        from jax.sharding import NamedSharding

        if lm:
            m = small_lm()
            params, _ = m.init(jax.random.key(0))

            def loss_fn(p, tokens, key):
                from tpu_dist.models.transformer_lm import lm_loss

                logits, _ = m.apply(p, {}, tokens)
                return lm_loss(logits.astype(jnp.float32), tokens), {}

            rng = np.random.default_rng(1)
            batch = jax.device_put(
                rng.integers(0, 64, (16, 32), dtype=np.int32),
                NamedSharding(mesh, rules.batch_spec()),
            )
        else:
            m = conv_net()
            params, state = m.init(jax.random.key(0), models.IN_SHAPE)

            def loss_fn(p, batch, key):
                x, y = batch
                scores, _ = m.apply(p, state, x, train=False)
                return nn.nll_loss(scores, y), {}

            batch = _mnist_batch(mesh, rules.batch_spec())
        built = part.make_partitioned_train_step(
            loss_fn, train.sgd(0.05, momentum=0.9), mesh, params, rules,
            compress=compress,
        )
        p, o = built.params, built.opt_state
        losses = []
        for i in range(steps):
            p, o, loss, _ = built.step(p, o, batch, jax.random.key(i))
            losses.append(float(loss))
        full = parallel.gather_replicated(p, mesh)
        return losses, jax.tree.map(np.asarray, full), built

    @pytest.mark.parametrize("spec,lm", [
        (f"dp={N}", False),
        ("dp=2,fsdp=4", False),
        ("dp=2,tp=4", True),
    ])
    def test_int8_engine_tracks_exact_engine(self, spec, lm):
        exact, p_e, _ = self._run(spec, None, lm=lm)
        comp, p_c, built = self._run(spec, self.CCFG, lm=lm)
        # EF convergence tolerance (the PR 6 bar): losses track within a
        # few percent and the final states agree at quantization scale
        for i, (a, b) in enumerate(zip(exact, comp)):
            assert b == pytest.approx(a, rel=0.1, abs=5e-3), f"step {i}"
        for (path, x), y in zip(
            part.tree_paths(p_e), jax.tree.leaves(p_c)
        ):
            scale = float(np.max(np.abs(np.asarray(x)))) + 1e-8
            assert float(np.max(np.abs(np.asarray(x) - np.asarray(y)))) \
                < 0.12 * scale + 1e-5, path
        # EF state present, sane, and donated through the step
        assert built.compress is not None
        err = float(built.opt_state["ef"]["err"])
        assert err == 0.0  # the INITIAL state (live state was donated)

    def test_tp_leaves_compress_at_shard_shape(self):
        """dp×tp: the engine FlatPlan is built over MODEL-LOCAL shapes —
        tp-sharded leaves enter the wire at 1/|tp| of their size."""
        spec = "dp=2,tp=4"
        mesh = part.build_mesh(spec, platform="cpu")
        rules = part.resolve_rules(spec, mesh)
        m = small_lm()
        params, _ = m.init(jax.random.key(0))

        def loss_fn(p, tokens, key):
            from tpu_dist.models.transformer_lm import lm_loss

            logits, _ = m.apply(p, {}, tokens)
            return lm_loss(logits.astype(jnp.float32), tokens), {}

        built = part.make_partitioned_train_step(
            loss_fn, train.sgd(0.05), mesh, params, rules,
            compress=self.CCFG,
        )
        import math

        full_elems = sum(
            math.prod(l.shape) for l in jax.tree.leaves(params)
        )
        plan_elems = sum(math.prod(s) for s in built.flat_plan.shapes)
        assert plan_elems < full_elems  # tp-sharded leaves entered 1/|tp|
        # residual K dim carries the model-axis product back
        res = built.opt_state["ef"]["residual"]
        assert res.shape == (2, 2, built.flat_plan.K_pad * 4)

    def test_compressed_engine_plan_is_one_byte_on_data_axes(self):
        """ISSUE 12 acceptance (analyzer form): the compressed engine
        programs' plans carry s8 wire operands on the data axes and no
        wide f32 gradient collective; dp×tp leaves tp untouched."""
        from tpu_dist.analysis import canonical_program

        for name in ("engine_dp_int8", "engine_dp_fsdp_int8"):
            prog = canonical_program(name)
            kinds = {(c.kind, c.dtypes[0]) for c in prog.plan}
            assert any(dt == "s8" for _, dt in kinds), (name, kinds)
            assert not prog.findings() or all(
                f.severity != "error" for f in prog.findings()
            ), prog.findings()

    def test_ef_residual_checkpoints_under_dp_fsdp(self, tmp_path):
        """Satellite: EF residual save/restore round-trips through
        sharded directory checkpoints and latest_intact resume under
        dp×fsdp; a residual saved under a different rule set is rejected
        with the elastic-resume-pointing error."""
        from tpu_dist.train import checkpoint
        from tpu_dist.train.checkpoint import latest_intact

        spec = "dp=2,fsdp=4"
        mesh = part.build_mesh(spec, platform="cpu")
        cfg = train.LMTrainConfig(
            mesh_axes=spec, grad_compress="int8", epochs=1,
            global_batch=16, inflight_steps=0, log=lambda s: None,
        )
        t = train.LMTrainer(small_lm(), mesh, cfg)
        windows = np.random.default_rng(0).integers(
            0, 64, (32, 32), dtype=np.int32
        )
        t.fit(windows, checkpoint_dir=str(tmp_path))
        ck = tmp_path / "lm_ckpt_0"
        assert ck.is_dir()
        assert latest_intact(tmp_path) == ck
        t2 = train.LMTrainer(small_lm(), mesh, cfg)
        assert t2.restore(ck) == 1
        np.testing.assert_array_equal(
            np.asarray(t.opt_state["ef"]["residual"]),
            np.asarray(t2.opt_state["ef"]["residual"]),
        )
        assert np.abs(np.asarray(t2.opt_state["ef"]["residual"])).max() > 0

        # a different rule set elastically resumes: params are
        # redistributed bit-exactly.  The per-rank EF residual survives
        # here too — its physical shape is keyed on the DATA-rank count
        # (8 under both dp=2,fsdp=4 and zero1:dp=8), so redistribution
        # carries it; only a data-rank-count change zero-resets it
        # (compress.reset_resized_residual semantics).
        mesh_z = part.build_mesh(f"zero1:dp={N}", platform="cpu")
        t3 = train.LMTrainer(
            small_lm(), mesh_z,
            train.LMTrainConfig(
                mesh_axes=f"zero1:dp={N}", grad_compress="int8",
                log=lambda s: None,
            ),
        )
        assert t3.restore(ck) == 1
        for (kp, a), (_, b) in zip(
            checkpoint._flatten_with_paths(
                part.gather_replicated(t.params, mesh)
            )[0],
            checkpoint._flatten_with_paths(
                part.gather_replicated(t3.params, mesh_z)
            )[0],
            strict=True,
        ):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=kp
            )
        np.testing.assert_array_equal(
            np.asarray(t3.opt_state["ef"]["residual"]),
            np.asarray(t.opt_state["ef"]["residual"]),
        )


class TestEnginePerRankKeys:
    """Satellite: per-rank dropout keys under the engine — the
    compressed region folds the data-axis coordinate into the step key,
    so per-rank random streams differ (ROADMAP item 2(b))."""

    def test_per_rank_masks_differ_in_compressed_region(self):
        """A loss whose gradient IS its dropout mask: with one shared
        key, every data rank would draw the same mask and the mean
        gradient would equal rank 0's mask; with per-rank folded keys it
        equals the mean of per-rank masks.  Seeded, exact prediction."""
        spec = "dp=4"
        mesh = part.build_mesh(spec, platform="cpu")
        rules = part.resolve_rules(spec, mesh)
        params = {"w": jnp.zeros(())}

        def loss_fn(p, batch, key):
            (x,) = batch
            # mask shaped like the LOCAL batch shard inside the region
            mask = jax.random.bernoulli(key, 0.5, x.shape).astype(
                jnp.float32
            )
            return p["w"] * jnp.mean(mask * x), {}

        built = part.make_partitioned_train_step(
            loss_fn, train.sgd(1.0), mesh, params, rules,
            compress="bf16",  # scale-free wire: the sync is exact-ish
        )
        x = jnp.ones((16,), jnp.float32)
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as PS

        xb = jax.device_put(x, NamedSharding(mesh, PS("dp")))
        key = jax.random.key(123)
        p2, _, _, _ = built.step(
            built.params, built.opt_state, (xb,), key
        )
        got = -float(np.asarray(p2["w"]))  # sgd(1.0): -grad

        def rank_mask(r):
            k = jax.random.fold_in(key, r)
            return jax.random.bernoulli(k, 0.5, (4,)).astype(jnp.float32)

        per_rank = float(np.mean([np.mean(rank_mask(r)) for r in range(4)]))
        shared = float(np.mean(rank_mask(0)))
        assert got == pytest.approx(per_rank, abs=1e-6)
        if abs(per_rank - shared) > 1e-9:  # seeds almost surely differ
            assert got != pytest.approx(shared, abs=1e-9)

    def test_reused_prng_key_lint_true_negative_on_engine_programs(self):
        """The per-rank fold_in derives keys (it is not consumption) —
        the reused-prng-key lint stays clean on the engine LM program
        and the compressed engine programs."""
        from tpu_dist.analysis import canonical_program
        from tpu_dist.analysis.lints import lint_reused_keys

        for name in ("engine_dp_tp", "engine_dp_int8",
                     "engine_dp_fsdp_int8"):
            assert lint_reused_keys(canonical_program(name)) == []
