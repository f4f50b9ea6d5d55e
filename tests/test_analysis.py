"""The SPMD program analyzer (`tpu_dist.analysis`): plan extraction must
be deterministic across retraces, the partition engine must be
plan-gated by blessed goldens (formerly pinned against the now-retired
legacy strategy builders — the ROADMAP
builder-retirement pin), every lint must fire on a seeded violation and
stay silent on every canonical program, and the golden gate must fail
readably when a plan changes."""

import json
import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_dist import analysis, parallel, train
from tpu_dist.analysis import lints as L
from tpu_dist.analysis import plan as plan_mod
from tpu_dist.analysis.programs import (
    CANONICAL,
    AnalysisProgram,
    _engine,
    _mlp_loss_pair,
    canonical_program,
)

N = 8


def _engine_built(spec, *, user_rules=None, donate=True):
    """A fresh engine program through the SAME builder the canonical
    registry uses (no cache), unpacked as (built, mesh, batch)."""
    prog = _engine(
        spec, name=f"test:{spec}", user_rules=user_rules, donate=donate
    )
    return prog.built, prog.mesh, prog.args[2]


# ---------------------------------------------------------- plan parsing


class TestHloParsing:
    def test_iota_replica_groups(self):
        assert plan_mod._parse_replica_groups("[1,8]<=[8]") == (
            tuple(range(8)),
        )
        assert plan_mod._parse_replica_groups("[2,4]<=[8]") == (
            (0, 1, 2, 3), (4, 5, 6, 7),
        )
        # transposed iota: groups over the MAJOR mesh axis
        assert plan_mod._parse_replica_groups("[4,2]<=[2,4]T(1,0)") == (
            (0, 4), (1, 5), (2, 6), (3, 7),
        )

    def test_explicit_replica_groups(self):
        assert plan_mod._parse_replica_groups("{{0,4},{1,5}}") == (
            (0, 4), (1, 5),
        )

    def test_axis_inference_on_2d_mesh(self):
        mesh = parallel.build_mesh("dp=2,fsdp=4", platform="cpu")
        idx = plan_mod._MeshIndex(mesh)
        assert idx.axes_for_groups([(0, 1, 2, 3), (4, 5, 6, 7)]) == ("fsdp",)
        assert idx.axes_for_groups(
            [(0, 4), (1, 5), (2, 6), (3, 7)]
        ) == ("dp",)
        assert idx.axes_for_groups([tuple(range(8))]) == ("dp", "fsdp")

    def test_ring_pairs_map_to_axis(self):
        mesh = parallel.build_mesh("dp=8", platform="cpu")
        idx = plan_mod._MeshIndex(mesh)
        fwd = [(i, (i + 1) % 8) for i in range(8)]
        assert idx.axes_for_pairs(fwd) == ("dp",)
        assert idx.axes_for_pairs([(0, 3)]) is None

    def test_minor_classification(self):
        c = plan_mod.Collective(
            kind="all-reduce", axes=("dp",), dtypes=("f32",),
            shapes=((),), bytes=4, elems=1,
        )
        assert c.minor
        big = plan_mod.Collective(
            kind="all-reduce", axes=("dp",), dtypes=("f32",),
            shapes=((784, 48),), bytes=784 * 48 * 4, elems=784 * 48,
        )
        assert not big.minor


# ------------------------------------------------------------ extraction


class TestExtraction:
    def test_engine_dp_plan_names_the_axis(self):
        plan = canonical_program("engine_dp").plan
        assert len(plan) >= 1
        assert all(c.kind == "all-reduce" for c in plan)
        assert all(c.axes == ("dp",) for c in plan)

    def test_stable_across_retraces(self):
        """Rebuilding + relowering the identical program yields the
        identical plan — goldens cannot flake on a retrace."""
        built1, mesh, batch = _engine_built(f"dp={N}")
        built2, _, _ = _engine_built(f"dp={N}")
        p1 = analysis.extract_plan(
            built1.step, (built1.params, built1.opt_state, batch,
                          jax.random.key(0)),
            mesh=mesh, name="a",
        )
        p2 = analysis.extract_plan(
            built2.step, (built2.params, built2.opt_state, batch,
                          jax.random.key(0)),
            mesh=mesh, name="a",
        )
        assert p1.collectives == p2.collectives
        assert p1.rows() == p2.rows()

    def test_plan_json_roundtrip(self):
        plan = canonical_program("engine_zero1").plan
        back = plan_mod.CollectivePlan.from_json(plan.to_json())
        assert back.collectives == plan.collectives
        assert back.mesh_axes == plan.mesh_axes

    def test_serve_decode_is_collective_free(self):
        assert len(canonical_program("serve_decode").plan) == 0

    def test_pipeline_plan_is_rings_plus_psum(self):
        plan = canonical_program("pipeline_1f1b").plan
        kinds = {c.kind for c in plan}
        assert "collective-permute" in kinds
        assert all(
            c.axes == ("pipe",)
            for c in plan
            if c.kind == "collective-permute"
        )
        assert kinds <= {"collective-permute", "all-reduce"}


# ----------------------------------------------------- engine-vs-legacy


class TestDiffPlans:
    def test_diff_of_a_plan_with_itself_is_empty(self):
        """diff_plans' reflexivity — the contract the (now-retired)
        engine-vs-legacy pins were built on; the builders are deleted,
        the goldens carry the plan gate forward."""
        a = canonical_program("engine_dp").plan
        assert analysis.diff_plans(a, a) == []

    def test_different_strategies_do_differ(self):
        diffs = analysis.diff_plans(
            canonical_program("engine_dp").plan,
            canonical_program("engine_fsdp").plan,
        )
        assert diffs  # fsdp gathers params; dp never does

    def test_compress_shows_up_as_a_plan_diff(self):
        diffs = analysis.diff_plans(
            canonical_program("engine_dp_int8").plan,
            canonical_program("engine_dp").plan,
        )
        joined = "\n".join(diffs)
        assert "s8" in joined  # the 1-byte wire is visible in the plan

    def test_rename_maps_axis_vocabularies(self):
        a = canonical_program("engine_dp").plan
        renamed = plan_mod._rename_axes(a, {"dp": "data"})
        assert renamed.mesh_axes == {"data": 8}
        assert analysis.diff_plans(a, renamed) != []
        assert analysis.diff_plans(a, renamed, rename={"data": "dp"}) == []

    def test_strict_catches_count_changes(self):
        # same signatures, different COUNT — doubled rather than dropped:
        # how many all-reduces the compiler leaves in engine_dp is its
        # combiner's call (XLA under jax 0.9 emits one)
        a = canonical_program("engine_dp").plan
        doubled = plan_mod.CollectivePlan(
            name="doubled", mesh_axes=a.mesh_axes,
            collectives=a.collectives * 2,
        )
        assert analysis.diff_plans(a, doubled) == []  # same signatures
        assert analysis.diff_plans(a, doubled, strict=True)


# ---------------------------------------------------------------- lints


class TestLintTrueNegatives:
    @pytest.mark.parametrize("name", list(CANONICAL))
    def test_canonical_program_is_clean(self, name):
        findings = canonical_program(name).findings()
        assert findings == [], "\n".join(str(f) for f in findings)


class TestHostTransferLint:
    def test_debug_print_in_jitted_fn_fires(self):
        def leaky(x):
            jax.debug.print("x={x}", x=x)
            return x * 2

        prog = AnalysisProgram(
            name="leaky", fn=jax.jit(leaky), args=(jnp.float32(1.0),)
        )
        findings = L.lint_host_transfer(prog)
        assert findings
        assert all(f.lint == "host-transfer" for f in findings)

    def test_pure_callback_fires(self):
        def cb(x):
            return jax.pure_callback(
                lambda a: np.asarray(a) * 2,
                jax.ShapeDtypeStruct((), jnp.float32), x,
            )

        prog = AnalysisProgram(
            name="cb", fn=jax.jit(cb), args=(jnp.float32(1.0),)
        )
        assert L.lint_host_transfer(prog)


class TestDonationLint:
    def test_undonated_engine_step_fires(self):
        built, mesh, batch = _engine_built(f"dp={N}", donate=False)
        prog = AnalysisProgram(
            name="undonated", fn=built.step,
            args=(built.params, built.opt_state, batch,
                  jax.random.key(0)),
            mesh=mesh, built=built, expect_donation=True,
        )
        findings = L.lint_donation(prog)
        assert [f.lint for f in findings] == ["missing-donation"]

    def test_donated_buffer_count_reads_the_alias_header(self):
        prog = canonical_program("engine_dp")
        assert L.donated_buffer_count(prog.hlo_text) >= (
            prog.donated_leaves or 1
        )


class TestCompressWireLint:
    def test_escaped_payload_fires(self):
        """An UNcompressed ENGINE step judged against the engine
        FlatPlan's expectations = the exact signature of an engine
        program that silently dropped to the f32 wire (the satellite's
        true-positive requirement)."""
        off = canonical_program("engine_dp")
        on = canonical_program("engine_dp_int8")
        fake = AnalysisProgram(
            name="escaped", fn=off.fn, args=off.args, mesh=off.mesh,
            compress=on.compress,
            compress_expectations=on.compress_expectations,
        )
        findings = L.lint_compress_wire(fake)
        assert findings
        assert all(f.lint == "compress-wire" for f in findings)

    def test_real_compressed_steps_are_clean(self):
        assert L.lint_compress_wire(
            canonical_program("engine_dp_int8")) == []
        assert L.lint_compress_wire(
            canonical_program("engine_dp_fsdp_int8")) == []


class TestDeadRuleLint:
    def test_dead_user_rule_warns_and_fires(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPU_DIST_TELEMETRY", str(tmp_path))
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            built, _, _ = _engine_built(
                f"fsdp={N}", user_rules=[("no/such/param$", "replicated")]
            )
        assert built.dead_rules == ("no/such/param$",)
        assert any("dead" in str(w.message) for w in caught)
        # the warning event rode telemetry
        from tpu_dist.observe import events as ev_mod

        recs = ev_mod.read_events(str(tmp_path))
        dead_evs = [
            r for r in recs
            if r.get("event") == "warning" and r.get("dead_rules")
        ]
        assert dead_evs and dead_evs[0]["dead_rules"] == ["no/such/param$"]
        # and the lint twin reports it
        prog = AnalysisProgram(
            name="dead", fn=built.step, args=None, built=built
        )
        assert [f.lint for f in L.lint_dead_rules(prog)] == ["dead-rule"]

    def test_live_user_rule_is_not_dead(self):
        built, _, _ = _engine_built(
            f"fsdp={N}", user_rules=[(r"1/w$", "fsdp,None")]
        )
        assert built.dead_rules == ()

    def test_dead_user_rules_helper(self):
        mesh = parallel.build_mesh(f"fsdp={N}", platform="cpu")
        rules = parallel.resolve_rules(
            f"fsdp={N}", mesh,
            user_rules=[("nope$", "replicated"), (r"1/w$", "replicated")],
        )
        params = _mlp_loss_pair()[0]
        assert parallel.dead_user_rules(rules, params, mesh) == ("nope$",)

    def test_opt_state_only_rule_is_not_dead(self):
        """A user rule pinning a momentum leaf (a `buf/`-prefixed path
        that exists only in the optimizer tree) is a CORRECT
        configuration, not a dead rule."""
        built, _, _ = _engine_built(
            f"zero1:dp={N}", user_rules=[("^buf/", "replicated")]
        )
        assert built.dead_rules == ()


class TestResidencyLint:
    def test_pinned_replicated_big_leaf_under_fsdp_fires(self):
        built, _, _ = _engine_built(
            f"fsdp={N}", user_rules=[(r"1/w$", "replicated")]
        )
        prog = AnalysisProgram(
            name="resid", fn=built.step, args=None, built=built
        )
        findings = L.lint_replicated_residency(prog)
        assert findings
        assert all(f.lint == "replicated-residency" for f in findings)
        assert any("1/w" in f.message for f in findings)


class TestFallthroughLint:
    def test_unknown_big_param_under_tp_rules_fires(self):
        from tpu_dist.models.transformer_lm import TransformerLM, lm_loss

        spec = "dp=4,tp=2"
        mesh = parallel.build_mesh(spec, platform="cpu")
        rules = parallel.resolve_rules(spec, mesh)
        lm = TransformerLM(vocab=64, dim=32, depth=2, heads=4, max_seq=32)
        params, state = lm.init(jax.random.key(0))
        params = dict(params)
        params["mystery"] = {"w": jnp.zeros((128, 64), jnp.float32)}

        def loss_fn(p, tokens, key):
            logits, _ = lm.apply(
                {k: v for k, v in p.items() if k != "mystery"},
                state, tokens, train=False,
            )
            return (
                lm_loss(logits.astype(jnp.float32), tokens)
                + jnp.sum(p["mystery"]["w"]) * 0.0,
                {},
            )

        built = parallel.make_partitioned_train_step(
            loss_fn, train.sgd(0.05), mesh, params, rules, donate=True
        )
        prog = AnalysisProgram(
            name="fall", fn=built.step, args=None, built=built
        )
        findings = L.lint_replicated_fallthrough(prog)
        assert [f.lint for f in findings] == ["replicated-fallthrough"]
        assert "mystery/w" in findings[0].message


class TestUnplannedReshardLint:
    def test_fallthrough_user_rule_forcing_gather_fires(self):
        """The seeded violation: a user rule pinning a Dense weight's
        OUTPUT dim over dp inside a plain-dp rule set forces GSPMD to
        all-gather over dp inside the step — a replication round-trip
        no role of the rule set derives."""
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            prog = _engine(
                f"dp={N}", name="seeded_reshard",
                user_rules=[(r"1/w$", "None,dp")],
            )
        findings = L.lint_unplanned_reshard(prog)
        assert findings
        assert all(f.lint == "unplanned-reshard" for f in findings)
        assert any(f.detail["kind"] == "all-gather" for f in findings)
        assert "not derivable from rule set 'dp'" in findings[0].message

    def test_gather_over_dp_is_planned_under_zero1(self):
        # zero1 shards the update over dp: its output all-gather is part
        # of the plan, not a reshard (and plain dp's grad reduce is the
        # reduce-class allowance)
        assert L.lint_unplanned_reshard(
            canonical_program("engine_zero1")) == []
        assert L.lint_unplanned_reshard(
            canonical_program("engine_dp")) == []

    def test_permute_and_foreign_axis_flag(self):
        from tpu_dist.analysis.plan import Collective, CollectivePlan

        base = canonical_program("engine_dp")
        fake = AnalysisProgram(
            name="perm", fn=base.fn, args=base.args, mesh=base.mesh,
            built=base.built,
        )
        fake._cache["plan"] = CollectivePlan(
            name="perm", mesh_axes={"dp": N},
            collectives=(
                # the engine plans no rings: any permute is unplanned
                Collective(kind="collective-permute", axes=("dp",),
                           dtypes=("f32",), shapes=((1024,),),
                           bytes=4096, elems=1024),
                # reduce over an axis no role names
                Collective(kind="all-reduce", axes=("pipe",),
                           dtypes=("f32",), shapes=((1024,),),
                           bytes=4096, elems=1024),
            ),
        )
        findings = L.lint_unplanned_reshard(fake)
        assert sorted(f.detail["kind"] for f in findings) == [
            "all-reduce", "collective-permute",
        ]

    def test_minor_and_unrecognized_axes_are_skipped(self):
        from tpu_dist.analysis.plan import Collective, CollectivePlan

        base = canonical_program("engine_dp")
        fake = AnalysisProgram(
            name="quiet", fn=base.fn, args=base.args, mesh=base.mesh,
            built=base.built,
        )
        fake._cache["plan"] = CollectivePlan(
            name="quiet", mesh_axes={"dp": N},
            collectives=(
                # scalar plumbing: minor, never judged
                Collective(kind="collective-permute", axes=("dp",),
                           dtypes=("f32",), shapes=((1,),),
                           bytes=4, elems=1),
                # sub-ring groups the mesh index could not name
                Collective(kind="all-gather", axes=None,
                           dtypes=("f32",), shapes=((1024,),),
                           bytes=4096, elems=1024),
            ),
        )
        assert L.lint_unplanned_reshard(fake) == []

    def test_non_engine_programs_are_skipped(self):
        # no rule-set context: the pipeline engine's rings are planned
        # by the schedule, not a rule set
        assert L.lint_unplanned_reshard(
            canonical_program("pipeline_1f1b")) == []


class TestReusedKeyLint:
    def test_reused_key_fires(self):
        def bad(k):
            return jax.random.normal(k, (4,)) + jax.random.uniform(k, (4,))

        hits = analysis.find_reused_keys(bad, (jax.random.key(0),))
        assert hits and hits[0]["uses"] == 2

    def test_raw_uint32_key_reuse_fires(self):
        def bad(k):
            return jax.random.normal(k, (4,)) + jax.random.uniform(k, (4,))

        assert analysis.find_reused_keys(bad, (jax.random.PRNGKey(0),))

    def test_scan_carry_reuse_fires(self):
        def bad(k, xs):
            def body(c, x):
                return c, jax.random.normal(c, ()) + jax.random.uniform(
                    c, ()
                )

            return jax.lax.scan(body, k, xs)

        assert analysis.find_reused_keys(
            bad, (jax.random.key(0), jnp.arange(3.0))
        )

    def test_split_and_fold_in_are_clean(self):
        def good(k):
            k1, k2 = jax.random.split(k)
            a = jax.random.normal(k1, (4,))
            b = jax.random.uniform(jax.random.fold_in(k2, 7), (4,))
            return a + b

        assert analysis.find_reused_keys(good, (jax.random.key(0),)) == []

    def test_lint_wraps_findings(self):
        def bad(k):
            return jax.random.normal(k, (4,)) + jax.random.uniform(k, (4,))

        prog = AnalysisProgram(
            name="rng", fn=jax.jit(bad), args=(jax.random.key(0),)
        )
        assert [f.lint for f in L.lint_reused_keys(prog)] == [
            "reused-prng-key"
        ]


# --------------------------------------------------------------- goldens


class TestGoldens:
    def test_bless_then_compare_roundtrip(self, tmp_path):
        plan = canonical_program("engine_dp").plan
        plan_mod.save_golden(plan, str(tmp_path))
        golden = plan_mod.load_golden(str(tmp_path), "engine_dp")
        assert golden is not None
        assert plan_mod.compare_to_golden(plan, golden) == []

    def test_structure_change_fails_readably(self, tmp_path):
        plan = canonical_program("engine_dp").plan
        plan_mod.save_golden(plan, str(tmp_path))
        golden = plan_mod.load_golden(str(tmp_path), "engine_dp")
        # simulate a PR that added a reduce-scatter and inflated bytes
        golden["rows"][0]["bytes"] += 4
        golden["rows"].append({
            "kind": "reduce-scatter", "axes": ["dp"], "dtype": "f32",
            "count": 2, "bytes": 1024, "max_elems": 128,
        })
        diffs = plan_mod.compare_to_golden(plan, golden)
        assert any("reduce-scatter" in d for d in diffs)
        assert any("bytes" in d for d in diffs)

    def test_mesh_change_is_reported(self, tmp_path):
        plan = canonical_program("engine_dp").plan
        plan_mod.save_golden(plan, str(tmp_path))
        golden = plan_mod.load_golden(str(tmp_path), "engine_dp")
        golden["mesh_axes"] = {"dp": 4}
        assert any(
            "mesh axes" in d
            for d in plan_mod.compare_to_golden(plan, golden)
        )

    def test_version_skew_is_reported_not_failed(self, tmp_path):
        """Exact counts/bytes are an XLA-lowering artifact: a golden
        blessed under a DIFFERENT jax reports skew (and the CLI does
        not gate on it) instead of failing CI on a version bump."""
        plan = canonical_program("engine_dp").plan
        plan_mod.save_golden(plan, str(tmp_path))
        golden = plan_mod.load_golden(str(tmp_path), "engine_dp")
        assert golden["jax_version"] == jax.__version__
        assert plan_mod.golden_version_skew(golden) is None
        golden["jax_version"] = "0.0.1"
        assert plan_mod.golden_version_skew(golden) == "0.0.1"
        # CLI path: skewed golden -> exit 0, status "version-skew"
        import json as json_mod

        from tpu_dist.analysis.__main__ import main

        path = plan_mod.golden_path(str(tmp_path), "engine_dp")
        with open(path, "w") as fh:
            json_mod.dump(golden, fh)
        report = tmp_path / "r.json"
        assert main(
            ["--programs", "engine_dp", "--goldens", str(tmp_path),
             "--json", str(report), "-q"]
        ) == 0
        payload = json_mod.loads(report.read_text())
        assert payload["golden"]["engine_dp"] == "version-skew"


# ------------------------------------------------------------------- CLI


class TestCli:
    def test_list(self, capsys):
        from tpu_dist.analysis.__main__ import main

        assert main(["--list"]) == 0
        out = capsys.readouterr().out
        for name in CANONICAL:
            assert name in out

    def test_bless_then_gate(self, tmp_path, capsys):
        from tpu_dist.analysis.__main__ import main

        goldens = str(tmp_path / "goldens")
        sel = "engine_dp,engine_dp_int8"
        assert main(
            ["--programs", sel, "--goldens", goldens, "--bless", "-q"]
        ) == 0
        assert main(["--programs", sel, "--goldens", goldens, "-q"]) == 0
        # corrupt one golden -> the gate fails and names the row
        path = plan_mod.golden_path(goldens, "engine_dp")
        golden = json.load(open(path))
        golden["rows"][0]["count"] += 1
        with open(path, "w") as fh:
            json.dump(golden, fh)
        assert main(["--programs", sel, "--goldens", goldens]) == 1
        assert "GOLDEN DIFF" in capsys.readouterr().out

    def test_missing_golden_fails(self, tmp_path):
        from tpu_dist.analysis.__main__ import main

        assert main(
            ["--programs", "engine_dp", "--goldens",
             str(tmp_path / "none"), "-q"]
        ) == 1

    def test_report_json_and_analysis_event(self, tmp_path, monkeypatch):
        from tpu_dist.analysis.__main__ import main
        from tpu_dist.observe import events as ev_mod

        monkeypatch.setenv("TPU_DIST_TELEMETRY", str(tmp_path))
        report = tmp_path / "report.json"
        assert main(
            ["--programs", "engine_dp,engine_dp_int8", "--no-goldens",
             "--json", str(report), "-q"]
        ) == 0
        payload = json.loads(report.read_text())
        assert "engine_dp" in payload["programs"]
        assert "engine_dp_int8" in payload["programs"]
        recs = [
            r for r in ev_mod.read_events(str(tmp_path))
            if r.get("event") == "analysis"
        ]
        assert recs, "no analysis event emitted"
        assert ev_mod.validate_record(recs[-1]) == []
        assert recs[-1]["programs"] == 2

    def test_tpu_top_renders_analysis_line(self, tmp_path, monkeypatch):
        from tpu_dist.analysis.__main__ import main

        monkeypatch.setenv("TPU_DIST_TELEMETRY", str(tmp_path))
        assert main(
            ["--programs", "engine_dp", "--no-goldens", "-q"]
        ) == 0
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "tpu_top",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "tools", "tpu_top.py",
            ),
        )
        tpu_top = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tpu_top)
        out = tpu_top.render(tpu_top.collect(str(tmp_path)))
        assert "analysis" in out and "programs 1" in out
