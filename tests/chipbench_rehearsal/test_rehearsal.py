"""CPU rehearsal: every cell end to end at a tiny preset through the same
code as on the chip, the controls, broken timed paths, and a cell added as
files only.  Nothing here is a measurement: no number of a CPU run is ever
written under a device metric's name, and the real entry refuses a CPU.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from chipbench import correct, schedule
from chipbench.families import gpt2
from chipbench.harness import run_cell, seed_key
from chipbench.kinds import serve
from chipbench.manifest import Manifest
from chipbench.reference import gpt2_ref

from .tiny import LIMITS, MODEL, REPO, make_tiny_root

CELLS = [w["name"] for w in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]
CFG = dict(MODEL, layer_norm_epsilon=1e-6)


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)


def _run(root, cell, *, seed=2**31 + 17, seconds=1.0, trace=False, control=False):
    return run_cell(root, cell, seed, seconds, trace, devices=jax.devices(),
                    t0=time.perf_counter(), control=control)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_runs_end_to_end_at_the_tiny_preset(tiny_root, cell, trace):
    result = _run(tiny_root, cell, trace=bool(trace))
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"  # a rehearsal, and it says so
    spec = Manifest(tiny_root).cell(cell)
    listed = {m["name"]: m for m in (spec.per_layer if trace else spec.end_to_end)}
    assert set(result["metrics"]) <= set(listed)
    for name, m in result["metrics"].items():
        assert m["unit"] == listed[name]["unit"] and np.isfinite(m["value"])
    if trace:
        # no device to trace here: what needs the trace or the peaks is left out
        assert any(n.startswith("compiles_in_window") for n in result["metrics"])
        assert all(result["metrics"][n]["value"] == 0 for n in result["metrics"]
                   if n.startswith("compiles_in_window"))
        assert "mfu_pct.train" not in result["metrics"]
        assert "busy_s" not in result["device"]
    else:
        assert set(result["metrics"]) == set(listed)
        assert all(m["value"] > 0 for m in result["metrics"].values())
    json.dumps(result)


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reports_every_per_layer_metric_of_its_cell(tiny_root, cell, monkeypatch):
    """Given a trace of as many chips as the cell has, and the chip's peaks,
    no reader finds nothing to read: the driver refuses a `--trace 1` line
    that lacks a metric which lists the cell."""
    from chipbench import harness
    from chipbench.peaks import peaks_for

    spec = Manifest(tiny_root).cell(cell)
    trace = {"busy_s": 0.2, "window_s": 0.3, "chips": spec.chips, "device_ops": [["fusion", 0.2]],
             "collective_share_pct": 12.5, "idle_gaps": [["train_step", 0.1]]}
    monkeypatch.setattr(harness.TraceSlice, "reduce", lambda self: trace)
    monkeypatch.setattr(harness, "_peaks", lambda dev: peaks_for("TPU v5 lite"))
    result = _run(tiny_root, cell, seconds=2.0, trace=True)
    # the CPU keeps no count of its memory's peak, so that reader alone has nothing
    assert set(result["metrics"]) == {
        m["name"] for m in spec.per_layer if not m["name"].startswith("hbm_peak_gb")}
    assert result["device"]["busy_s"] == 0.2 and result["device"]["window_s"] == 0.3
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_real_entry_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "train-medium-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "TPU" in proc.stderr


# ------------------------------------------------- reference against program


def test_reference_forward_is_the_programs_forward():
    lm = gpt2.make_lm(CFG, seed_key(3), "float32")
    params, _ = lm.init()
    ref = gpt2.make_init(CFG, "float32", layout="reference")(seed_key(3))
    toks = np.random.default_rng(0).integers(0, CFG["vocab_size"], (2, 48), dtype=np.int32)
    got, _ = lm.apply(params, {}, toks)
    want = gpt2_ref.forward(ref, toks, CFG)
    # float32 both sides: rounding order alone separates them
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    back = gpt2.to_reference(params)
    assert all(np.array_equal(back[k], ref[k]) for k in ref)
    assert float(gpt2_ref.loss(ref, toks, CFG)) == pytest.approx(
        float(gpt2_ref.loss(ref, toks, CFG, remat=True)), abs=1e-6)


def test_worst_leaf_gap_is_measured_against_the_median_leaf_at_least():
    ref = {"a": np.array([1.0, 2.0, 4.0]), "b": np.array(1e-9)}
    same = {k: v.copy() for k, v in ref.items()}
    assert correct.worst_leaf_gap(same, ref)[0] == 0.0
    off = dict(same, a=np.array([1.0, 2.2, 4.0]))
    gap, where = correct.worst_leaf_gap(off, ref)
    assert gap == pytest.approx(0.1) and where == "a[1]"
    # an all-but-zero leaf is held against the median leaf, not itself
    tiny = dict(same, b=np.array(3e-9))
    assert correct.worst_leaf_gap(tiny, ref)[0] < 1e-8


# ------------------------------------------------------------------ controls


def test_training_control_one_precision_down_is_not_correct():
    """The reference in the program's place with float8 matrix products
    (the step below the configuration's bfloat16) fails a limit; the
    reference in float32 against itself passes them all."""
    rows = schedule.token_rows({"seq_len": 64}, 11, 16, CFG["vocab_size"])
    batches = [rows[:8], rows[8:]]
    kw = dict(lr=3e-4, block_rows=4, devices=jax.devices()[:1])
    ref = correct.reference_training(gpt2, CFG, 11, batches, **kw)
    again = correct.reference_training(gpt2, CFG, 11, batches, **kw)
    low = correct.reference_training(gpt2, CFG, 11, batches, quant=correct.CONTROL_DTYPE, **kw)
    assert all(r.ok for r in correct.compare_training(again, ref, LIMITS["train"]))
    assert not all(r.ok for r in correct.compare_training(low, ref, LIMITS["train"]))


@pytest.mark.parametrize("cell", ["serve-medium-chat-rate", "serve-xl-decode-sat"])
def test_serving_control_one_precision_down_is_not_correct(tiny_root, cell, capfd):
    result = _run(tiny_root, cell, control=True, seed=23)
    err = capfd.readouterr().err
    assert result["correct"] is True
    control = [ln for ln in err.splitlines() if ln.startswith("chipbench control:")]
    assert control and all(ln.endswith("OVER") for ln in control)
    checked = [ln for ln in err.splitlines() if ln.startswith("chipbench correct: served")]
    assert checked and checked[0].endswith("ok")


def test_the_reference_sample_covers_every_slot_index_that_served():
    """One request a slot index at least, the longest of all, and seeded
    others up to `check_requests`; a slot that finished none is stood for
    by the request it still holds."""
    class Req:
        def __init__(self, n):
            self.tokens = list(range(n))

    def track(slot, prompt, seen, finished):
        plan = schedule.PlannedRequest(0.0, np.zeros(prompt, np.int32), seen)
        return serve.Track(plan=plan, req=Req(seen), due=0.0, submitted=0.0, slot=slot,
                           seen=seen, finished=finished)

    class Ctx:
        seed = 2**31 + 5

        class cell:
            traffic = {"check_requests": 5}

    class Drv:
        done = [track(s % 4, 10 + i, 3, 2.0) for i, s in enumerate(range(12))]
        live = [track(7, 50, 2, None), track(1, 5, 1, None), track(9, 5, 0, None)]

    Drv.done.append(track(2, 10, 3, 0.5))  # finished before the window opened
    got = serve._sample_served(Ctx, Drv, window_start=1.0)
    sizes = sorted(p.size + t.size for p, t in got)
    # slots 0-3 finished requests, slot 7 only holds one (and it is the longest)
    assert len(got) == 6 and sizes[-1] == 52
    assert serve._sample_served(Ctx, Drv, window_start=1.0)[0][0].size == got[0][0].size
    Ctx.cell.traffic = {"check_requests": 9}
    assert len(serve._sample_served(Ctx, Drv, window_start=1.0)) == 9


# ------------------------------------------------- broken timed paths


def test_a_training_step_that_returns_its_state_unchanged_is_not_correct(tiny_root, monkeypatch):
    from tpu_dist import train

    real_init = train.LMTrainer.__init__

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        step = self.step

        def unchanged(p, ms, os_, batch, key):
            keep = jax.tree.map(lambda x: x.copy(), (p, os_))  # step donates its state
            _, _, _, loss, aux = step(p, ms, os_, batch, key)
            return keep[0], ms, keep[1], loss, aux

        self.step = unchanged

    monkeypatch.setattr(train.LMTrainer, "__init__", init)
    result = _run(tiny_root, "train-medium-1chip")
    assert result["correct"] is False


def test_a_training_step_that_drops_part_of_the_batch_is_not_correct(tiny_root, monkeypatch):
    from tpu_dist import train

    real_init = train.LMTrainer.__init__

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        step = self.step

        def half(p, ms, os_, batch, key):
            (rows,) = batch
            n = rows.shape[0] // 2
            rows = jax.numpy.concatenate([rows[:n], rows[:n]])  # second half never seen
            return step(p, ms, os_, (rows,), key)

        self.step = half

    monkeypatch.setattr(train.LMTrainer, "__init__", init)
    assert _run(tiny_root, "train-medium-1chip")["correct"] is False


@pytest.mark.parametrize("cell", ["serve-medium-chat-rate", "serve-xl-decode-sat"])
def test_a_token_altered_where_it_is_produced_is_not_correct(tiny_root, cell, monkeypatch):
    from tpu_dist.serve import engine as engine_mod

    real = engine_mod.ServeEngine._build_decode_fn

    def build(self, *, greedy):
        fn = real(self, greedy=greedy)

        def altered(params, cache, ints, flt):
            toks, ints, cache = fn(params, cache, ints, flt)
            return (toks + 1) % self.lm.vocab, ints, cache  # what the host reads back

        return altered

    monkeypatch.setattr(engine_mod.ServeEngine, "_build_decode_fn", build)
    assert _run(tiny_root, cell)["correct"] is False


# --------------------------------------------------- driven by data


def test_a_cell_a_configuration_and_a_metric_are_added_as_files_only(tiny_root):
    """A later PR adds files and entries and edits no file that is there."""
    before = {p: p.read_bytes() for p in tiny_root.rglob("*") if p.is_file()
              and p.name != "BENCHMARK.json"}
    cfg = json.loads((tiny_root / "chipbench/configs/gpt2-medium.json").read_text())
    cfg.update(name="gpt2-wide", n_embd=96, n_head=6, family="wide")
    cfg["train"]["batch_tokens"] = 256  # a configuration's own, in its own file
    (tiny_root / "chipbench/configs/gpt2-wide.json").write_text(json.dumps(cfg))
    # an architecture of its own: a family file (here the same model under
    # other names for its sizes, with a count the harness can be seen to use)
    (tiny_root / "chipbench/families/wide.py").write_text(
        '"""A test\'s family."""\nfrom chipbench.families import gpt2 as _g\n'
        'reference, make_init, to_reference, make_lm = '
        '_g.reference, _g.make_init, _g.to_reference, _g.make_lm\n'
        'forward_flops_per_token, kv_bytes_per_token = '
        '_g.forward_flops_per_token, _g.kv_bytes_per_token\n\n\n'
        'def vocab_size(cfg):\n    return cfg["vocab_size"]\n\n\n'
        'def param_count(cfg):\n    return 4242\n')
    burst = json.loads((tiny_root / "chipbench/traffic/short-answer-open.json").read_text())
    burst["arrivals"] = {"gaps": "bursty", "burst": 4, "burst_gap_s": 0.0}
    (tiny_root / "chipbench/traffic/short-answer-burst.json").write_text(json.dumps(burst))
    (tiny_root / "chipbench/layer_metrics/engine_steps.py").write_text(
        '"""Engine steps in the window."""\n\n\ndef read(run):\n'
        '    return run.facts.get("engine_steps")\n')
    (tiny_root / "chipbench/layer_metrics/params_m.py").write_text(
        '"""Parameters, as the family counts them."""\n\n\ndef read(run):\n'
        '    return run.cell.family.param_count(run.cell.config)\n')
    doc = json.loads((tiny_root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "gpt2-wide", "source": "https://example.org/wide",
                           "file": "chipbench/configs/gpt2-wide.json", "reduced": [],
                           "why": "a test's configuration"})
    doc["workloads"].append({"name": "serve-wide-burst", "config": "gpt2-wide",
                             "traffic": "short-answer-burst", "chips": 1, "why": "a test's cell"})
    doc["workloads"].append({"name": "train-wide", "config": "gpt2-wide",
                             "traffic": "pretrain-1024", "chips": 1, "why": "a test's cell"})
    for m in doc["end_to_end"]:
        if m["name"] in ("ttft_mean_ms", "tpot_mean_ms"):
            m["workloads"].append("serve-wide-burst")
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("train-wide")
    doc["per_layer"].append({"name": "engine_steps", "unit": "count", "better": "lower",
                             "source": "program_counter", "layer": "serving",
                             "moves": "ttft_mean_ms", "workloads": ["serve-wide-burst"]})
    doc["per_layer"].append({"name": "params_m", "unit": "count", "better": "lower",
                             "source": "program_counter", "layer": "kernels",
                             "moves": "train_tokens_per_s", "workloads": ["train-wide"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(doc))
    assert Manifest(tiny_root).problems() == []
    plain = _run(tiny_root, "serve-wide-burst")
    assert plain["correct"] and set(plain["metrics"]) == {"ttft_mean_ms", "tpot_mean_ms", "setup_s"}
    traced = _run(tiny_root, "serve-wide-burst", trace=True)
    assert set(traced["metrics"]) == {"engine_steps"} and traced["metrics"]["engine_steps"]["value"] > 0
    # a third training configuration under a traffic mix that is already there
    trained = _run(tiny_root, "train-wide", trace=True)
    assert trained["correct"] and trained["attempted"] > 0
    assert trained["metrics"]["params_m"]["value"] == 4242
    assert all(p.read_bytes() == b for p, b in before.items())
