"""CPU rehearsal: every cell end to end at a tiny preset through the same
code as on the chip, the controls, broken timed paths, and a cell added as
files only.  Nothing here is a measurement: no number of a CPU run is ever
written under a device metric's name, and the real entry refuses a CPU.
"""

import json
import os
import re
import shutil
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

from .tiny import LIMITS, REPO, made_trace, make_tiny_root

CELLS = [w["name"] for w in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]
CFG = dict(gpt2.tiny({}), layer_norm_epsilon=1e-6)


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)


def _run(root, cell, *, seed=2**31 + 17, seconds=1.0, trace=False, control=False):
    return run_cell(root, cell, seed, seconds, trace, devices=jax.devices(),
                    t0=time.perf_counter(), control=control)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_runs_end_to_end_at_the_tiny_preset(tiny_root, cell, trace):
    runs_end_to_end(tiny_root, cell, trace)


def runs_end_to_end(tiny_root, cell, trace):
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
    that lacks a metric which lists the cell.  The trace is made from the
    vocabulary (`tiny.made_trace`): the programs of the cell's kind, every
    scope and kernel the program or the cell's family names."""
    reports_every_per_layer_metric(tiny_root, cell, monkeypatch)


def reports_every_per_layer_metric(tiny_root, cell, monkeypatch):
    from chipbench import harness
    from chipbench.peaks import peaks_for

    spec = Manifest(tiny_root).cell(cell)
    reduced, table = made_trace(spec)
    monkeypatch.setattr(harness.TraceSlice, "reduce", lambda self, families=(): (reduced, table))
    monkeypatch.setattr(harness, "_peaks", lambda dev: peaks_for("TPU v5 lite"))
    result = _run(tiny_root, cell, seconds=2.0, trace=True)
    # the CPU keeps no count of its memory's peak, so that reader alone has nothing
    assert set(result["metrics"]) == {
        m["name"] for m in spec.per_layer if not m["name"].startswith("hbm_peak_gb")}
    assert all(np.isfinite(m["value"]) for m in result["metrics"].values())
    assert (result["device"]["busy_s"], result["device"]["window_s"]) == (
        reduced["busy_s"], reduced["window_s"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    # the idle gaps are the table's: by the program's spans where it has them
    assert result["breakdown"]["idle_gaps"] == table["idle_gaps"]
    return result


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


@pytest.mark.parametrize("loss_gap,over", [
    (0.001, ["loss_gap_step2", "loss_gap_step3"]),   # one limit for every step
    ([0.001, 0.005], []),                            # one a step, the last for the later steps
    ([0.0001, 0.005], ["loss_gap_step1"]),
])
def test_a_loss_limit_may_be_given_step_by_step(loss_gap, over):
    norms = {"a": np.ones(3)}
    ref = {"losses": [1.0, 2.0, 3.0], "grad_norms": norms, "delta_norms": norms}
    program = dict(ref, losses=[1.0005, 2.003, 3.004])
    rows = correct.compare_training(
        program, ref, {"loss_gap": loss_gap, "grad_norm_gap": 0.01, "delta_norm_gap": 0.01})
    assert [r.name for r in rows if not r.ok] == over
    assert [r.limit for r in rows[:3]] == (
        [loss_gap] * 3 if not isinstance(loss_gap, list) else [loss_gap[0], loss_gap[1], loss_gap[1]])


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


def test_cells_of_a_family_that_is_there_are_added_as_files_only(tiny_root):
    """A configuration of GPT-2's own key names, a bursty mix, a serving and
    a training cell and two readers, added to a root that is shrunk already."""
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


HYBRID_FAMILY = '''"""A test's family: NOT GPT-2 by its key names, serving only.  (Underneath
it hands the harness the program's one servable model, which is all the
engine can serve today; what is shown is that the harness asks the family
file and nothing else for sizes, rehearsal preset, names on the device and
counts.)"""
from chipbench.families import gpt2 as _g

SCOPES = ("mixer/scan", "experts/route")
KERNELS = ("mixer_scan_fwd",)


def _as_gpt2(cfg):
    return {"n_embd": cfg["hidden_size"], "n_layer": cfg["num_hidden_layers"],
            "n_head": cfg["num_attention_heads"], "n_positions": cfg["max_position_embeddings"],
            "vocab_size": cfg["vocab_size"], "layer_norm_epsilon": cfg["rms_norm_eps"],
            "initializer_range": cfg["initializer_range"]}


def tiny(cfg):
    layers = 2
    return {"hidden_size": 64, "num_hidden_layers": layers, "num_attention_heads": 4,
            "layer_types": cfg["layer_types"][:layers], "max_position_embeddings": 96,
            "vocab_size": 512, "initializer_range": 0.15,
            "serve": {"max_batch": 3, "max_seq": 96, "num_blocks": 36}}


def vocab_size(cfg):
    return cfg["vocab_size"]


def param_count(cfg):
    return _g.param_count(_as_gpt2(cfg))


def kv_bytes_per_token(cfg, bytes_per_value):
    return _g.kv_bytes_per_token(_as_gpt2(cfg), bytes_per_value)


def state_bytes_per_slot(cfg):
    """float32 state of the layers that keep one."""
    return 4 * cfg["state_size"] * cfg["hidden_size"] * cfg["layer_types"].count("mixer")


def make_init(cfg, dtype, *, layout):
    return _g.make_init(_as_gpt2(cfg), dtype, layout=layout)


def make_lm(cfg, seeded_key, dtype, *, remat=False):
    return _g.make_lm(_as_gpt2(cfg), seeded_key, dtype, remat=remat)


class reference:
    @staticmethod
    def forward(p, tokens, cfg, quant=None):
        return _g.reference.forward(p, tokens, _as_gpt2(cfg), quant=quant)
'''
HYBRID_CONFIG = {
    "name": "hybrid-base", "source": "https://example.org/hybrid", "family": "hybrid",
    "hidden_size": 768, "num_hidden_layers": 12, "num_attention_heads": 12,
    "layer_types": ["mixer", "mixer", "attention"] * 4, "state_size": 16,
    "max_position_embeddings": 2048, "vocab_size": 32000, "rms_norm_eps": 1e-6,
    "initializer_range": 0.02, "reduced": [],
    # no `train` section: serving only.  `decode_event_every` is a field of the
    # engine's configuration that no committed configuration sets
    "serve": {"dtype": "bfloat16", "max_batch": 64, "block_size": 16, "num_blocks": 8192,
              "max_seq": 2048, "prefill_chunk": 256, "prefill_batch": 4,
              "decode_event_every": 4, "env": {}},
    "limits": {"serve": {"served_logit_gap": 0.1}},
}
OWN_READERS = {
    "mixer_scan_ms_per_step": '''"""Device self time under the family's own scope, a decode step."""
from chipbench.device_reads import scope_ms_per_run


def read(run):
    return scope_ms_per_run(run, "serve_decode", "mixer/scan")
''',
    "mixer_scan_kernel_calls": '''"""Calls of the family's own kernel in the slice."""


def read(run):
    if run.scopes is None:
        return None
    return next((calls for name, _, calls in run.scopes["kernels"] if name == "mixer_scan_fwd"), 0)
''',
}


def _a_later_prs_root(tmp_path):
    """The committed benchmark with what a `model_config` PR of another
    family adds, as files and entries alone: a family file, a configuration at
    widths no CPU test could run, a traffic mix, two readers of the family's
    own scope and kernel, and one cell listed by the metrics it reports."""
    src = tmp_path / "src"
    (src / "chipbench").mkdir(parents=True)
    for sub in ("configs", "traffic", "layer_metrics", "families"):
        shutil.copytree(REPO / "chipbench" / sub, src / "chipbench" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    there = {p: p.read_bytes() for p in src.rglob("*") if p.is_file()}
    (src / "chipbench/families/hybrid.py").write_text(HYBRID_FAMILY)
    (src / "chipbench/configs/hybrid-base.json").write_text(json.dumps(HYBRID_CONFIG))
    mix = json.loads((REPO / "chipbench/traffic/long-answer-closed.json").read_text())
    mix.update(clients=64, prompt_tokens={"dist": "lognormal", "median": 512, "sigma": 0.6,
                                          "min": 128, "max": 1536},
               output_tokens={"dist": "uniform", "min": 128, "max": 384})
    (src / "chipbench/traffic/long-prompt-closed.json").write_text(json.dumps(mix))
    for name, text in OWN_READERS.items():
        (src / "chipbench/layer_metrics" / f"{name}.py").write_text(text)
    doc = json.loads((REPO / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "hybrid-base", "source": "https://example.org/hybrid",
                           "file": "chipbench/configs/hybrid-base.json", "reduced": [],
                           "why": "a test's configuration"})
    doc["workloads"].append({"name": "serve-hybrid-sat", "config": "hybrid-base",
                             "traffic": "long-prompt-closed", "chips": 1, "why": "a test's cell"})
    reports = {"serve_out_tokens_per_s", "compiles_in_window.sat", "decode_step_ms_p50.sat",
               "decode_device_ms_p50.sat", "decode_hbm_roofline_pct", "hbm_peak_gb.sat"}
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m["name"] in reports:
            m["workloads"].append("serve-hybrid-sat")
    doc["per_layer"] += [
        {"name": name, "unit": unit, "better": "lower", "source": "device_trace",
         "layer": "kernels", "moves": "serve_out_tokens_per_s", "workloads": ["serve-hybrid-sat"]}
        for name, unit in (("mixer_scan_ms_per_step", "ms"), ("mixer_scan_kernel_calls", "count"))]
    (src / "BENCHMARK.json").write_text(json.dumps(doc))
    return src, there


def test_a_cell_a_configuration_and_a_metric_are_added_as_files_only(tmp_path, monkeypatch):
    """A later PR of ANOTHER family adds files and entries and edits no file
    that is there, the rehearsal's among them: its cell is shrunk by its own
    family file and passes the bodies of the three parametrised tests."""
    src, there = _a_later_prs_root(tmp_path)
    root = make_tiny_root(tmp_path, source=src)
    assert all(p.read_bytes() == b for p, b in there.items())
    assert all((REPO / p.relative_to(src)).read_bytes() == b for p, b in there.items())
    assert Manifest(root).problems() == []
    spec = Manifest(root).cell("serve-hybrid-sat")
    # shrunk by its own key names, its own engine sizes over the common ones
    assert "n_embd" not in spec.config and "train" not in spec.config
    assert spec.config["hidden_size"] == 64 and spec.config["layer_types"] == ["mixer", "mixer"]
    assert spec.config["serve"]["max_batch"] == 3 and spec.config["serve"]["block_size"] == 8
    assert spec.family.param_count(spec.config) < 5e6 < spec.family.param_count(HYBRID_CONFIG)
    # lengths follow the smallest tiny max_seq: the family's own 96
    assert max(spec.traffic[k]["max"] for k in ("prompt_tokens", "output_tokens")) <= 48
    # a field of the engine's configuration that comes from the file alone
    assert serve.engine_config(spec.config["serve"]).decode_event_every == 4

    for trace in (0, 1):
        runs_end_to_end(root, "serve-hybrid-sat", trace)
    traced = reports_every_per_layer_metric(root, "serve-hybrid-sat", monkeypatch)
    assert traced["metrics"]["mixer_scan_ms_per_step"]["value"] > 0
    assert traced["metrics"]["mixer_scan_kernel_calls"]["value"] > 0
    # the family's per-slot state counts in the decode step's bytes, read and written
    from chipbench import arithmetic, harness
    from chipbench.peaks import peaks_for

    facts = {"decode_step_ms": [10.0], "decode_held_tokens": [100], "decode_busy_slots": [3]}
    view = harness.RunView(cell=spec, facts=facts, trace=None, scopes=None, rec=None,
                           peaks=peaks_for("TPU v5 lite"))
    read = Manifest(root).reader("decode_hbm_roofline_pct")
    fam, cfg = spec.family, spec.config
    state = fam.state_bytes_per_slot(cfg)
    assert state == 4 * 16 * 64 * 2
    plain = arithmetic.decode_step_bytes(4 * fam.param_count(cfg), 100, fam.kv_bytes_per_token(cfg, 4))
    assert read(view) == pytest.approx(
        arithmetic.hbm_roofline_pct(plain + 2 * 3 * state, 0.010, 819e9))
    del fam.state_bytes_per_slot   # a family that keeps no state offers none: 0 bytes
    assert read(view) == pytest.approx(arithmetic.hbm_roofline_pct(plain, 0.010, 819e9))
    # and the cells that were there rehearse as before beside it
    runs_end_to_end(root, "serve-xl-decode-sat", 0)


@pytest.mark.parametrize("fault,said", [
    ("no-tiny", "offers no tiny(cfg)"), ("too-large", "parameters after"),
    ("unknown-kind", "no tiny preset for traffic kind"),
])
def test_what_cannot_be_shrunk_fails_the_rehearsal_at_once(tmp_path, fault, said):
    """Never a run at published widths on the CPU: a family without `tiny`,
    a configuration still above 5 M parameters after it, and a traffic kind
    with no preset are each refused while the root is made, by file name."""
    src, _ = _a_later_prs_root(tmp_path)
    family = src / "chipbench/families/hybrid.py"
    if fault == "no-tiny":
        family.write_text(HYBRID_FAMILY.replace("def tiny(cfg):", "def _tiny(cfg):"))
    elif fault == "too-large":
        family.write_text(HYBRID_FAMILY.replace('"hidden_size": 64', '"hidden_size": 1024'))
    else:
        (src / "chipbench/traffic/long-prompt-closed.json").write_text(
            json.dumps({"kind": "serve-replay"}))
    with pytest.raises(ValueError, match=re.escape(said)) as err:
        make_tiny_root(tmp_path, source=src)
    assert ("long-prompt-closed.json" if fault == "unknown-kind" else "hybrid") in str(err.value)
