"""`chipbench.span_reads` (CPU): the readers that split ``setup_s`` by the
program's kept spans, cell by cell on a rehearsal run and on made-up
spans, and the share of the window's decode steps launched ahead."""

import importlib
import json
import time
import types
from pathlib import Path

import numpy as np
import pytest

import jax

from chipbench import harness, span_reads, spans as harness_spans
from chipbench.manifest import Manifest
from tpu_dist.observe import compile_spans, spans

from .tiny import REPO, make_tiny_root

DOC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in DOC["workloads"]]
SETUP = [m for m in DOC["per_layer"] if m["moves"] == "setup_s"]
READER = Manifest(Path(__file__).resolve().parents[2]).reader


def test_every_cell_has_per_layer_metrics_under_setup_s():
    """`setup_s` was the one end-to-end metric with nothing under it."""
    for cell in CELLS:
        mine = {m["name"] for m in SETUP if cell in m["workloads"]}
        assert {"setup_trace_lower_s", "setup_backend_compile_s", "setup_cache_load_s",
                "setup_cache_misses", "setup_steps_s"} <= mine
        assert ("setup_engine_init_s" in mine) == cell.startswith("serve-")
        assert ({"setup_trainer_init_s", "setup_place_state_s"} <= mine) == cell.startswith("train-")


def _view(root, cell, monkeypatch, seconds=1.0) -> harness.RunView:
    """One untraced run of ``cell`` and what a reader would be handed after
    it (the readers of spans need no profiler)."""
    spec = Manifest(root).cell(cell)
    kind = importlib.import_module(harness.KINDS[spec.traffic["kind"]])
    seen = {}
    real = kind.run

    def run(ctx):
        seen["ctx"], seen["out"] = ctx, real(ctx)
        return seen["out"]

    monkeypatch.setattr(kind, "run", run)
    result = harness.run_cell(root, cell, 2**31 + 29, seconds, False, devices=jax.devices(),
                              t0=time.perf_counter())
    assert result["correct"] is True
    return harness.RunView(cell=spec, facts=seen["out"].facts, trace=None, scopes=None,
                           rec=seen["ctx"].rec, peaks=None)


@pytest.mark.parametrize("cell", CELLS)
def test_the_setup_readers_read_a_rehearsal_run_and_not_its_reference_pass(
        tmp_path, cell, monkeypatch):
    compile_spans.install()  # another test of this process may have cleared the listeners
    began = time.perf_counter()
    view = _view(make_tiny_root(tmp_path), cell, monkeypatch)
    at = span_reads.window_start(view)
    got = {m["name"]: READER(m["name"])(view) for m in SETUP if cell in m["workloads"]}
    assert len(got) == (7 if cell.startswith("train-") else 6)
    assert all(v is not None and np.isfinite(v) and v >= 0.0 for v in got.values()), got
    # set-up traced, lowered and compiled (or loaded) its programs
    assert got["setup_trace_lower_s"] > 0.0
    assert got["setup_backend_compile_s"] + got["setup_cache_load_s"] > 0.0
    if cell.startswith("train-"):
        assert 0.0 < got["setup_place_state_s"] <= got["setup_trainer_init_s"]
        assert got["setup_steps_s"] > 0.0  # the check steps
    else:
        assert got["setup_engine_init_s"] > 0.0
    # the parts lie inside set-up and do not overlap
    parts = sum(v for k, v in got.items() if k not in ("setup_cache_misses", "setup_place_state_s"))
    assert parts <= at - began
    # the reference pass compiled its own programs AFTER the window: none is counted
    stages = [s for s in spans.kept(began) if s.name in span_reads.STAGES]
    held = [s for s in span_reads.setup_spans(view) if s.name in span_reads.STAGES]
    after = [s for s in stages if s.end > at]
    assert after and all(s.end <= at for s in held) and len(held) + len(after) == len(stages)
    assert (got["setup_trace_lower_s"] + got["setup_backend_compile_s"]
            + got["setup_cache_load_s"]) == pytest.approx(sum(s.end - s.start for s in held))
    # the programs are there by name, each once for each shape it was warmed at
    funs = [s.attrs["fun"] for s in held if s.name == "compile.backend"]
    if cell.startswith("train-"):
        assert funs.count("train_step") == 1
    else:
        sc = view.cell.config["serve"]
        assert funs.count("serve_prefill") == min(sc["prefill_batch"], sc["max_batch"])
        assert funs.count("serve_decode_greedy") == 1
    ahead = [m["name"] for m in DOC["per_layer"]
             if m["name"].startswith("decode_ahead_share") and cell in m["workloads"]]
    for name in ahead:
        assert 0.0 <= READER(name)(view) <= 100.0
    assert len(ahead) == (1 if cell.startswith("serve-") else 0)


# ------------------------------------------------------------ made-up spans


def _made(kind: str = "serve", *, hit: bool = False):
    """A run whose set-up kept: the weights' draw with one compile in it,
    a construction span with a backend stage inside, then two harness steps
    before a window of three, the first of them with a trace inside."""
    rec = harness_spans.Recorder()
    cache = dict(cache="hit", load_s=0.25, saved_s=9.0) if hit else dict(cache="miss")
    def draw():
        with spans.span("model.init", keep=True):
            t = time.perf_counter()
            spans.record("compile.lower", t - 0.125, t, keep=True, nest=True, fun="fn")

    if kind == "serve":
        draw()  # the harness draws the weights, then builds the engine
    with spans.span("trainer.init" if kind == "train" else "engine.init", keep=True) as built:
        if kind == "train":
            draw()  # the trainer draws its own
            with spans.span("trainer.place_state", keep=True):
                pass
        t = time.perf_counter()
        while time.perf_counter() - t < 0.01:
            pass
        t1 = time.perf_counter()
        spans.record("compile.backend", t, t1, keep=True, nest=True, fun="serve_decode_greedy",
                     **cache)
    step = "train_step" if kind == "train" else "engine_step"
    for i in range(5):
        with rec.span(step) as sp:
            if i == 0:
                t = time.perf_counter()
                spans.record("compile.trace", t, time.perf_counter(), keep=True, fun="serve_prefill")
            with spans.span("engine.step", step=i):
                with spans.span("engine.decode_dispatch", ahead=i % 2 == 0, fed=1):
                    pass
    # the reference pass, after the window
    t = time.perf_counter()
    spans.record("compile.backend", t - 100.0, t, keep=True, fun="forward", cache="miss")
    facts = {"train_step_ms": [0.0] * 3} if kind == "train" else {"engine_steps": 3}
    return types.SimpleNamespace(rec=rec, facts=facts), built, rec.named(step)


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_the_readers_split_a_made_up_setup(kind):
    run, built, steps = _made(kind)
    assert span_reads.window_start(run) == steps[2].start
    held = span_reads.setup_spans(run)
    stage = {s.name: s for s in held if s.name in span_reads.STAGES}
    assert READER("setup_trace_lower_s")(run) == pytest.approx(
        0.125 + stage["compile.trace"].end - stage["compile.trace"].start)
    inside = stage["compile.backend"].end - stage["compile.backend"].start
    assert READER("setup_backend_compile_s")(run) == pytest.approx(inside)
    assert READER("setup_cache_load_s")(run) == 0.0 and READER("setup_cache_misses")(run) == 1
    own = "setup_trainer_init_s" if kind == "train" else "setup_engine_init_s"
    other = "setup_engine_init_s" if kind == "train" else "setup_trainer_init_s"
    # (the made-up lowering began before the span it is a child of: time decides, and it is not inside)
    assert READER(own)(run) == pytest.approx(built.end - built.start - inside)
    assert READER(other)(run) == 0.0  # a number where nothing of its kind happened
    assert (READER("setup_place_state_s")(run) > 0.0) == (kind == "train")
    before = sum(s.end - s.start for s in steps[:2])
    traced = stage["compile.trace"].end - stage["compile.trace"].start
    assert READER("setup_steps_s")(run) == pytest.approx(before - traced)


def test_a_cache_hit_is_load_time_and_no_miss():
    run, _, _ = _made(hit=True)
    assert READER("setup_backend_compile_s")(run) == 0.0 and READER("setup_cache_misses")(run) == 0
    assert READER("setup_cache_load_s")(run) >= 0.01


def test_a_run_before_this_one_in_the_process_is_not_this_runs_setup():
    _made()  # leaves a reference pass's 100-s compile behind, before the next run's draw
    run, _, _ = _made()
    assert READER("setup_backend_compile_s")(run) < 1.0
    assert READER("setup_cache_misses")(run) == 1


def test_a_program_without_kept_spans_is_read_as_nothing(monkeypatch):
    run, _, _ = _made()
    monkeypatch.delattr(spans, "kept")  # the program as it was before PR 40
    assert span_reads.setup_spans(run) is None
    for m in SETUP:
        assert READER(m["name"])(run) is None, m["name"]


@pytest.mark.parametrize("name", ["decode_ahead_share.sat", "decode_ahead_share.rate"])
def test_the_share_of_decode_steps_launched_ahead(name):
    run, _, _ = _made()
    assert READER(name)(run) == pytest.approx(100.0 * 2 / 3)  # steps 2, 3, 4 of the window
    for s in spans.recent(run.rec.named("engine_step")[2].start):
        if s.name == "engine.decode_dispatch":
            s.attrs["ahead"] = False
    assert READER(name)(run) == 0.0  # such spans, and none ahead
    run.facts["engine_steps"] = 0
    assert READER(name)(run) is None
