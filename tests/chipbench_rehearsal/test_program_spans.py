"""`chipbench.program_spans` (CPU): the window the five readers of the
program's own spans share, and what it refuses to read."""

import collections
import types
from pathlib import Path

import pytest

from chipbench import program_spans, spans as harness_spans
from chipbench.manifest import Manifest
from tpu_dist.observe import spans

READER = Manifest(Path(__file__).resolve().parents[2]).reader


def _run(n_window: int, n_before: int = 2, *, prefill_every: int = 2):
    """A run of ``n_before`` warm-up steps and ``n_window`` measured ones:
    each harness ``engine_step`` holds one ``engine.step`` of the program
    with a decode wait, every ``prefill_every``-th a prefill round too."""
    rec = harness_spans.Recorder()
    for i in range(n_before + n_window):
        with rec.span("engine_step"):
            with spans.span("engine.step", step=i):
                with spans.span("engine.decode_wait"):
                    pass
                if i % prefill_every == 0:
                    with spans.span("engine.prefill_dispatch", rows=2, chunk=8) as sp:
                        sp.attrs["real_tokens"] = 12
                    with spans.span("engine.prefill_wait"):
                        pass
        at = rec.spans[-1]
        spans.record("request.queued", at.start, at.end, request_id=i)
    return types.SimpleNamespace(rec=rec, facts={"engine_steps": n_window})


def test_the_window_is_the_runs_last_engine_steps():
    run = _run(5)
    got = program_spans.window_spans(run)
    assert [s.attrs["step"] for s in got["engine.step"]] == [2, 3, 4, 5, 6]
    assert len(got["engine.decode_wait"]) == 5 and len(got["engine.prefill_wait"]) == 3
    assert [s.attrs["request_id"] for s in got["request.queued"]] == [2, 3, 4, 5, 6]
    host = program_spans.engine_host_ms_p50(run)
    assert 0.0 < host <= max(s.ms for s in got["engine.step"])


@pytest.mark.parametrize("name,want", [
    ("engine_host_ms_p50.rate", None), ("engine_host_ms_p50.sat", None),
    ("admit_wait_ms_p90", None), ("prefill_wait_ms_p50", None),
    ("prefill_useful_token_share", 75.0),
])
def test_each_reader_reads_the_window(name, want):
    value = READER(name)(_run(4))
    assert value is not None and value >= 0.0
    if want is not None:
        assert value == pytest.approx(want)


def test_a_run_with_no_engine_steps_reads_nothing():
    run = _run(0)
    assert program_spans.window_spans(run) is None
    assert program_spans.engine_host_ms_p50(run) is None


def test_a_ring_that_wrapped_inside_the_window_is_refused(monkeypatch):
    """At some 13 spans a step the ring lasts a 51-s window down to steps of
    about 10 ms; past that the readers must not read the window's tail."""
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=16))
    assert program_spans.window_spans(_run(3, prefill_every=99)) is not None  # 15 spans: held
    with pytest.raises(RuntimeError, match="wrapped inside"):
        program_spans.window_spans(_run(8))
    with pytest.raises(RuntimeError, match="wrapped inside"):
        READER("admit_wait_ms_p90")(_run(8))


def test_a_step_of_the_harness_without_one_of_the_program_is_refused():
    run = _run(4)
    with run.rec.span("engine_step"):
        pass  # the harness stepped, the program recorded nothing
    with pytest.raises(RuntimeError, match="3 engine.step spans of the program for the window's 4"):
        program_spans.window_spans(run)
