"""What set-up spent, from the program's KEPT spans, and the share of the
window's decode steps launched ahead, for the readers under
``layer_metrics/`` that split ``setup_s``.

The program keeps the stages of JAX's compile pipeline (``compile.trace``,
``compile.lower``, ``compile.backend`` with ``cache`` = hit / miss / off,
each with ``fun``, the program's name) and the phases of a trainer's and
an engine's construction past the wrap of its span ring
(`tpu_dist.observe.spans.kept`), so they are still there when the readers
run, after the window and after the reference pass.  They share
`time.perf_counter` with the harness's spans.

Set-up ends where the window starts: at the start of the first of the
run's last ``facts["engine_steps"]`` harness ``engine_step`` spans
(serving), of its last ``len(facts["train_step_ms"])`` ``train_step`` spans
(training).  A span is set-up's when it ENDED before that, which leaves
out what the reference pass compiles after the window.  In a process that
has run a cell before (the rehearsal, a builder's loop over variants) it
also has to have ended after this run's draw of its weights began, the
last ``model.init`` or ``trainer.init`` that no span contains: what the
run before compiled for its reference is not this run's.

The compile stages of one thread never overlap (the program records the
outermost), so their seconds add.  Where the program keeps no such spans,
as before PR 40, every function here returns nothing and its reader leaves
the metric out of the line; where it does, a reader returns a number, 0.0
when nothing of its kind happened.
"""

from __future__ import annotations

from chipbench.program_spans import window_spans

STAGES = ("compile.trace", "compile.lower", "compile.backend")
FIRST = ("model.init", "trainer.init")


def _steps(run) -> tuple[str, int]:
    """(the harness's span round one step, how many the window has)."""
    if "train_step_ms" in run.facts:
        return "train_step", len(run.facts["train_step_ms"])
    return "engine_step", int(run.facts.get("engine_steps") or 0)


def window_start(run) -> float | None:
    name, n = _steps(run)
    steps = run.rec.named(name)[-n:] if n else []
    if steps:
        return steps[0].start
    # a window with no step in it: set-up ended by the harness's last span
    return run.rec.spans[-1].end if run.rec.spans else None


def setup_spans(run) -> list | None:
    """The program's kept spans of this run's set-up, in the order they ended."""
    try:
        from tpu_dist.observe.spans import kept
    except ImportError:
        return None
    at = window_start(run)
    if at is None:
        return None
    held = [s for s in kept() if s.end <= at]
    firsts = [s for s in held if s.name in FIRST and s.parent is None]
    if len(firsts) > 1:
        held = [s for s in held if s.end > firsts[-1].start]
    return held


def _seconds(spans) -> float:
    return float(sum(s.end - s.start for s in spans))


def stages(run, names=STAGES, cache=None) -> list | None:
    """Set-up's compile stages named ``names``; ``cache``: the values of a
    ``compile.backend`` span's ``cache`` attribute to keep."""
    held = setup_spans(run)
    if held is None:
        return None
    return [s for s in held if s.name in names
            and (cache is None or s.attrs.get("cache") in cache)]


def stage_seconds(run, names, cache=None) -> float | None:
    found = stages(run, names, cache)
    return None if found is None else _seconds(found)


def cache_misses(run) -> int | None:
    """Programs set-up compiled and wrote to the persistent cache: 0 says
    that the run started warm."""
    found = stages(run, ("compile.backend",), ("miss",))
    return None if found is None else len(found)


def _less_stages(run, spans, same_thread: bool) -> float | None:
    """Seconds of ``spans`` less set-up's compile stages that ran inside
    them (on their thread, where the spans say which)."""
    found = stages(run)
    if found is None:
        return None
    return float(sum(
        p.end - p.start - _seconds(s for s in found if p.start <= s.start and s.end <= p.end
                                   and (not same_thread or s.tid == p.tid))
        for p in spans))


def phase_seconds(run, name: str) -> float | None:
    """Seconds of set-up's kept spans named ``name`` less the compile
    stages inside them."""
    held = setup_spans(run)
    if held is None:
        return None
    return _less_stages(run, [p for p in held if p.name == name], same_thread=True)


def steps_seconds(run) -> float | None:
    """Seconds of the harness's step spans before the window (the check
    steps, the closed loop's fill) less the compile stages inside them:
    what set-up spent RUNNING the programs under the harness's spans."""
    at = window_start(run)
    if at is None:
        return None
    before = [s for s in run.rec.named(_steps(run)[0]) if s.end <= at]
    return _less_stages(run, before, same_thread=False)


def decode_ahead_share(run) -> float | None:
    """Share (%) of the window's ``engine.decode_dispatch`` spans whose
    ``ahead`` is true: decode steps launched while the step before was
    still unread.  Nothing where the window has no decode dispatch."""
    spans = window_spans(run)
    launched = spans.get("engine.decode_dispatch", []) if spans else []
    if not launched or any("ahead" not in s.attrs for s in launched):
        return None
    return 100.0 * sum(bool(s.attrs["ahead"]) for s in launched) / len(launched)
