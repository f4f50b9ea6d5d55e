"""The program's own spans (`tpu_dist.observe.spans`' ring) inside the
measured window, for the per-layer readers under ``layer_metrics/``.

The harness's ``engine_step`` spans (`chipbench/spans.py`) and the
program's share `time.perf_counter`, so no clocks are matched.  The window
is the stretch from the first to the last of the run's last
``facts["engine_steps"]`` harness ``engine_step`` spans: the steps of the
measured window, warm-up and set-up left out.  A span is inside it when it
ENDED there: an ``engine.*`` phase that ended inside also began inside,
and a ``request.queued`` that ended inside is a request admitted inside.

The ring is bounded and drops its oldest spans.  A window it no longer
holds whole (some 13 spans a step: 65536 of them last a 51-s window down
to steps of about 10 ms) is refused, not read from its tail: `window_spans`
raises when the ring has wrapped past the window's start, and when it does
not find one ``engine.step`` for each of the harness's ``engine_step``.

Where the program keeps no such ring, as before PR 25, every function
here returns nothing and its reader leaves the metric out of the line.
"""

from __future__ import annotations

from chipbench.arithmetic import median


def window_spans(run) -> dict[str, list] | None:
    """-> {span name: the program's spans of that name in the window}."""
    try:
        from tpu_dist.observe.spans import complete_since, recent
    except ImportError:
        return None
    n = int(run.facts.get("engine_steps") or 0)
    steps = run.rec.named("engine_step")[-n:] if n else []
    if not steps:
        return None
    lo, hi = steps[0].start, steps[-1].end
    if not complete_since(lo):
        raise RuntimeError(
            f"the program's span ring wrapped inside the {hi - lo:.1f}-s window: its "
            "spans there are no longer all held (tpu_dist.observe.spans.RING_SIZE)")
    out: dict[str, list] = {}
    for s in recent():
        if lo <= s.end <= hi:
            out.setdefault(s.name, []).append(s)
    found = len(out.get("engine.step", []))
    if found != len(steps):
        raise RuntimeError(
            f"{found} engine.step spans of the program for the window's {len(steps)} engine steps")
    return out


def engine_host_ms_p50(run) -> float | None:
    """Median over the window's ``engine.step`` spans of the step's time
    less its ``engine.decode_wait`` and ``engine.prefill_wait`` children:
    what the host itself took, the waits for the device left out."""
    spans = window_spans(run)
    if not spans or not spans.get("engine.step"):
        return None
    waited: dict[int, float] = {}
    for name in ("engine.decode_wait", "engine.prefill_wait"):
        for s in spans.get(name, []):
            waited[s.parent] = waited.get(s.parent, 0.0) + s.ms
    return median(s.ms - waited.get(s.id, 0.0) for s in spans["engine.step"])
