"""The harness's own spans and counters, kept in memory for the run.

Spans go round the harness's calls into each layer (an engine step, a
trainer step, the loader's ``next``); spans inside the program are a later
PR.  With the profiler on, each span is also a `TraceAnnotation`, so the
device trace carries the same names on the same clock.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field


@dataclass
class Span:
    name: str
    start: float
    end: float
    attrs: dict = field(default_factory=dict)

    @property
    def ms(self) -> float:
        return (self.end - self.start) * 1e3


class Recorder:
    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.spans: list[Span] = []
        self.annotate = False  # set while the profiler runs

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        rec = Span(name, self.clock(), 0.0, attrs)
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(f"chipbench/{name}")
            ann.__enter__()
        try:
            yield rec
        finally:
            if ann is not None:
                ann.__exit__(None, None, None)
            rec.end = self.clock()
            self.spans.append(rec)

    def named(self, name: str, since: float = float("-inf")) -> list[Span]:
        return [s for s in self.spans if s.name == name and s.start >= since]


class CompileCounter:
    """Counts XLA compilations through `jax.monitoring`: a backend compile
    (a persistent-cache hit reports none) and every persistent-cache miss.
    Installed once per process; `value` is read at the window's edges."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax

        self.value = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.value += 1
