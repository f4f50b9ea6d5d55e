"""Host-side spans: one recorder, on the profiler's clock.

A span is ``(name, start, end, id, parent, attrs)``.  ``start`` / ``end``
are `time.perf_counter()` readings and nothing else (the clock the
serving engine's ``now`` and the benchmark's harness use); ``parent`` is
the id of the span that was open on the same thread when this one
started; request-scoped spans carry ``request_id`` in ``attrs`` and
step-scoped ones ``step``.

Every span lands in ONE process-global bounded ring (a
``deque(maxlen=RING_SIZE)`` like `flightrec`'s: no lock, no I/O), read
with `recent`.  It is always on.  A span made with ``keep=True`` (a stage
of JAX's compile pipeline, a phase of a trainer's or an engine's
construction: tens to hundreds a process) goes to the ring like any other
AND to a second, small one that the hot path's spans never reach, read
with `kept`: what set-up cost can still be read after hours of steps have
wrapped the ring.  Opening a span also enters
``jax.profiler.TraceAnnotation("tpu_dist/<name>")``: a flag test while no
profiler session is open, and while one is the span sits in the
``.xplane.pb`` host plane on the device trace's clock, so idle gaps on
the chip can be named after what the host was doing
(`python -m chipbench.scopes`).  jax is imported at the first span, not
with this module, which stays importable before a backend exists.

The Chrome-trace export is an operator's view of the same ring
(docs/observability.md): under ``TPU_DIST_TELEMETRY=<dir>`` `from_env`
returns a `SpanRecorder` whose `save` writes the ring's spans since the
recorder was made, whoever opened them (a trainer's ``dispatch``, the
serving engine's phases and its requests' lifecycles), to
``<dir>/spans_rank<r>.trace.json`` (fit-exit, interpreter exit, the flight
recorder's crash paths).  ``ts`` there is the same ``start`` moved onto
the wall clock by one process-wide offset, so the ranks of a gang line up
in `merge_traces`; a span that carries a ``request_id`` is drawn on that
request's own lane.
"""

from __future__ import annotations

import atexit
import itertools
import json
import os
import threading
import time
from collections import deque

from tpu_dist.observe import events as _events

RING_SIZE = 65536
KEPT_SIZE = 4096
PREFIX = "tpu_dist/"

# wall clock minus perf_counter, read once: the export's only other clock
WALL_OFFSET = time.time() - time.perf_counter()

_ring: deque = deque(maxlen=RING_SIZE)
_kept: deque = deque(maxlen=KEPT_SIZE)
_ids = itertools.count(1)
_local = threading.local()
_annotation = None  # jax.profiler.TraceAnnotation, resolved at the first span


def _open_ids() -> list:
    try:
        return _local.open
    except AttributeError:
        _local.open = []
        return _local.open


class Span:
    """One span; also its own context manager (`span` hands it out, so a
    caller can add to ``attrs`` what it learns inside)."""

    __slots__ = ("name", "start", "end", "id", "parent", "attrs", "tid", "keep", "_ann")

    def __init__(self, name: str, attrs: dict, keep: bool = False):
        self.name, self.attrs, self.keep = name, attrs, keep
        self.start = self.end = 0.0
        self.id = self.parent = None
        self.tid = threading.get_ident() & 0xFFFFFF  # the export's lane
        self._ann = None

    @property
    def ms(self) -> float:
        return (self.end - self.start) * 1e3

    def __enter__(self) -> "Span":
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        opened = _open_ids()
        self.parent = opened[-1] if opened else None
        self.id = next(_ids)
        opened.append(self.id)
        self._ann = _annotation(PREFIX + self.name)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        self._ann = None
        _open_ids().pop()
        _ring.append(self)
        if self.keep:
            _kept.append(self)
        return False

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, {self.start:.6f}..{self.end:.6f}, "
                f"id={self.id}, parent={self.parent}, {self.attrs})")


def span(name: str, keep: bool = False, **attrs) -> Span:
    """``with span("engine.step", step=3) as sp:`` times the block;
    ``keep=True`` also holds the span past the ring's wrap (`kept`)."""
    return Span(name, attrs, keep)


def record(name: str, start: float, end: float, keep: bool = False, nest: bool = False,
           **attrs) -> Span:
    """A span whose two ends the caller read itself (`time.perf_counter`),
    for a stretch no ``with`` block covers: a request's wait in the queue.
    Lying in the past it has no profiler annotation, and no parent unless
    ``nest=True`` says that it lay inside the span now open on the calling
    thread (a compile stage inside the dispatch that paid for it)."""
    sp = Span(name, attrs, keep)
    sp.start, sp.end, sp.id = start, end, next(_ids)
    if nest:
        opened = _open_ids()
        sp.parent = opened[-1] if opened else None
    _ring.append(sp)
    if keep:
        _kept.append(sp)
    return sp


def recent(since: float = float("-inf")) -> list[Span]:
    """The ring's spans that started at or after ``since``, in the order
    they ended (a span is kept when it closes)."""
    return [s for s in list(_ring) if s.start >= since]


def kept(since: float = float("-inf")) -> list[Span]:
    """The ``keep=True`` spans that started at or after ``since``, in the
    order they ended, whether or not the ring still holds them (the newest
    `KEPT_SIZE` of them)."""
    return [s for s in list(_kept) if s.start >= since]


def complete_since(since: float) -> bool:
    """Whether `recent(since)` is all there was: False once the ring has
    wrapped past ``since`` (it is full, and its oldest span ended after
    ``since``, so a span that started later may already have been dropped)."""
    ring = _ring
    return len(ring) < ring.maxlen or ring[0].end <= since


def _with_step(step: int | None, attrs: dict) -> dict:
    if step is not None:
        attrs["step"] = int(step)
    return attrs


class SpanRecorder:
    """The Chrome-trace file of the ring: `span` and `instant` are the
    module's own with ``step`` named, and `save` writes every span the ring
    holds that started since this recorder was made (the ring bounds a
    multi-day run's file to its newest `RING_SIZE` spans, and the kept
    spans that came before them)."""

    enabled = True

    def __init__(self, path: str | None = None, rank: int = 0):
        self.path = path
        self.rank = int(rank)
        self.since = time.perf_counter()

    def span(self, name: str, step: int | None = None, **args) -> Span:
        """Time a host-side region.  ``step`` is the global step id;
        extra kwargs land in the span's ``attrs``."""
        return Span(name, _with_step(step, args))

    def instant(self, name: str, step: int | None = None, **args) -> None:
        """A zero-duration marker (preemption signal, chaos injection)."""
        now = time.perf_counter()
        record(name, now, now, **_with_step(step, args))

    def _event(self, sp: Span) -> dict:
        ev = {
            "name": sp.name,
            "ts": (sp.start + WALL_OFFSET) * 1e6,  # microseconds
            "pid": self.rank,
            # a request's spans overlap other requests': a lane for each
            "tid": sp.attrs.get("request_id", sp.tid),
            "args": dict(sp.attrs, id=sp.id, parent=sp.parent),
        }
        if sp.end > sp.start:
            ev.update(ph="X", dur=(sp.end - sp.start) * 1e6)
        else:
            ev.update(ph="i", s="p")  # process-scoped instant
        return ev

    def save(self, path: str | None = None) -> str | None:
        """Write the Chrome-trace JSON; returns the path (None if this
        recorder has nowhere to write).  Idempotent — call at every
        fit-exit; later spans simply extend the file on the next save.
        A span still open is not in the ring yet: the next save has it.
        A kept span is drawn once, from the ring while that holds it and
        from `kept` after (``complete`` speaks of the ring alone)."""
        path = path or self.path
        if path is None:
            return None
        drawn = recent(self.since)
        if not complete_since(self.since):
            # a kept span the ring has dropped ended before every span it holds
            held = {sp.id for sp in drawn}
            drawn = [sp for sp in kept(self.since) if sp.id not in held] + drawn
        doc = {
            "traceEvents": [self._event(sp) for sp in drawn],
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "tpu_dist.observe.spans",
                "rank": self.rank,
                "complete": complete_since(self.since),
            },
        }
        tmp = f"{path}.tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            # attrs are any caller's: a numpy scalar among them must not
            # cost the crash paths the whole file
            json.dump(doc, fh, default=lambda o: o.item() if hasattr(o, "item") else repr(o))
        os.replace(tmp, path)
        return path


class NullRecorder(SpanRecorder):
    """No file is written: the spans go to the ring and nowhere else."""

    enabled = False


NULL = NullRecorder()
_cache: dict[tuple[str, int], SpanRecorder] = {}
_cache_lock = threading.Lock()
_flush_installed = False


def flush_all() -> None:
    """Save every cached recorder.  Best-effort and LOCK-FREE: this runs
    inside signal handlers (a flight-recorder crash callback), where
    acquiring ``_cache_lock`` could deadlock against the interrupted
    thread already holding it — a racing ``from_env`` insert at worst
    costs this flush one recorder, not the process."""
    try:
        recs = list(_cache.values())
    except RuntimeError:  # dict mutated mid-iteration by a live insert
        recs = []
    for rec in recs:
        try:
            rec.save()
        except Exception:
            pass


def _install_flush_hooks() -> None:
    """`save` is otherwise only called at fit-exit, so a crash between
    fits (or mid-fit before the finally) would lose the whole trace:
    register the flush at interpreter exit AND on the flight recorder's
    crash paths (watchdog fire, SIGTERM/SIGINT, unhandled exception,
    chaos kill) so Chrome traces survive crashes."""
    global _flush_installed
    if not _flush_installed:
        _flush_installed = True
        atexit.register(flush_all)
    # (Re-)register with the flight recorder on every new recorder:
    # registration de-dupes, and this heals the hook if someone reset
    # the crash-callback list.
    try:
        from tpu_dist.observe import flightrec as _flightrec

        _flightrec.register_crash_callback(flush_all)
    except Exception:
        pass


def from_env(rank: int | None = None):
    """Whether a file is written: this process's `SpanRecorder` under
    ``TPU_DIST_TELEMETRY`` (cached per dir+rank), else `NULL`.  Either
    way the spans reach the ring."""
    dirpath = os.environ.get(_events.ENV_DIR)
    if not dirpath:
        return NULL
    r = _events.env_rank(rank)
    key = (dirpath, r)
    with _cache_lock:
        rec = _cache.get(key)
        if rec is None:
            os.makedirs(dirpath, exist_ok=True)
            rec = SpanRecorder(
                os.path.join(dirpath, f"spans_rank{r}.trace.json"), rank=r
            )
            _cache[key] = rec
    _install_flush_hooks()
    return rec


def merge_traces(paths, out_path: str | None = None) -> dict:
    """Merge per-rank Chrome-trace files into ONE trace with a process
    lane per rank: every event's ``pid`` becomes its rank (taken from
    the file's ``otherData.rank``, falling back to the recorded pid) and
    a ``process_name`` metadata event labels each lane ``rank <r>``, so
    perfetto shows the gang side by side.  Used by the flight-recorder
    merge CLI; returns the merged trace document (written to
    ``out_path`` when given)."""
    events: list[dict] = []
    complete = True
    for i, path in enumerate(paths):
        try:
            with open(path, encoding="utf-8") as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        other = doc.get("otherData", {}) or {}
        rank = other.get("rank", i)
        complete = complete and bool(other.get("complete", True))
        events.append({
            "name": "process_name", "ph": "M", "pid": rank, "tid": 0,
            "ts": 0, "args": {"name": f"rank {rank}"},
        })
        for ev in doc.get("traceEvents", []):
            ev = dict(ev)
            ev["pid"] = rank
            events.append(ev)
    events.sort(key=lambda e: (e.get("ph") != "M", e.get("ts", 0)))
    merged = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "tpu_dist.observe.spans.merge_traces",
            "sources": len(paths),
            "complete": complete,
        },
    }
    if out_path is not None:
        tmp = f"{out_path}.tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(merged, fh)
        os.replace(tmp, out_path)
    return merged


def _cost_ns(n: int = 100_000) -> float:
    t0 = time.perf_counter()
    for i in range(n):
        with span("cost", step=i):
            pass
    return (time.perf_counter() - t0) / n * 1e9


if __name__ == "__main__":
    # what the always-on ring costs: ns a span, off and on a profiler session
    import tempfile

    import jax

    _cost_ns(10_000)
    off = [_cost_ns() for _ in range(3)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            on = [_cost_ns(20_000) for _ in range(3)]
        finally:
            jax.profiler.stop_trace()
    t0 = time.perf_counter()
    for i in range(100_000):
        record("cost", 0.0, 1.0, request_id=i)
    rec_ns = (time.perf_counter() - t0) / 100_000 * 1e9
    print(f"{jax.devices()[0].platform}: span() {min(off):.0f} ns with no profiler session "
          f"(three runs: {', '.join(f'{x:.0f}' for x in off)}), {min(on):.0f} ns inside one "
          f"({', '.join(f'{x:.0f}' for x in on)}); record() {rec_ns:.0f} ns")
