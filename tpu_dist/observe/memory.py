"""Live memory accounting + OOM forensics — the memory twin of spans.

The analyzer's `analysis.memory` knows what a compiled program SHOULD
keep resident (the static `MemoryPlan`); this module is the live half:

- `memory_snapshot()` — one ``{source, bytes_in_use, peak_bytes_in_use,
  bytes_limit}`` reading.  On backends that track HBM
  (``device.memory_stats``) the source is ``"hbm"``; on CPU-sim — where
  `train.metrics.device_memory_stats` has returned None since PR 3 and
  the step event's ``hbm`` field has been null in every CI run — it
  falls back to host RSS (``/proc/self/statm`` + ``getrusage``),
  labeled ``source: "rss"`` so a dashboard can never mistake a host
  number for a chip number.  The telemetry is therefore EXERCISED (and
  testable) on the CPU mesh.
- `WatermarkSampler` — per-rank phase-bucketed peak accounting: each
  `sample(phase)` reads the watermark and attributes the delta since
  the previous sample to that phase (``data`` / ``dispatch`` /
  ``readback`` / ``checkpoint`` / ``prefill`` / ``decode`` — the
  existing span-phase vocabulary).  Publishes the
  ``tpu_dist_hbm_{in_use,peak,limit}_bytes`` gauges, appends a
  ``memory`` record to the flight ring whenever the watermark moves
  (so a post-mortem merge shows the memory trajectory per rank), and
  emits the required ``memory`` telemetry event via `emit`.
- OOM forensics — `is_resource_exhausted(exc)` recognizes XLA's
  ``RESOURCE_EXHAUSTED`` on any step path; `record_oom` builds the
  plan-vs-live report (the failing PHASE, the HEADROOM at failure, the
  top RESIDENT classes — params/opt/EF/KV/temp) and routes it through
  `flightrec.crash_dump("oom")`, so the `comm.launch` supervisor
  gathers it like any flight dump and the merge CLI renders it.

Like the rest of `tpu_dist.observe` this module is stdlib-only at
import time (jax is probed lazily inside `memory_snapshot`), so it is
importable from bootstrap paths and usable on a login host.
"""

from __future__ import annotations

import os
import re as _re
import time

from tpu_dist.observe import events as _events
from tpu_dist.observe import flightrec as _flightrec

# The phase vocabulary the sampler buckets watermark deltas into — the
# union of the trainer span phases, the serve engine's step halves, and
# the elastic-resume redistribution (`train.reshard`).
PHASES = (
    "data", "dispatch", "readback", "checkpoint", "prefill", "decode",
    "reshard",
)

_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def host_rss_bytes() -> int | None:
    """Current resident-set size of this process (bytes).  Linux
    ``/proc/self/statm`` first (live number), `getrusage` peak as the
    fallback so the function still answers off-Linux."""
    try:
        with open("/proc/self/statm") as fh:
            return int(fh.read().split()[1]) * _PAGE_SIZE
    except (OSError, ValueError, IndexError):
        pass
    return host_peak_rss_bytes()


def host_peak_rss_bytes() -> int | None:
    """Peak RSS of this process (bytes) — ``ru_maxrss`` is kilobytes on
    Linux, bytes on macOS."""
    try:
        import resource
        import sys

        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(peak) if sys.platform == "darwin" else int(peak) * 1024
    except Exception:
        return None


def memory_snapshot(device=None) -> dict:
    """One live memory reading: ``{source, bytes_in_use,
    peak_bytes_in_use, bytes_limit}``.

    ``source`` is ``"hbm"`` when the backend tracks device memory
    (real chips), ``"rss"`` for the host-RSS fallback (CPU-sim —
    ``bytes_limit`` is None there: the host has no HBM budget).  Keys
    are always present so consumers never probe."""
    stats = None
    if device is not None or _jax_available():
        try:
            import jax

            dev = device if device is not None else jax.devices()[0]
            stats = getattr(dev, "memory_stats", lambda: None)()
        except Exception:
            stats = None
    if stats:
        return {
            "source": "hbm",
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        }
    return {
        "source": "rss",
        "bytes_in_use": host_rss_bytes(),
        "peak_bytes_in_use": host_peak_rss_bytes(),
        "bytes_limit": None,
    }


def _jax_available() -> bool:
    """True when jax is imported AND a backend is already initialized — a
    telemetry read must never be the thing that first initializes one: a
    chip belongs to the process that touches it first, and a supervisor
    or load generator sampling memory must not take it from its worker."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return False
    return bool(jax._src.xla_bridge.backends_are_initialized())  # noqa: SLF001


def publish_gauges(snapshot: dict, registry=None) -> None:
    """Set the ``tpu_dist_hbm_{in_use,peak,limit}_bytes`` gauges from
    one snapshot (labeled with its source, so an RSS fallback never
    masquerades as a chip reading in a scrape)."""
    from tpu_dist.observe import registry as reg_mod

    reg = registry if registry is not None else reg_mod.REGISTRY
    src = snapshot.get("source", "?")
    for key, name, help_ in (
        ("bytes_in_use", "tpu_dist_hbm_in_use_bytes",
         "live device-memory (or host-RSS fallback) bytes in use"),
        ("peak_bytes_in_use", "tpu_dist_hbm_peak_bytes",
         "peak device-memory (or host-RSS fallback) bytes"),
        ("bytes_limit", "tpu_dist_hbm_limit_bytes",
         "device-memory capacity (absent on the RSS fallback)"),
    ):
        value = snapshot.get(key)
        if value is not None:
            reg.gauge(name, help_).set(value, source=src)


class WatermarkSampler:
    """Phase-bucketed peak-memory accounting for one rank.

    Each `sample(phase)` takes a snapshot and attributes the watermark
    delta (``peak_bytes_in_use`` growth since the previous sample) to
    ``phase``; per-phase buckets accumulate ``{samples, delta_bytes,
    peak_bytes}``.  The watermark only ever rises, so the sum of the
    per-phase deltas is the run's total peak growth and the phase with
    the largest delta is where the footprint was built.  Every rise
    also lands one ``memory`` record in the flight ring — the per-rank
    memory trajectory a post-mortem merge renders."""

    def __init__(self, device=None, *, flight=None, registry=None):
        self.device = device
        self.flight = flight if flight is not None else _flightrec.get()
        self.registry = registry
        self.phases: dict[str, dict] = {}
        self.last: dict | None = None
        self._last_peak: int | None = None
        self.last_phase: str | None = None

    def snapshot(self) -> dict:
        """The most recent sample (a fresh unbucketed reading when
        never sampled — probing must not invent a phase delta)."""
        if self.last is None:
            return memory_snapshot(self.device)
        return dict(self.last)

    def sample(self, phase: str) -> dict:
        snap = memory_snapshot(self.device)
        peak = snap.get("peak_bytes_in_use")
        bucket = self.phases.setdefault(
            phase, {"samples": 0, "delta_bytes": 0, "peak_bytes": None}
        )
        bucket["samples"] += 1
        if peak is not None:
            delta = peak - self._last_peak if self._last_peak is not None else 0
            if delta > 0:
                bucket["delta_bytes"] += int(delta)
                # ring record only when the watermark MOVED: a steady-
                # state step adds nothing, so the ring keeps its step
                # history instead of drowning in flat memory lines
                self.flight.record(
                    "memory", phase=phase, peak_bytes=int(peak),
                    delta_bytes=int(delta), source=snap.get("source"),
                )
            bucket["peak_bytes"] = int(peak)
            self._last_peak = int(peak)
        self.last = snap
        self.last_phase = phase
        publish_gauges(snap, self.registry)
        return snap

    def summary(self) -> dict:
        """The ``memory`` event payload: the latest snapshot plus the
        per-phase watermark attribution."""
        snap = self.last or memory_snapshot(self.device)
        return {
            "source": snap.get("source"),
            "bytes_in_use": snap.get("bytes_in_use"),
            "peak_bytes_in_use": snap.get("peak_bytes_in_use"),
            "bytes_limit": snap.get("bytes_limit"),
            "phases": {k: dict(v) for k, v in self.phases.items()},
        }

    def emit(self, logger=None) -> dict | None:
        """Emit the required ``memory`` telemetry event."""
        log = logger if logger is not None else _events.from_env()
        return log.emit("memory", **self.summary())


class MemoryBoundExceeded(RuntimeError):
    """An explicitly-accounted transient exceeded its configured bound —
    a broken streaming plan (a bug), not an organic OOM."""


class TransientMeter:
    """Exact accounting of TRANSIENT host bytes for a bounded streaming
    operation (the elastic-resume redistribution, `train.reshard`).

    RSS cannot isolate transient overhead on the CPU-sim: the target
    device buffers land in the same process RSS as the staging buffers,
    so "never materialize a full replica" must be asserted on an
    explicit counter — `hold` on staging-buffer allocation, `release`
    after hand-off to the device.  With ``limit_bytes`` set, crossing
    the bound raises `MemoryBoundExceeded` at the exact allocation that
    broke it.  Pair with a `WatermarkSampler` for the ambient watermark
    (the `reshard` event reports both)."""

    def __init__(self, limit_bytes: int | None = None, *,
                 what: str = "reshard"):
        self.limit_bytes = limit_bytes
        self.what = what
        self.current = 0
        self.peak = 0

    def hold(self, nbytes: int) -> None:
        self.current += int(nbytes)
        if self.current > self.peak:
            self.peak = self.current
        if self.limit_bytes is not None and self.current > self.limit_bytes:
            raise MemoryBoundExceeded(
                f"{self.what}: transient host bytes ({self.current}) "
                f"exceed the configured bound ({self.limit_bytes}) — the "
                "streaming bucket plan is broken"
            )

    def release(self, nbytes: int) -> None:
        self.current = max(0, self.current - int(nbytes))


# ------------------------------------------------------------ OOM forensics


# Substrings that mark an allocation failure on the step path: XLA
# surfaces RESOURCE_EXHAUSTED through XlaRuntimeError (and sometimes a
# bare "out of memory" on CPU allocators / MemoryError).
OOM_MARKERS = ("RESOURCE_EXHAUSTED", "resource exhausted", "out of memory",
               "Out of memory")
# bare "OOM" only as a whole word — a substring match would flag
# unrelated text like "BLOOM" and pollute the forensics with spurious
# flight dumps
_OOM_WORD = _re.compile(r"\bOOM\b")


def is_resource_exhausted(exc: BaseException) -> bool:
    """True when ``exc`` is an allocation failure worth a memory
    post-mortem: a `MemoryError`, or any exception whose message (or
    type name) carries an OOM marker — XLA's ``RESOURCE_EXHAUSTED``
    status rides `XlaRuntimeError` text, not a dedicated type."""
    if isinstance(exc, MemoryError):
        return True
    text = f"{type(exc).__name__}: {exc}"
    return (any(marker in text for marker in OOM_MARKERS)
            or bool(_OOM_WORD.search(text)))


def oom_report(*, phase: str | None, snapshot: dict | None = None,
               resident: list | None = None, plan: dict | None = None,
               error: str | None = None) -> dict:
    """The plan-vs-live OOM story: which PHASE was executing, how much
    HEADROOM the device had (``bytes_limit - bytes_in_use``; None on
    the RSS fallback, which has no budget), the top RESIDENT classes
    (params / opt / ef_residual / kv_pool / weights / batch — whatever
    the caller can attribute), and the static plan's numbers when one
    is on hand, so "live exceeded plan" is readable from the dump."""
    snap = snapshot or memory_snapshot()
    limit = snap.get("bytes_limit")
    in_use = snap.get("bytes_in_use")
    headroom = (
        int(limit) - int(in_use)
        if limit is not None and in_use is not None else None
    )
    rows = sorted(
        (dict(r) for r in (resident or []) if r.get("bytes") is not None),
        key=lambda r: -int(r["bytes"]),
    )
    return {
        "phase": phase,
        "source": snap.get("source"),
        "bytes_in_use": in_use,
        "peak_bytes_in_use": snap.get("peak_bytes_in_use"),
        "bytes_limit": limit,
        "headroom_bytes": headroom,
        "resident": rows,
        "top_class": rows[0]["class"] if rows else None,
        "plan": plan,
        "error": error,
    }


def record_oom(exc: BaseException, *, phase: str | None = None,
               sampler: WatermarkSampler | None = None,
               resident: list | None = None, plan: dict | None = None,
               events_logger=None, dirpath: str | None = None) -> dict:
    """The one OOM entry point every step path calls: build the
    plan-vs-live report, append it to the flight ring as a ``mark``
    (``what: "oom"``), dump the ring via `flightrec.crash_dump("oom")`
    — the supervisor gathers it like any flight dump — and emit an
    ``oom`` telemetry event.  Never raises (it runs on a crash path);
    returns the report."""
    try:
        snap = None
        if sampler is not None:
            # a FRESH reading at failure time — the sampler's last
            # sample predates the failing allocation, so its in-use
            # number would overstate the headroom.  Exception: a
            # tracked (hbm) snapshot the live probe cannot reproduce
            # stays authoritative — that is the documented fake-
            # bytes_limit test hook on backends with no tracked HBM.
            snap = memory_snapshot(sampler.device)
            last = sampler.last
            if (last is not None and last.get("source") == "hbm"
                    and snap.get("source") != "hbm"):
                snap = dict(last)
        if phase is None and sampler is not None:
            phase = sampler.last_phase
        report = oom_report(
            phase=phase, snapshot=snap, resident=resident, plan=plan,
            error=f"{type(exc).__name__}: {str(exc)[:500]}",
        )
    except Exception:
        report = {"phase": phase, "error": repr(exc), "headroom_bytes": None,
                  "top_class": None}
    try:
        _flightrec.get().record("mark", what="oom", t_mark=time.time(),
                                **report)
    except Exception:
        pass
    try:
        _flightrec.crash_dump("oom", dirpath=dirpath)
    except Exception:
        pass
    try:
        log = events_logger if events_logger is not None else _events.from_env()
        log.emit(
            "oom",
            phase=report.get("phase"),
            headroom_bytes=report.get("headroom_bytes"),
            top_class=report.get("top_class"),
            source=report.get("source"),
            bytes_in_use=report.get("bytes_in_use"),
            bytes_limit=report.get("bytes_limit"),
            resident=report.get("resident"),
            error=report.get("error"),
        )
    except Exception:
        pass
    return report
