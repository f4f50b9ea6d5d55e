"""Reduction of a profiler trace (`.xplane.pb`) to the numbers the
benchmark reports: device busy and idle time, operations by time, the
collectives' share, and idle gaps by what the host was doing.

Read with `jax.profiler.ProfileData` and nothing else.  On a TPU every
chip is a plane ``/device:TPU:<n>`` whose ``XLA Ops`` line holds one event
per executed operation (nested where a loop or a call holds others); the
host's threads are lines of ``/host:CPU``, where the harness's spans
appear as ``chipbench/<name>``.  All times in one file share a clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "chipbench/"
COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast",
)


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_trace(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str, *, device_plane: str = DEVICE_PLANE, ops_line: str = OPS_LINE,
         host_plane: str = HOST_PLANE) -> tuple[dict[str, list[Event]], list[Event]]:
    """-> ({device plane name: its operation events}, the harness's spans)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith(device_plane):
            for line in plane.lines:
                if line.name.startswith(ops_line):
                    devices.setdefault(plane.name, []).extend(
                        Event(e.name, e.start_ns, e.duration_ns) for e in line.events
                    )
        elif plane.name == host_plane:
            for line in plane.lines:
                spans.extend(
                    Event(e.name[len(SPAN_PREFIX):], e.start_ns, e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX)
                )
    return devices, spans


def busy_intervals(events: list[Event]) -> list[tuple[float, float]]:
    """The union of the events' intervals, merged and in order."""
    merged: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.dur_ns <= 0:
            continue
        if merged and e.start_ns <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end_ns)
        else:
            merged.append([e.start_ns, e.end_ns])
    return [(a, b) for a, b in merged]


def self_times(events: list[Event]) -> dict[str, float]:
    """Seconds by operation, each event's time less that of the events
    nested inside it, so a loop and its body are not both counted.  An
    event's name is the whole HLO instruction (``%fusion.123 = f32[...]
    fusion(...)``); the key keeps the instruction's base name (``fusion``)."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [event, child_ns]

    def close(item):
        ev, child = item
        key = op_name(ev.name)
        out[key] = out.get(key, 0.0) + max(ev.dur_ns - child, 0.0) / 1e9

    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and e.start_ns >= stack[-1][0].end_ns:
            close(stack.pop())
        if stack:
            stack[-1][1] += e.dur_ns
        stack.append([e, 0.0])
    while stack:
        close(stack.pop())
    return out


def op_name(event_name: str) -> str:
    """``%copy-start.125 = (bf16[...]) copy-start(...)`` -> ``copy-start``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.split(r"\.", head, maxsplit=1)[0] or head


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def idle_gaps(events: list[Event], spans: list[Event], lo: float, hi: float) -> dict[str, float]:
    """Seconds the device sat idle inside ``[lo, hi]``, by the innermost
    harness span that covers each gap's middle (``unannotated`` if none)."""
    edges = [(lo, lo)] + busy_intervals(events) + [(hi, hi)]
    out: dict[str, float] = {}
    for (_, end), (start, _) in zip(edges, edges[1:]):
        a, b = max(end, lo), min(start, hi)
        if b <= a:
            continue
        mid = (a + b) / 2
        cover = [s for s in spans if s.start_ns <= mid <= s.end_ns]
        name = min(cover, key=lambda s: s.dur_ns).name if cover else "unannotated"
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def reduce(devices: dict[str, list[Event]], spans: list[Event]) -> dict | None:
    """The trace's summary, or None where no operation ran on a device.
    The window is the stretch from the first to the last harness span
    (where there are none, from the first to the last device event)."""
    devices = {k: v for k, v in devices.items() if v}
    if not devices:
        return None
    marks = spans or [e for evs in devices.values() for e in evs]
    lo, hi = min(e.start_ns for e in marks), max(e.end_ns for e in marks)
    busy = []
    for evs in devices.values():
        busy.append(sum(
            max(0.0, min(b, hi) - max(a, lo)) for a, b in busy_intervals(evs)
        ) / 1e9)
    first = devices[sorted(devices)[0]]
    ops = self_times(first)
    total = sum(ops.values())
    coll = sum(s for n, s in ops.items() if is_collective(n))
    top = lambda d: [  # noqa: E731
        [n, s] for n, s in sorted(d.items(), key=lambda kv: -kv[1])[:10]
    ]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (hi - lo) / 1e9,
        "chips": len(devices),
        "device_ops": top(ops),
        "collective_share_pct": 100.0 * coll / total if total else 0.0,
        "idle_gaps": top(idle_gaps(first, spans, lo, hi)),
    }
