"""Device time by program and by the program's own scopes, from a kept
profiler trace:

    CHIPBENCH_KEEP_TRACE=1 python3 -m chipbench.run --workload <cell> ... --trace 1
    python3 -m chipbench.scopes .chipbench_trace/<cell>        # or a .xplane.pb

The harness makes the same table of every traced run, before it deletes the
trace, and hands it to the per-layer readers as ``run.scopes``
(`chipbench/device_reads.py` has the few reads they share).

How an event finds its program and its scope.  `jax.profiler.ProfileData`
does not surface an event's metadata, so the file is read as what it is, an
``XSpace`` protobuf, with the few lines of wire format below (no generated
module, no TensorFlow).  On a device plane every event of the ``XLA Ops``
line points at an ``XEventMetadata`` whose stats hold ``program_id`` (the
``XLA Modules`` line's events are named ``<module>(<program_id>)``) and
``tf_op``: the ``op_name`` XLA kept for the instruction, which is JAX's
name stack, ``jit(train_step)/transpose(jvp(block/attn))/dot_general``.
The scope is the innermost entry of the vocabulary (`SCOPES`, plus what the
configuration's family file lists, `vocabulary`) on that path; the pass is
``remat`` under ``rematted_computation``, else ``bwd`` under
``transpose(``, else ``fwd``.  A FUSION IS CHARGED TO ITS ROOT'S SCOPE:
XLA gives a fusion the metadata of its root instruction, so what it fused
in from a neighbouring scope is counted with the root.  What the compiler
put in itself has no scope of its own and is charged, in this order:

- an instruction whose ``tf_op`` is an argument's path (``cache[3]['k']``:
  a copy of that argument into another layout) to ``arg:cache``;
- one with no ``tf_op`` to the scope of the instruction it reads (the
  first ``%operand`` in its text, looked up in the same program: the copy
  of a scatter's result back into the argument's layout counts under
  ``attn/kv_scatter``), then to that of the event it is nested in (a
  loop's body under ``grad_accum``);
- else to ``(unscoped)``.

Time charged by one of these three rules was NOT under a `jax.named_scope`:
the table counts it apart (``fallback_share_pct``, a ``*`` on the row)
from the time whose own ``op_name`` carries an entry of `SCOPES`
(``scoped_share_pct``), so a scope that goes missing shows as a fall of the
second, whatever the rules then do with the time.  The (operation, scope)
table keeps them apart too: ``copy / attn/kv_scatter`` is not the scatter.

Self time follows `xplane.self_times`' nesting rule (an event's time less
that of the events nested in it) and sums to the same total.  Idle gaps
are split among the innermost ``tpu_dist/...`` spans (the program's,
`tpu_dist.observe.spans`) that cover their parts, else the innermost
``chipbench/...`` spans (the harness's): a 3-ms gap between two steps lies
under a wait, the bookkeeping and the next dispatch, so `xplane.idle_gaps`'
rule (the span over the gap's middle) would name one of three.

The device's clock and the host's are not one.  Device times are first
moved onto the host's clock by ``device_ahead_ms``, and that offset is
BOUNDED from the file, not assumed (`clock_bounds`): a program's run cannot
start before the host span that dispatches it has, and the span that reads
its result back cannot end before the run has.  The table is made at the
middle of the two bounds and the idle gaps also at either bound; at gaps of
a few ms the split between neighbouring phases moves with the offset, the
sum does not.
"""

from __future__ import annotations

import argparse
import json
import re
import struct
import sys
from bisect import bisect_left, bisect_right
from dataclasses import dataclass, field
from pathlib import Path

from chipbench import xplane

# the program's scope vocabulary (`PERF.md` §3); a family file adds its
# architecture's own under the same two names (`vocabulary`)
SCOPES = (
    # serving (`serve/paged_kv.py`, `serve/engine.py`)
    "embed", "ln", "attn/qkv", "attn/kv_scatter", "attn/kv_gather",
    "attn/scores", "attn/out", "mlp", "lm_head", "sample", "state_update",
    # training (`models/transformer_lm.py`, the train step)
    "cast", "block/attn", "block/mlp", "loss", "grad_accum", "optimizer", "grad_sync",
)
# the program's Pallas kernels, by their `pl.pallas_call(name=)`
KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq", "matmul_fused", "ring_all_reduce")
UNSCOPED = "(unscoped)"
PROGRAM_SPANS, HARNESS_SPANS = "tpu_dist/", "chipbench/"
MODULES_LINE = "XLA Modules"
OWN, FALLBACK = "own", "fallback"   # how an event came by its scope

# host span -> the program (by the start of its name) that the span
# dispatches / whose result it reads back: what `clock_bounds` rests on
DISPATCHES = {
    PROGRAM_SPANS + "engine.decode_dispatch": "serve_decode",
    PROGRAM_SPANS + "engine.prefill_dispatch": "serve_prefill",
    HARNESS_SPANS + "train_step": "train_step",
}
READBACKS = {
    PROGRAM_SPANS + "engine.decode_wait": "serve_decode",
    PROGRAM_SPANS + "engine.prefill_wait": "serve_prefill",
    HARNESS_SPANS + "train_step": "train_step",   # trainer.step, then host_sync(loss)
}


# ------------------------------------------------------ protobuf wire format


def _fields(buf: bytes):
    """(field number, wire type, value) of one message: a varint as int, a
    length-delimited field as bytes, a fixed 64/32 as its raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield num, wire, val


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


@dataclass
class Meta:
    """An ``XEventMetadata``: the event's name and its stats by name."""
    name: str = ""
    stats: dict = field(default_factory=dict)


@dataclass
class Line:
    name: str = ""
    timestamp_ns: int = 0
    events: list = field(default_factory=list)   # (metadata id, start ns, dur ns)


@dataclass
class Plane:
    name: str = ""
    lines: list = field(default_factory=list)
    metas: dict = field(default_factory=dict)     # id -> Meta


def _stat(buf: bytes) -> tuple[int, object]:
    """An ``XStat`` -> (stat metadata id, value); a ``ref_value`` stays the
    id of the stat metadata that holds the string."""
    key, val = 0, None
    for num, wire, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num == 3:
            val = v
        elif num == 7:
            val = ("ref", v)
        elif num == 4:
            val = _signed(v)
        elif num == 5:
            val = v.decode("utf-8", "replace")
        elif num == 6:
            val = v
    return key, val


def _plane(buf: bytes) -> Plane:
    plane, stat_names, raw_metas = Plane(), {}, []
    for num, _, v in _fields(buf):
        if num == 2:
            plane.name = v.decode()
        elif num == 3:
            plane.lines.append(_line(v))
        elif num == 4:      # map<int64, XEventMetadata> entry: key=1, value=2
            raw_metas.extend(val for n, _, val in _fields(v) if n == 2)
        elif num == 5:      # map<int64, XStatMetadata> entry
            for n, _, val in _fields(v):
                if n == 2:
                    sid, sname = 0, ""
                    for n2, _, v2 in _fields(val):
                        if n2 == 1:
                            sid = v2
                        elif n2 == 2:
                            sname = v2.decode()
                    stat_names[sid] = sname
    for raw in raw_metas:
        mid, meta = 0, Meta()
        for n, _, v in _fields(raw):
            if n == 1:
                mid = v
            elif n == 2:
                meta.name = v.decode("utf-8", "replace")
            elif n == 5:
                key, val = _stat(v)
                if isinstance(val, tuple):
                    val = stat_names.get(val[1], "")
                meta.stats[stat_names.get(key, str(key))] = val
        plane.metas[mid] = meta
    return plane


def _line(buf: bytes) -> Line:
    line, raw = Line(), []
    for num, _, v in _fields(buf):
        if num == 2:
            line.name = v.decode()
        elif num == 3:
            line.timestamp_ns = _signed(v)
        elif num == 4:
            raw.append(v)
    for ev in raw:
        mid = off = dur = 0
        for n, _, v in _fields(ev):
            if n == 1:
                mid = v
            elif n == 2:
                off = _signed(v)
            elif n == 3:
                dur = _signed(v)
        # whole nanoseconds, as `jax.profiler.ProfileData` gives them to `xplane.load`
        line.events.append((mid, float(line.timestamp_ns + off // 1000), float(dur // 1000)))
    return line


def _plane_name(buf: bytes) -> str:
    return next((v.decode() for num, _, v in _fields(buf) if num == 2), "")


def raw_planes(path: str) -> list[tuple[str, bytes]]:
    """(name, bytes) of each ``XPlane``, unparsed: parsing is the cost, and
    of four chips' planes `table` reads one."""
    with open(path, "rb") as fh:
        buf = fh.read()
    return [(_plane_name(v), v) for num, _, v in _fields(buf) if num == 1]


def read_xspace(path: str) -> list[Plane]:
    return [_plane(buf) for _, buf in raw_planes(path)]


# ---------------------------------------------------------------- reduction


_WRAPPERS = re.compile(r"(?:jvp|transpose|vmap)\(|\)")
_ARGUMENT = re.compile(r"([A-Za-z_]\w*)\[")  # a leaf of an argument's tree


def vocabulary(*families) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """(scopes, kernels): the program's own (`SCOPES`, `KERNELS`) and what
    each family file offers under the same two names (both optional)."""
    def with_theirs(base: tuple, name: str) -> tuple:
        return tuple(dict.fromkeys((*base, *(x for fam in families for x in getattr(fam, name, ())))))

    return with_theirs(SCOPES, "SCOPES"), with_theirs(KERNELS, "KERNELS")


def scope_of(op_name: str, scopes: tuple[str, ...] = SCOPES) -> str | None:
    """The innermost entry of ``scopes`` on an ``op_name`` path;
    ``arg:<name>`` for an argument's own path; else None."""
    op_name = op_name.split(";", 1)[0]
    path = "/" + _WRAPPERS.sub("", op_name) + "/"
    best, where = None, (-1, 0)
    for scope in scopes:
        at = path.rfind("/" + scope + "/")
        if at >= 0 and (at + len(scope), len(scope)) > where:
            best, where = scope, (at + len(scope), len(scope))
    if best is None and "/" not in op_name:
        arg = _ARGUMENT.match(op_name)
        if arg:
            return "arg:" + arg.group(1)
    return best


def pass_of(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "remat"
    return "bwd" if "transpose(" in op_name else "fwd"


def kernel_of(meta: Meta, kernels: tuple[str, ...] = KERNELS) -> str | None:
    """The name of the Pallas kernel an instruction is, or None: the entry
    before ``pallas_call`` on its ``tf_op`` path (`pl.pallas_call(name=)`
    is a scope of its own), else its base name where the vocabulary lists it."""
    path = (meta.stats.get("tf_op") or "").rstrip(":").split(";", 1)[0].split("/")
    if len(path) > 1 and path[-1] == "pallas_call":
        return path[-2]
    base = xplane.op_name(meta.name)
    return base if base in kernels else None


def program_name(module_event: str) -> str:
    """``jit_serve_prefill(123456)`` -> ``serve_prefill``."""
    name = module_event.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def _self_times(events: list) -> list:
    """[(event, self ns, the event it is nested in or None)], by
    `xplane.self_times`' rule; an event is (meta id, start ns, dur ns)."""
    out, stack = [], []   # stack of [event, child ns, parent event]

    def close(item):
        ev, child, parent = item
        out.append((ev, max(ev[2] - child, 0.0), parent))

    for e in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and e[1] >= stack[-1][0][1] + stack[-1][0][2]:
            close(stack.pop())
        if stack:
            stack[-1][1] += e[2]
        stack.append([e, 0.0, stack[-1][0] if stack else None])
    while stack:
        close(stack.pop())
    return out


def table(path: str, device_ahead_ms: float | None = None, *, families=()) -> dict:
    """The first chip's device time by program (each run's duration too), by
    (program, scope, pass) and by named kernel, its collectives by scope,
    and its idle gaps by host span.  ``families``: the family modules whose
    scopes and kernels join the vocabulary.  Without a ``device_ahead_ms``
    the clock offset is the middle of `clock_bounds` (0 where the file
    bounds nothing)."""
    vocab, kernel_names = vocabulary(*families)
    raw = raw_planes(path)
    planes = [_plane(buf) for name, buf in raw if name == xplane.HOST_PLANE]
    chip = None
    for name, buf in sorted(raw):
        if name.startswith(xplane.DEVICE_PLANE):
            chip = _plane(buf)
            if any(ln.name.startswith(xplane.OPS_LINE) and ln.events for ln in chip.lines):
                break
            chip = None
    if chip is None:
        raise ValueError(f"no device plane with operations in {path}")
    ops = [e for ln in chip.lines if ln.name.startswith(xplane.OPS_LINE) for e in ln.events]
    modules = sorted((e for ln in chip.lines if ln.name == MODULES_LINE for e in ln.events),
                     key=lambda e: e[1])

    program_runs: dict[str, list[float]] = {}   # program -> each run's device seconds, in order
    by_id: dict[int, str] = {}
    for mid, _, dur in modules:
        name = chip.metas[mid].name
        program_runs.setdefault(program_name(name), []).append(dur / 1e9)
        m = re.search(r"\((\d+)\)\s*$", name)
        if m:
            by_id[int(m.group(1))] = program_name(name)

    def program_of(ev) -> str:
        pid = chip.metas[ev[0]].stats.get("program_id")
        if pid in by_id:
            return by_id[pid]
        mid_ns = ev[1] + ev[2] / 2
        for m_id, start, dur in modules:
            if start <= mid_ns <= start + dur:
                return program_name(chip.metas[m_id].name)
        return "(no program)"

    selfs = _self_times(ops)
    parent_of = {id(ev): parent for ev, _, parent in selfs}
    # (program id, "%fusion.51") -> the metadata of that instruction
    by_instr = {
        (m.stats.get("program_id"), m.name.split(" = ", 1)[0]): m
        for m in chip.metas.values() if m.name.startswith("%")
    }
    memo: dict[int, tuple[str, str, str] | None] = {}

    def own_scope(meta: Meta, hops: int = 4) -> tuple[str, str, str] | None:
        """(scope, pass, how) of one instruction: `OWN` where its own
        ``tf_op`` holds an entry of `SCOPES`; `FALLBACK` where it is an
        argument's path, or the scope is that of the instruction it reads."""
        if id(meta) in memo:
            return memo[id(meta)]
        op = (meta.stats.get("tf_op") or "").rstrip(":")
        scope = scope_of(op, vocab) if op else None
        found = (scope, pass_of(op), OWN if scope in vocab else FALLBACK) if scope else None
        if found is None and not op and hops:
            body = meta.name.split(" = ", 1)[-1]
            read = re.search(r"%[\w.\-]+", body.split("(", 1)[-1])
            src = by_instr.get((meta.stats.get("program_id"), read.group(0))) if read else None
            if src is not None and src is not meta:
                found = own_scope(src, hops - 1)
                found = found and (*found[:2], FALLBACK)
        memo[id(meta)] = found
        return found

    def scoped(ev) -> tuple[str, str, str]:
        """(scope, pass, how) of an event, looked for up the nesting."""
        at = ev
        while at is not None:
            found = own_scope(chip.metas[at[0]])
            if found:
                return found if at is ev else (*found[:2], FALLBACK)
            at = parent_of.get(id(at))
        return UNSCOPED, "fwd", UNSCOPED

    by_scope: dict[tuple, float] = {}
    by_op_scope: dict[tuple, float] = {}
    by_how = {OWN: 0.0, FALLBACK: 0.0, UNSCOPED: 0.0}
    collectives: dict[str, float] = {}
    kernels: dict[str, list] = {}   # kernel -> [self seconds, calls]
    kernel_by_meta: dict[int, str | None] = {}
    for ev, self_ns, _ in selfs:
        scope, which, how = scoped(ev)
        key = (program_of(ev), scope, which)
        by_scope[key] = by_scope.get(key, 0.0) + self_ns / 1e9
        by_how[how] += self_ns / 1e9
        op = xplane.op_name(chip.metas[ev[0]].name)
        by_op_scope[op, scope, how] = by_op_scope.get((op, scope, how), 0.0) + self_ns / 1e9
        if xplane.is_collective(op):
            collectives[scope] = collectives.get(scope, 0.0) + self_ns / 1e9
        if ev[0] not in kernel_by_meta:
            kernel_by_meta[ev[0]] = kernel_of(chip.metas[ev[0]], kernel_names)
        kernel = kernel_by_meta[ev[0]]
        if kernel:
            seen = kernels.setdefault(kernel, [0.0, 0])
            seen[0] += self_ns / 1e9
            seen[1] += 1
    total = sum(by_scope.values())
    share = lambda s: 100.0 * s / total if total else 0.0  # noqa: E731

    host = _host_spans(planes)
    bounds = clock_bounds(host, [(program_name(chip.metas[m].name), s, d) for m, s, d in modules])
    if device_ahead_ms is None:
        device_ahead_ms = sum(bounds) / 2 if bounds else 0.0
    busy = xplane.busy_intervals([xplane.Event("", s, d) for _, s, d in ops])
    timeline = _timeline(host)
    gaps, window_s = _idle_gaps(host, timeline, busy, device_ahead_ms)
    ends = [_idle_gaps(host, timeline, busy, b)[0] for b in bounds or ()]
    ordered = lambda d: sorted(d.items(), key=lambda kv: -kv[1])  # noqa: E731
    return {
        "chip": chip.name,
        "programs": ordered({name: sum(runs) for name, runs in program_runs.items()}),
        "program_runs": program_runs,
        "by_scope": [[*k, s] for k, s in ordered(by_scope)],
        "total_self_s": total,
        "scoped_share_pct": share(by_how[OWN]),
        "fallback_share_pct": share(by_how[FALLBACK]),
        "by_op_scope": [[*k, s] for k, s in ordered(by_op_scope)],
        "kernels": [[name, s, calls] for name, (s, calls) in
                    sorted(kernels.items(), key=lambda kv: -kv[1][0])],
        "collectives_by_scope": ordered(collectives),
        "idle_gaps": ordered(gaps),
        "idle_gaps_at_bounds": [[name, *(e.get(name, 0.0) for e in ends)]
                                for name, _ in ordered(gaps)] if ends else [],
        "window_s": window_s,
        "device_ahead_ms": device_ahead_ms,
        "device_ahead_bounds_ms": list(bounds) if bounds else None,
    }


def _host_spans(planes: list) -> dict[str, list]:
    """{prefix: the host plane's spans under it}, the program's and the harness's."""
    host = {PROGRAM_SPANS: [], HARNESS_SPANS: []}
    for p in planes:
        if p.name != xplane.HOST_PLANE:
            continue
        for ln in p.lines:
            for m, s, d in ln.events:
                name = p.metas[m].name if m in p.metas else ""
                for prefix, into in host.items():
                    if name.startswith(prefix):
                        into.append(xplane.Event(name, s, d))
    return host


def clock_bounds(host: dict, runs: list, dispatches: dict = DISPATCHES,
                 readbacks: dict = READBACKS) -> tuple[float, float] | None:
    """(low, high) in ms for how far the device's clock is ahead of the
    host's (negative: behind), from what cannot happen: a run of a program
    starts no earlier than the host span that dispatches it (an upper
    bound, tight when the device was idle), and the span that reads its
    result back ends no earlier than the run (a lower bound, short by the
    time the host took to notice).  ``runs`` is the ``XLA Modules`` line as
    (program, start ns, dur ns).  The k-th span of a name belongs to the
    k-th run of its program: the harness starts and stops the profiler
    between two steps; where the two counts differ the pair says nothing.
    None where no pair speaks or the two bounds cross."""
    spans = sorted((s for group in host.values() for s in group), key=lambda s: s.start_ns)
    runs = sorted(runs, key=lambda r: r[1])

    def pairs(table: dict):
        for name, program in table.items():
            mine = [s for s in spans if s.name == name]
            theirs = [r for r in runs if r[0].startswith(program)]
            if mine and len(mine) == len(theirs):
                yield from zip(mine, theirs)

    highs = [(start - sp.start_ns) / 1e6 for sp, (_, start, _) in pairs(dispatches)]
    lows = [(start + dur - sp.end_ns) / 1e6 for sp, (_, start, dur) in pairs(readbacks)]
    if not highs or not lows or max(lows) > min(highs):
        return None
    return max(lows), min(highs)


def _timeline(host: dict) -> tuple[list[float], list[str]]:
    """The host's time cut at every span's edge: (the edges, with an
    infinite one at either end; the name of each piece between two).  A
    piece is named after the innermost (shortest) program span over it,
    else the innermost harness span, else ``unannotated``."""
    edges = sorted({t for group in host.values() for s in group for t in (s.start_ns, s.end_ns)})
    edges = [float("-inf"), *edges, float("inf")]
    names = ["unannotated"] * (len(edges) - 1)
    for prefix in (HARNESS_SPANS, PROGRAM_SPANS):   # the program's are written last: they win
        shortest: dict[int, float] = {}
        for s in host[prefix]:
            for k in range(bisect_left(edges, s.start_ns), bisect_left(edges, s.end_ns)):
                if s.dur_ns < shortest.get(k, float("inf")):
                    shortest[k], names[k] = s.dur_ns, s.name
    return edges, names


def _idle_gaps(host: dict, timeline: tuple, busy: list, device_ahead_ms: float):
    """-> ({host span name: idle seconds of the chip under it}, window s).
    ``busy`` is the device's `xplane.busy_intervals`, moved here onto the
    host's clock; the window runs from the first to the last harness span
    (else program span, else device operation)."""
    shift = device_ahead_ms * 1e6
    busy = [(a - shift, b - shift) for a, b in busy]
    marks = host[HARNESS_SPANS] or host[PROGRAM_SPANS]
    lo = min(s.start_ns for s in marks) if marks else busy[0][0]
    hi = max(s.end_ns for s in marks) if marks else busy[-1][1]
    edges, names = timeline
    gaps: dict[str, float] = {}
    for (_, end), (start, _) in zip([(lo, lo), *busy], [*busy, (hi, hi)]):
        a, b = max(end, lo), min(start, hi)
        k = bisect_right(edges, a) - 1
        while a < b:   # the gap's part in each piece of the timeline it crosses
            upto = min(b, edges[k + 1])
            gaps[names[k]] = gaps.get(names[k], 0.0) + (upto - a) / 1e9
            a, k = upto, k + 1
    return gaps, (hi - lo) / 1e9


def render(t: dict) -> str:
    total = t["total_self_s"] or 1.0
    out = [f"{t['chip']}: {t['total_self_s']:.4f} s of operations' self time: "
           f"{t['scoped_share_pct']:.1f} % under a scope of the program's own, "
           f"{t['fallback_share_pct']:.1f} % charged by a fallback rule (*), "
           f"{100.0 - t['scoped_share_pct'] - t['fallback_share_pct']:.1f} % unscoped",
           "", "device seconds by program (XLA Modules):"]
    out += [f"  {s:10.4f}  {name}" for name, s in t["programs"]]
    out += ["", "self time by program / scope / pass (a fusion counts with its root's scope):"]
    out += [f"  {s:10.4f}  {100 * s / total:5.1f} %  {prog} / {scope} / {which}"
            for prog, scope, which, s in t["by_scope"]]
    out += ["", "the largest (operation, scope) pairs (*: the scope is not the operation's own):"]
    out += [f"  {s:10.4f}  {100 * s / total:5.1f} %  {op} / {scope}{' *' if how == FALLBACK else ''}"
            for op, scope, how, s in t["by_op_scope"][:16]]
    if t["kernels"]:
        out += ["", "named kernels (pl.pallas_call(name=)): self seconds, calls"]
        out += [f"  {s:10.4f}  {calls:6d}  {name}" for name, s, calls in t["kernels"]]
    if t["collectives_by_scope"]:
        out += ["", "collectives by scope:"]
        out += [f"  {s:10.4f}  {name}" for name, s in t["collectives_by_scope"]]
    bounds = t["device_ahead_bounds_ms"]
    said = (f"between {bounds[0]:.3f} and {bounds[1]:.3f} ms ahead of the host's by the "
            f"file's dispatches and readbacks" if bounds else "not bounded by this file")
    out += ["", f"idle gaps over {t['window_s']:.3f} s by host span, the device's clock taken "
                f"{t['device_ahead_ms']:.3f} ms ahead ({said}):"]
    at_bounds = {name: rest for name, *rest in t["idle_gaps_at_bounds"]}
    for name, s in t["idle_gaps"]:
        ends = at_bounds.get(name)
        out.append(f"  {s:10.4f}  {name}" + (f"   ({ends[0]:.4f} .. {ends[1]:.4f})" if ends else ""))
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory or a .xplane.pb")
    ap.add_argument("--device-ahead-ms", type=float, default=None,
                    help="clock offset to assume; default: the middle of the file's own bounds")
    ap.add_argument("--json", help="also write the table here")
    args = ap.parse_args(argv)
    path = args.trace if args.trace.endswith(".pb") else xplane.find_trace(args.trace)
    from chipbench.manifest import Manifest

    # by hand no cell is named: every family file's scopes and kernels count
    t = table(path, args.device_ahead_ms,
              families=Manifest(Path(__file__).resolve().parents[1]).families())
    print(render(t))
    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(t, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
