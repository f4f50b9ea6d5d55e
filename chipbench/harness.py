"""One run of one cell: context, the traced slice, and the result line."""

from __future__ import annotations

import importlib
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

from chipbench import device as device_mod
from chipbench import scopes, xplane
from chipbench.manifest import Cell, Manifest
from chipbench.peaks import peaks_for
from chipbench.spans import CompileCounter, Recorder

# traffic kind -> the module under chipbench/kinds that drives it
KINDS = {
    "train-tokens": "chipbench.kinds.train",
    "serve-open": "chipbench.kinds.serve",
    "serve-closed": "chipbench.kinds.serve",
}


class TraceSlice:
    """The profiler over the last seconds of the window (``--trace 1``),
    with the Python tracer off, so that the trace stays small and the host
    is slowed as little as can be."""

    def __init__(self, directory: Path, recorder: Recorder, seconds: float):
        self.dir, self.rec, self.seconds = directory, recorder, seconds
        self.started = self.stopped = None

    def maybe_start(self, now: float, window_start: float, window_s: float,
                    tail_s: float = 0.0) -> None:
        """Start the profiler so that the slice ends with the window (less
        ``tail_s``, an open loop's drain).  It is stopped after the window
        has closed: stopping serialises the trace, which takes seconds, and
        inside the window that would stall the loop being measured."""
        if self.started is None and now - window_start >= window_s - tail_s - self.seconds:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.rec.annotate = True
            self.started = now

    def stop(self, now: float) -> None:
        if self.started is not None and self.stopped is None:
            import jax

            self.rec.annotate = False
            jax.profiler.stop_trace()
            self.stopped = now

    def reduce(self, families=()) -> tuple[dict | None, dict | None]:
        """(`xplane.reduce`'s summary, `scopes.table`'s table by the
        program's names), both made before the trace is deleted and both
        None where no slice was taken or no operation ran on a device."""
        if self.stopped is None:
            return None, None
        try:
            path = xplane.find_trace(str(self.dir))
            t0 = time.perf_counter()
            reduced = xplane.reduce(*xplane.load(path))
            t1 = time.perf_counter()
            table = scopes.table(path, families=families) if reduced is not None else None
            log(f"trace reduced in {t1 - t0:.1f} s, by-scope table in "
                f"{time.perf_counter() - t1:.1f} s")
            return reduced, table
        finally:
            if not os.environ.get("CHIPBENCH_KEEP_TRACE"):
                shutil.rmtree(self.dir, ignore_errors=True)


@dataclass
class RunContext:
    root: Path
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    control: bool
    devices: list
    t0: float                      # process start, time.perf_counter's clock
    rec: Recorder = field(default_factory=Recorder)
    compiles: CompileCounter | None = None
    tracer: TraceSlice | None = None


@dataclass
class Outcome:
    end_to_end: dict               # name -> value, setup_s among them
    attempted: int
    failed: int
    correct: bool
    facts: dict                    # what the per-layer readers read


@dataclass
class RunView:
    """What a per-layer metric's reader is handed."""
    cell: Cell
    facts: dict
    trace: dict | None             # `xplane.reduce`: by XLA's instruction names
    scopes: dict | None            # `scopes.table`: by program, scope, pass and kernel
    rec: Recorder
    peaks: object | None


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool, *,
             devices: list, t0: float, control: bool = False) -> dict:
    """Everything of a run after the look for the chip; returns the result
    object (`main` prints it).  Tests call this with CPU devices."""
    manifest = Manifest(root)
    cell = manifest.cell(workload)
    kind = cell.traffic["kind"]
    if kind not in KINDS:
        raise ValueError(f"traffic kind {kind!r} is not one of {sorted(KINDS)}")
    ctx = RunContext(
        root=Path(root), cell=cell, seed=int(seed), seconds=float(seconds),
        trace=bool(trace), control=control, devices=devices, t0=t0,
        compiles=CompileCounter(),
    )
    if trace:
        ctx.tracer = TraceSlice(
            ctx.root / ".chipbench_trace" / workload, ctx.rec,
            seconds=min(float(cell.traffic.get("trace_seconds", 4.0)), 0.5 * seconds),
        )
    outcome: Outcome = importlib.import_module(KINDS[kind]).run(ctx)

    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    device_extra = {}
    if trace:
        reduced, table = ctx.tracer.reduce(families=[cell.family])
        view = RunView(
            cell=cell, facts=outcome.facts, trace=reduced, scopes=table, rec=ctx.rec,
            peaks=_peaks(devices[0]),
        )
        for m in wanted:
            value = manifest.reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if reduced is not None:
            device_extra = {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
    else:
        for m in wanted:
            if m["name"] in outcome.end_to_end:
                metrics[m["name"]] = {
                    "value": float(outcome.end_to_end[m["name"]]), "unit": m["unit"],
                }
    result = {
        "correct": bool(outcome.correct),
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        "metrics": metrics,
        "device": device_mod.describe(
            devices, outcome.facts["hbm_peak_bytes"], **device_extra),
    }
    if trace and reduced is not None:
        # idle gaps by the table's rule: split among the program's own spans
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": [list(gap) for gap in table["idle_gaps"][:10]],
        }
    return result


def _peaks(dev):
    """The chip's peaks, or None where the run is a rehearsal on the CPU."""
    return peaks_for(dev.device_kind) if dev.platform == "tpu" else None


_LOG_T0 = time.perf_counter()


def claim_chips(cell: Cell) -> list:
    """What an entry does before anything of the program runs: the
    program's kernel switches (read at trace time, so set before it is
    imported), the look for the chip, and the program's own placement of
    JAX's persistent cache ($JAX_COMPILATION_CACHE_DIR if set, else
    <checkout>/.jax_cache)."""
    section = "train" if cell.traffic["kind"] == "train-tokens" else "serve"
    os.environ.update(cell.config.get(section, {}).get("env", {}))
    devices = device_mod.require_chips(cell.chips)
    from tpu_dist.utils.platform import setup_compile_cache

    setup_compile_cache()
    return devices


def seed_key(seed: int):
    """A key from any whole-number seed (the driver's exceed 31 bits)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def log(msg: str) -> None:
    print(f"chipbench [{time.perf_counter() - _LOG_T0:7.1f} s]: {msg}",
          file=sys.stderr, flush=True)
