"""`tpu_dist.observe` — the unified telemetry subsystem.

The reference's observability story is per-rank ``print`` (SURVEY.md §5);
before this package ours was scattered timing helpers (`train.metrics`),
a stderr watchdog (`utils.debug`), and interleaved stdout.  This package
is the measurement substrate the ROADMAP's perf PRs cite:

- `events`    — per-rank structured JSONL event log (manifest + step /
                epoch / checkpoint / retry / chaos / stall records),
                opt-in via ``TPU_DIST_TELEMETRY=<dir>``
- `registry`  — counters / gauges / histograms with a Prometheus
                text-exposition endpoint (``TPU_DIST_METRICS_PORT``)
- `spans`     — host-side span tracing emitted as Chrome-trace JSON,
                correlated with `jax.profiler` device traces by step id
- `compile_spans` — JAX's trace / lower / compile-or-load stages as
                kept spans of the same ring, by program name
                (`jax.monitoring` listeners; `install` is called when
                ``tpu_dist`` is imported)
- `heartbeat` — per-rank progress heartbeats, stall attribution
                ("rank N is K seconds behind"), and goodput accounting
- `flightrec` — always-on per-rank ring buffer of step/phase/collective
                records, dumped on watchdog fire / signals / chaos kill /
                crashes; ``python -m tpu_dist.observe.flightrec merge``
                clock-aligns the dumps and names the divergent rank
- `memory`    — live memory snapshots (HBM, host-RSS fallback on
                CPU-sim), phase-bucketed watermark accounting, and OOM
                forensics (`record_oom` → flight dump + ``oom`` event)
- `results`   — the shared loader for the persisted
                ``benchmarks/results/*.jsonl`` records (metric-series /
                platform-provenance filtering) that `regress`, the
                attribution row gates, and `analysis.costmodel` all
                route through
- `regress`   — trailing-median regression checker over the persisted
                bench trajectory (``python -m tpu_dist.observe.regress``;
                a ``-m`` CLI like flightrec's merge — import it
                explicitly, it is not re-exported here)

Everything here is stdlib-only and import-light: these modules are
imported from bootstrap paths (`comm.launch._child`,
`resilience.chaos`) that run before JAX backends initialize.  The one
exception is `observe.attribution` (plan-vs-measured cost attribution —
it EXECUTES compiled programs, so it needs jax); import it explicitly.
"""

from tpu_dist.observe import (
    compile_spans,
    events,
    flightrec,
    heartbeat,
    memory,
    registry,
    results,
    spans,
)

__all__ = [
    "compile_spans", "events", "flightrec", "heartbeat", "memory", "registry",
    "results", "spans",
]
