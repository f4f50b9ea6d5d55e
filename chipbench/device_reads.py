"""What the per-layer readers under ``layer_metrics/`` share in reading the
device's time by the program's own names: ``run.scopes``, the table
`chipbench.scopes.table` makes of the traced slice (`None` off the chip).

A program is named by the start of its name (``serve_decode`` is the greedy
and the sampled decode program).  Every function returns `None` where there
is no trace or no program of that name ran in the slice, and 0.0 where one
ran and the scope or kernel holds no time: a PR that removes a scope still
prints the line.
"""

from __future__ import annotations

from chipbench.arithmetic import median


def runs_ms(run, program: str) -> list[float] | None:
    """Device durations, in ms, of the slice's runs of ``program``."""
    if run.scopes is None:
        return None
    found = [1e3 * s for name, runs in run.scopes["program_runs"].items()
             if name.startswith(program) for s in runs]
    return found or None


def median_run_ms(run, program: str) -> float | None:
    """Median device duration, in ms, of one run of ``program`` in the slice."""
    ms = runs_ms(run, program)
    return median(ms) if ms else None


def scope_seconds(run, program: str, scope: str | None = None) -> float | None:
    """Self seconds of ``program``, all passes: under ``scope``, or in all."""
    if runs_ms(run, program) is None:
        return None
    return sum(s for name, at, _, s in run.scopes["by_scope"]
               if name.startswith(program) and scope in (None, at))


def scope_ms_per_run(run, program: str, scope: str) -> float | None:
    """Self time under ``scope`` in ``program`` over its runs in the slice."""
    runs = runs_ms(run, program)
    return 1e3 * scope_seconds(run, program, scope) / len(runs) if runs else None


def scope_share_pct(run, program: str, scope: str) -> float | None:
    """``scope``'s share of ``program``'s self time."""
    total = scope_seconds(run, program)
    return 100.0 * scope_seconds(run, program, scope) / total if total else None


def kernel_seconds(run, prefix: str) -> float | None:
    """Device seconds of the named kernels whose name starts with ``prefix``."""
    if run.scopes is None:
        return None
    return sum(s for name, s, _ in run.scopes["kernels"] if name.startswith(prefix))
