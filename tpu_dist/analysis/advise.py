"""``python -m tpu_dist.analysis.advise`` — the static auto-sharding
advisor CLI and the cost-model calibration gate.

Two modes:

- **advise** (default; ``make advise``): fit the α–β cost model from
  the persisted attribution rows, enumerate candidate (mesh_axes,
  compress) configurations for ``--model`` at ``--chips`` chips, prune
  on the memory plan vs ``--bytes-limit``, rank survivors by predicted
  step time, check rank agreement against the measured ``bench-mesh``
  trajectory, predict the pipeline bubble from the measured stage-cost
  table, and emit the validated ``advice`` telemetry event.  Exit 1
  when the agreement check runs and fails.
- **costcheck** (``--costcheck``; ``make costcheck``): pure data-plane
  calibration gate — fit on the persisted attribution rows, predict
  each program's own measured step time back, fail (exit 1) when any
  program's relative error exceeds the blessed tolerance
  (``tests/goldens/costcheck.json``; ``--bless-tolerance`` re-blesses).
  Rows recorded under a different jax report ``skew`` and are waived,
  analyzer-style — re-run ``make attribute`` under the new version to
  re-arm the gate.  Emits the validated ``costcheck`` event.

CPU-sim caveat: fitted bandwidths are memcpy numbers; rankings and
regression gates are meaningful, absolute times only on real chips
(docs/analysis.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _default_goldens() -> str:
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(here, "tests", "goldens")


def _jax_version() -> str | None:
    try:
        import jax

        return jax.__version__
    except Exception:
        return None


def _platform_rows(path: str | None):
    """Attribution rows scoped to the platform of the latest recording
    (a CPU round must never calibrate against TPU rows or vice versa)."""
    from tpu_dist.observe import attribution as attr_mod
    from tpu_dist.observe import results as results_mod

    rows = attr_mod.load_attribution_rows(path)
    if not rows:
        return [], None
    plat = results_mod.row_platform(rows[-1])
    if plat is not None:
        rows = [
            r for r in rows
            if results_mod.row_platform(r) in (None, plat)
        ]
    return rows, plat


def run_costcheck(args) -> int:
    from tpu_dist.analysis import costmodel as cost_mod
    from tpu_dist.observe import events as ev_mod

    say = (lambda *a: None) if args.quiet else print
    rows, plat = _platform_rows(args.path)
    tolerance = args.tolerance
    if tolerance is None:
        tolerance = cost_mod.load_blessed_tolerance(args.goldens)
    if tolerance is None:
        tolerance = cost_mod.DEFAULT_TOLERANCE
    if args.bless_tolerance is not None:
        path = cost_mod.save_blessed_tolerance(
            args.goldens, args.bless_tolerance
        )
        say(f"blessed costcheck tolerance {args.bless_tolerance} -> "
            f"{os.path.relpath(path)}")
        tolerance = args.bless_tolerance
    if not rows:
        say("costcheck: no attribution rows — run `make attribute` first")
        ev_mod.from_env().emit(
            "costcheck", programs=0, tolerance=tolerance, status="no-rows",
        )
        return 0
    model, verdicts = cost_mod.calibration_check(
        rows, tolerance=tolerance, jax_version=_jax_version()
    )
    say(f"costcheck: platform {plat or '?'}  tolerance {tolerance:.0%}  "
        f"({model.n_rows} rows, {len(model.terms)} class terms)")
    for v in verdicts:
        meas = (f"{v['measured_s'] * 1e3:8.3f}ms"
                if v["measured_s"] else "      --")
        pred = (f"{v['predicted_s'] * 1e3:8.3f}ms"
                if v["predicted_s"] is not None else "      --")
        err = f"{v['error']:+.1%}" if v["error"] is not None else "--"
        say(f"  {v['status']:>9}  {v['program']:<24} measured {meas}  "
            f"predicted {pred}  err {err}")
        if v["status"] == "skew":
            say(f"             (recorded under jax "
                f"{v.get('recorded_jax')} — re-run `make attribute` "
                f"under this version to re-arm)")
    violations = [v for v in verdicts if v["status"] == "violation"]
    states = {v["status"] for v in verdicts}
    status = (
        "violation" if violations
        else "skew" if states == {"skew"}
        else "ok"
    )
    ev_mod.from_env().emit(
        "costcheck",
        programs=len(verdicts),
        tolerance=tolerance,
        status=status,
        verdicts=verdicts,
    )
    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump({"tolerance": tolerance, "status": status,
                       "verdicts": verdicts,
                       "model": model.summary()}, fh, indent=2,
                      sort_keys=True, default=str)
        say(f"report -> {args.json}")
    if violations:
        say(f"costcheck FAILED: {len(violations)} program(s) past "
            f"±{tolerance:.0%}")
        return 1
    say("costcheck OK" if status == "ok" else f"costcheck: {status}")
    return 0


def run_advise(args) -> int:
    from tpu_dist.analysis import advisor as adv_mod
    from tpu_dist.analysis import costmodel as cost_mod
    from tpu_dist.observe import attribution as attr_mod
    from tpu_dist.observe import events as ev_mod
    from tpu_dist.observe import results as results_mod

    say = (lambda *a: None) if args.quiet else print
    rows, plat = _platform_rows(args.path)
    specs = (
        [s.strip() for s in args.specs.split(";") if s.strip()]
        if args.specs else None
    )
    compress_modes = tuple(
        m.strip() for m in args.compress.split(",") if m.strip()
    )
    report = adv_mod.advise(
        model=args.model,
        chips=args.chips,
        compress_modes=compress_modes,
        specs=specs,
        bytes_limit=args.bytes_limit,
        attribution_rows=rows,
    )
    for line in report.summary_lines():
        say(line)
    empty = not report.ranked()
    if empty:
        say("advise: no viable candidates survived")

    # measured-rank agreement vs the persisted bench-mesh trajectory
    agreement = None
    if not args.no_agreement and not empty:
        bench_rows = results_mod.load_rows(
            args.bench_path or results_mod.results_path("bench_runs.jsonl"),
            series="mesh_rule_set", platform=plat,
        )
        measured = adv_mod.measured_rule_ranking(bench_rows)
        agreement = adv_mod.rank_agreement(
            report, measured, tolerance=args.agreement_tolerance
        )
        if agreement["checked"]:
            say(
                f"rank agreement vs bench-mesh: predicted best "
                f"{agreement['predicted_best']!r}, measured best "
                f"{agreement['measured_best']!r} -> "
                + ("AGREE" if agreement["agree"] else "DISAGREE")
                + f" (±{agreement['tolerance']:.0%} band)"
            )
        else:
            say("rank agreement: no measured bench-mesh rows to check "
                "against (run `make bench-mesh`)")

    # pipeline bubble prediction from the measured stage-cost table
    stage_rows = attr_mod.load_stage_cost_rows(platform=plat)
    table = cost_mod.stage_table_from_rows(stage_rows)
    bubble = None
    if table is not None:
        from tpu_dist.parallel.pipeline import build_schedule

        n = table["n_stages"]
        M = 4 * n
        bubble = {"model": table["model"], "n": n, "M": M}
        for kind in ("gpipe", "1f1b"):
            sched = build_schedule(n, M, 1, kind)
            bubble[kind] = round(cost_mod.predict_bubble_fraction(
                sched, table["fwd_s"], table["bwd_s"]
            ), 4)
            bubble[f"{kind}_uniform"] = round(sched.bubble_fraction(), 4)
        say(
            f"pipeline bubble (measured stage costs, {table['model']}, "
            f"n={n}, M={M}): gpipe {bubble['gpipe']:.1%} "
            f"(uniform-table {bubble['gpipe_uniform']:.1%}), "
            f"1f1b {bubble['1f1b']:.1%} "
            f"(uniform-table {bubble['1f1b_uniform']:.1%})"
        )

    fields = report.event_fields()
    fields["agreement"] = agreement
    fields["bubble"] = bubble
    rec = ev_mod.from_env().emit("advice", **fields)
    if rec is not None:
        errs = ev_mod.validate_record(rec)
        if errs:
            say(f"advice event INVALID: {errs}")
            return 1
    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(fields, fh, indent=2, sort_keys=True, default=str)
        say(f"report -> {args.json}")
    if empty:
        return 1  # the null-best advice event above records the refusal
    if agreement and agreement["checked"] and not agreement["agree"]:
        say("advise FAILED: predicted ranking disagrees with the "
            "measured bench-mesh trajectory past the tolerance band")
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tpu_dist.analysis.advise",
        description="static auto-sharding advisor + cost-model "
        "calibration gate",
    )
    ap.add_argument("--model", default="lm",
                    help="advisor model spec: 'lm' (default) or 'mlp'")
    ap.add_argument("--chips", type=int, default=8)
    ap.add_argument("--compress", default="off,int8",
                    help="comma-separated compress modes per candidate")
    ap.add_argument("--specs", default=None,
                    help="semicolon-separated mesh_axes specs (default: "
                    "parallel.enumerate_mesh_axes over --chips)")
    ap.add_argument("--bytes-limit", type=int, default=None,
                    help="per-rank memory budget; candidates whose "
                    "memory-plan peak exceeds it are pruned")
    ap.add_argument("--path", default=None,
                    help="attribution.jsonl (default: benchmarks/results/)")
    ap.add_argument("--bench-path", default=None,
                    help="bench_runs.jsonl for the agreement check")
    ap.add_argument("--goldens", default=_default_goldens())
    ap.add_argument("--no-agreement", action="store_true",
                    help="skip the measured-rank agreement check")
    ap.add_argument("--agreement-tolerance", type=float, default=0.15)
    ap.add_argument("--costcheck", action="store_true",
                    help="run the calibration gate instead of advising")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="costcheck: override the blessed tolerance")
    ap.add_argument("--bless-tolerance", type=float, default=None,
                    help="costcheck: (re)write tests/goldens/costcheck.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny model, two candidates, no "
                    "agreement check")
    ap.add_argument("--json", default=None, metavar="PATH")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    if args.smoke:
        args.model = "mlp"
        args.specs = args.specs or f"dp={args.chips};fsdp={args.chips}"
        args.compress = "off"
        args.no_agreement = True
    if args.costcheck:
        # pure data-plane: no mesh, no compiles, no pinning needed
        return run_costcheck(args)
    # The advisor compiles candidates for a CPU-sim mesh of the ADVISED
    # chip count; pin before any backend initializes (the analyzer-CLI
    # bootstrap, sized by --chips so `make advise WORLD=16` works).
    from tpu_dist.utils.platform import pin_cpu

    pin_cpu(max(8, args.chips))
    return run_advise(args)


if __name__ == "__main__":
    sys.exit(main())
