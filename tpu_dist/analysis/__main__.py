"""``python -m tpu_dist.analysis`` — the SPMD program analyzer CLI.

Runs collective-plan extraction + every lint over the canonical entry
programs (`make analyze`) and compares each plan to its blessed golden
under ``tests/goldens/`` (``--bless`` regenerates: ``make
analyze-bless``).  Exit status 1 on any lint finding or golden
mismatch — the CI gate that turns a silent collective-structure
regression into a readable plan diff.  (The engine-vs-legacy diff pins
retired WITH the legacy builders: they held through PR 11, every
trainer flag now routes through the engine, and the goldens carry the
contract forward.)
"""

from __future__ import annotations

import os
import sys

# The analyzer compiles for the 8-device CPU-sim mesh; pin BEFORE any
# backend initializes (same bootstrap as tests/conftest.py).  Real
# hardware is never needed — plans are compile-time artifacts.
from tpu_dist.utils.platform import pin_cpu  # noqa: E402

pin_cpu(8)

import argparse  # noqa: E402
import json  # noqa: E402


def _default_goldens() -> str:
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(here, "tests", "goldens")


def main(argv=None) -> int:
    from tpu_dist.analysis import plan as plan_mod
    from tpu_dist.analysis import programs as prog_mod
    from tpu_dist.observe import events as ev_mod

    ap = argparse.ArgumentParser(
        prog="python -m tpu_dist.analysis",
        description="static analysis of the repo's compiled SPMD programs",
    )
    ap.add_argument(
        "--programs",
        default=None,
        help="comma-separated subset (default: all canonical programs)",
    )
    ap.add_argument("--list", action="store_true",
                    help="list canonical program names and exit")
    ap.add_argument("--goldens", default=_default_goldens(),
                    help="golden CollectivePlan directory")
    ap.add_argument("--bless", action="store_true",
                    help="(re)write goldens instead of comparing")
    ap.add_argument("--no-goldens", action="store_true",
                    help="skip the golden comparison")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the full report as JSON")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    if args.list:
        for name in prog_mod.CANONICAL:
            print(name)
        return 0

    names = (
        [n.strip() for n in args.programs.split(",") if n.strip()]
        if args.programs
        else list(prog_mod.CANONICAL)
    )
    say = (lambda *a: None) if args.quiet else print

    failures = 0
    findings_by_lint: dict[str, int] = {}
    report = {"programs": {}, "golden": {}}
    for name in names:
        prog = prog_mod.canonical_program(name)
        cplan = prog.plan
        rows = cplan.rows()
        say(f"== {name}  ({len(cplan)} collectives, "
            f"{cplan.total_bytes(major_only=False):,} payload bytes)")
        for r in rows:
            axes = "x".join(r["axes"]) if r["axes"] else "-"
            say(f"   {r['kind']:<20} over {axes:<10} [{r['dtype']}] "
                f"x{r['count']}  {r['bytes']:,} B")
        findings = prog.findings()
        for f in findings:
            findings_by_lint[f.lint] = findings_by_lint.get(f.lint, 0) + 1
            say(f"   FINDING {f}")
            if f.severity == "error":
                failures += 1
        report["programs"][name] = {
            "plan": cplan.summary(),
            "findings": [
                {"lint": f.lint, "severity": f.severity,
                 "message": f.message}
                for f in findings
            ],
        }
        if args.bless:
            path = plan_mod.save_golden(cplan, args.goldens)
            say(f"   blessed -> {os.path.relpath(path)}")
            report["golden"][name] = "blessed"
        elif not args.no_goldens:
            golden = plan_mod.load_golden(args.goldens, name)
            if golden is None:
                say(f"   GOLDEN MISSING (run with --bless / "
                    f"`make analyze-bless`)")
                report["golden"][name] = "missing"
                failures += 1
            elif (skew := plan_mod.golden_version_skew(golden)) is not None:
                # exact counts/bytes are an XLA-lowering artifact: a
                # different jax than the one the golden was blessed
                # under reports skew (re-bless there), never a failure
                say(f"   GOLDEN VERSION SKEW: blessed under jax {skew} "
                    f"— re-bless under this version to re-arm the gate")
                report["golden"][name] = "version-skew"
            else:
                diffs = plan_mod.compare_to_golden(cplan, golden)
                for d in diffs:
                    say(f"   GOLDEN DIFF: {d}")
                report["golden"][name] = "stale" if diffs else "ok"
                failures += len(diffs)

    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
        say(f"report -> {args.json}")

    golden_states = set(report["golden"].values())
    ev_mod.from_env().emit(
        "analysis",
        programs=len(names),
        findings=findings_by_lint,
        golden=(
            "blessed" if "blessed" in golden_states
            else "missing" if "missing" in golden_states
            else "stale" if "stale" in golden_states
            else "version-skew" if "version-skew" in golden_states
            else "ok" if golden_states else None
        ),
    )
    say(
        f"\nanalyzed {len(names)} programs: "
        + ("clean" if failures == 0 else f"{failures} failure(s)")
    )
    return 0 if failures == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
