"""Static per-program memory plans — the HBM twin of the collective gate.

`analysis.plan` made the compiled WIRE structure a comparable artifact;
this module does the same for the compiled MEMORY structure.  OOM is
the dominant production failure mode, and until now the repo's memory
story was three disconnected hooks (`train.metrics.device_memory_stats`,
`compiled_memory_analysis`, `parallel.per_device_bytes`) with no plans,
no budgets and no gate.  Here:

- `extract_memory_plan(program)` turns XLA's
  ``compiled.memory_analysis()`` (argument / output / temp / alias /
  generated-code bytes — a compile-time property, available on every
  backend including CPU-sim) plus rule-engine STATE attribution
  (per-class resident shard bytes on device 0 via
  `parallel.state_bytes_by_class`: params / opt / EF-residual for
  engine programs, weights / KV-pool for the serving steps) into a
  per-rank `MemoryPlan` for any `analysis.AnalysisProgram`.
- ``peak_bytes`` is the plan's headline: arguments + outputs + temps +
  generated code, minus the aliased (donated) overlap — the
  steady-state high-water a rank needs to run this program.
- `save_memory_golden` / `load_memory_golden` /
  `compare_to_memory_golden` persist the plan under
  ``tests/goldens/memory/`` and compare row-exact (every byte field),
  with the analyzer's version-skew tolerance: exact byte counts are an
  XLA-lowering artifact, so a golden blessed under a different jax
  reports skew instead of failing the gate.
- The CLI (``python -m tpu_dist.analysis.memory`` / ``make memcheck``)
  runs the gate over the canonical programs — a PR that regresses a hot
  path's peak HBM fails CI with the offending field named.  ``--bless``
  regenerates (``make memcheck-bless``).

The live counterpart is `observe.memory` (watermark sampling, OOM
forensics): plans say what SHOULD be resident, the sampler says what
IS, and `observe.memory.record_oom` joins the two when a step path
hits RESOURCE_EXHAUSTED.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field

from tpu_dist.analysis import plan as plan_mod

# XLA's compiled memory sections, in plan/golden order.
XLA_FIELDS = (
    "argument_bytes",
    "output_bytes",
    "temp_bytes",
    "alias_bytes",
    "generated_code_bytes",
)


def compiled_memory_stats(fn, args) -> dict | None:
    """XLA's memory plan for one jitted fn on example args (arrays or
    ShapeDtypeStructs — nothing executes, nothing is donated): the
    `XLA_FIELDS` section bytes, or None where the backend exposes no
    `memory_analysis` (the plan then carries null XLA rows and the
    golden gate compares state rows only)."""
    import jax

    if not hasattr(fn, "lower"):
        fn = jax.jit(fn)
    try:
        ma = fn.lower(*args).compile().memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }


@dataclass
class MemoryPlan:
    """The per-rank memory footprint of one compiled program.

    ``xla``: the compiled sections (`XLA_FIELDS`; values may be None on
    backends without `memory_analysis`).  ``state``: resident
    ``[{class, bytes}]`` rows attributed by the rule engine — what the
    arguments ARE (params vs opt vs EF residual vs KV pool), which the
    XLA section totals cannot say.  All numbers are PER-RANK shard
    bytes, same convention as `parallel.per_device_bytes`."""

    program: str
    mesh_axes: dict = field(default_factory=dict)
    xla: dict = field(default_factory=dict)
    state: list = field(default_factory=list)

    @property
    def peak_bytes(self) -> int | None:
        """The plan's headline: steady-state high-water per rank —
        arguments + outputs + temps + generated code minus the aliased
        (donated output reuses argument buffer) overlap.  None when the
        backend reported no sections."""
        vals = [self.xla.get(k) for k in XLA_FIELDS]
        if any(v is None for v in vals):
            return None
        arg, out, temp, alias, code = vals
        return int(arg + out + temp + code - alias)

    def state_bytes(self, cls: str) -> int | None:
        for row in self.state:
            if row.get("class") == cls:
                return int(row["bytes"])
        return None

    def rows(self) -> list[dict]:
        """The golden format: one row per XLA section, one per state
        class, plus the derived peak."""
        rows = [
            {"kind": "xla", "name": k, "bytes": self.xla.get(k)}
            for k in XLA_FIELDS
        ]
        rows += [
            {"kind": "state", "name": r["class"], "bytes": int(r["bytes"])}
            for r in sorted(self.state, key=lambda r: r["class"])
        ]
        rows.append({"kind": "derived", "name": "peak_bytes",
                     "bytes": self.peak_bytes})
        return rows

    def summary(self) -> dict:
        return {
            "program": self.program,
            "mesh_axes": dict(self.mesh_axes),
            "peak_bytes": self.peak_bytes,
            "xla": dict(self.xla),
            "state": [dict(r) for r in self.state],
        }

    def to_json(self) -> str:
        return json.dumps(self.summary(), indent=2, sort_keys=True) + "\n"

    @classmethod
    def from_json(cls, text: str) -> "MemoryPlan":
        payload = json.loads(text)
        return cls(
            program=payload.get("program", ""),
            mesh_axes=payload.get("mesh_axes", {}),
            xla=payload.get("xla", {}),
            state=payload.get("state", []),
        )


# ------------------------------------------------------------- extraction


def state_rows(program) -> list[dict]:
    """Rule-engine attribution of a program's resident state: what the
    argument bytes ARE.  Engine train steps: params / opt /
    EF-residual shard bytes on device 0 of the program's mesh (the
    rule-set truth `parallel.per_device_bytes` reads off the live
    shards).  Serve steps: weights vs KV pool (the two big arguments
    of the decode/prefill programs).  Pipeline / plain programs: the
    first argument as params.  Unattributable programs return []."""
    from tpu_dist import parallel

    dev = None
    if program.mesh is not None:
        dev = program.mesh.devices.flat[0]
    built = getattr(program, "built", None)
    if built is not None:
        return parallel.state_bytes_by_class(
            built.params, built.opt_state, dev
        )
    args = tuple(getattr(program, "args", ()) or ())
    tags = tuple(getattr(program, "tags", ()) or ())
    if "serve" in tags and len(args) >= 2:
        return parallel.state_bytes_by_class(
            None, None, dev, weights=args[0], kv_pool=args[1]
        )
    if args:
        return parallel.state_bytes_by_class(args[0], None, dev)
    return []


def extract_memory_plan(program) -> "MemoryPlan":
    """The `MemoryPlan` of one `analysis.AnalysisProgram` (cached on
    the program like its collective plan — one compile per process)."""
    cache = getattr(program, "_cache", None)
    if cache is not None and "memory_plan" in cache:
        return cache["memory_plan"]
    xla = compiled_memory_stats(program.fn, program.args) or {
        k: None for k in XLA_FIELDS
    }
    axes = {}
    if program.mesh is not None:
        axes = {
            str(k): int(v)
            for k, v in zip(
                program.mesh.axis_names, program.mesh.devices.shape
            )
        }
    plan = MemoryPlan(
        program=program.name,
        mesh_axes=axes,
        xla=xla,
        state=state_rows(program),
    )
    if cache is not None:
        cache["memory_plan"] = plan
    return plan


# ---------------------------------------------------------------- goldens


def memory_goldens_dir(goldens_dir: str) -> str:
    """Memory goldens live in a ``memory/`` subdir of the collective
    goldens dir — same blessing workflow, separate namespace."""
    return os.path.join(goldens_dir, "memory")


def memory_golden_path(goldens_dir: str, program: str) -> str:
    return os.path.join(memory_goldens_dir(goldens_dir), f"{program}.json")


def save_memory_golden(plan: MemoryPlan, goldens_dir: str) -> str:
    """Bless ``plan`` as its program's memory golden.  Records the jax
    version: exact section bytes are an XLA-lowering artifact, so a
    different jax reports skew instead of failing
    (`analysis.plan.golden_version_skew` — the same tolerance the
    collective gate uses)."""
    import jax

    os.makedirs(memory_goldens_dir(goldens_dir), exist_ok=True)
    path = memory_golden_path(goldens_dir, plan.program)
    payload = dict(plan.summary())
    payload["jax_version"] = jax.__version__
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    return path


def load_memory_golden(goldens_dir: str, program: str) -> dict | None:
    path = memory_golden_path(goldens_dir, program)
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def compare_to_memory_golden(
    plan: MemoryPlan, golden: dict, *, tolerance: float = 0.0
) -> list[str]:
    """Differences between a live memory plan and its blessed golden
    (empty = pass).  Row-exact by default: every XLA section, every
    state class, and the derived peak must match byte-for-byte — a PR
    that grows a hot path's footprint fails with the field named.
    ``tolerance`` relaxes the gate to a relative band (e.g. 0.02 allows
    2% drift) without hiding NEW or VANISHED state classes."""
    diffs = []
    if dict(plan.mesh_axes) != dict(golden.get("mesh_axes", {})):
        diffs.append(
            f"mesh axes changed: {golden.get('mesh_axes')} -> "
            f"{dict(plan.mesh_axes)}"
        )
    gold_plan = MemoryPlan(
        program=golden.get("program", ""),
        mesh_axes=golden.get("mesh_axes", {}),
        xla=golden.get("xla", {}),
        state=golden.get("state", []),
    )
    live = {(r["kind"], r["name"]): r["bytes"] for r in plan.rows()}
    gold = {(r["kind"], r["name"]): r["bytes"] for r in gold_plan.rows()}
    for key in sorted(set(gold) - set(live)):
        diffs.append(f"memory row gone: {key[0]}/{key[1]} "
                     f"({gold[key]} bytes in golden)")
    for key in sorted(set(live) - set(gold)):
        diffs.append(f"new memory row: {key[0]}/{key[1]} "
                     f"({live[key]} bytes)")
    for key in sorted(set(live) & set(gold)):
        lv, gv = live[key], gold[key]
        if gv is None or lv is None:
            if lv != gv:
                diffs.append(
                    f"{key[0]}/{key[1]}: {gv} -> {lv} "
                    f"(section tracking changed)"
                )
            continue
        band = abs(gv) * tolerance
        if abs(lv - gv) > band:
            grew = lv > gv
            diffs.append(
                f"{key[0]}/{key[1]}: {gv:,} -> {lv:,} bytes "
                f"({'+' if grew else ''}{lv - gv:,}"
                + (f", tolerance ±{band:,.0f}" if tolerance else "")
                + ")"
            )
    return diffs


# -------------------------------------------------------------------- CLI


def _default_goldens() -> str:
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(here, "tests", "goldens")


def main(argv=None) -> int:
    """``make memcheck`` — the peak-HBM regression gate.  Mirrors the
    collective analyzer CLI: per-program plan print, golden compare
    (``--bless`` regenerates), version-skew waiver, ``memcheck``
    telemetry event, exit 1 on any diff or missing golden."""
    import argparse

    from tpu_dist.utils.platform import pin_cpu

    # Same bootstrap as the collective analyzer: plans are compile-time
    # artifacts, so the 8-device CPU-sim mesh is always enough.
    pin_cpu(8)

    from tpu_dist.analysis import programs as prog_mod
    from tpu_dist.observe import events as ev_mod

    ap = argparse.ArgumentParser(
        prog="python -m tpu_dist.analysis.memory",
        description="per-program HBM memory plans + the golden gate",
    )
    ap.add_argument("--programs", default=None,
                    help="comma-separated subset (default: all canonical)")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--goldens", default=_default_goldens(),
                    help="goldens root (memory goldens live in memory/)")
    ap.add_argument("--bless", action="store_true",
                    help="(re)write memory goldens instead of comparing")
    ap.add_argument("--no-goldens", action="store_true")
    ap.add_argument("--tolerance", type=float, default=0.0,
                    help="relative byte drift allowed per row (0 = exact)")
    ap.add_argument("--json", default=None, metavar="PATH")
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
    report: dict = {"programs": {}, "golden": {}}
    for name in names:
        prog = prog_mod.canonical_program(name)
        mplan = extract_memory_plan(prog)
        peak = mplan.peak_bytes
        say(f"== {name}  (peak "
            + (f"{peak:,} B" if peak is not None else "untracked")
            + ")")
        for r in mplan.rows():
            b = f"{r['bytes']:,} B" if r["bytes"] is not None else "--"
            say(f"   {r['kind']:<8} {r['name']:<22} {b}")
        report["programs"][name] = mplan.summary()
        if args.bless:
            path = save_memory_golden(mplan, args.goldens)
            say(f"   blessed -> {os.path.relpath(path)}")
            report["golden"][name] = "blessed"
        elif not args.no_goldens:
            golden = load_memory_golden(args.goldens, name)
            if golden is None:
                say("   MEMORY GOLDEN MISSING (run `make memcheck-bless`)")
                report["golden"][name] = "missing"
                failures += 1
            elif (skew := plan_mod.golden_version_skew(golden)) is not None:
                say(f"   GOLDEN VERSION SKEW: blessed under jax {skew} "
                    f"— re-bless under this version to re-arm the gate")
                report["golden"][name] = "version-skew"
            else:
                diffs = compare_to_memory_golden(
                    mplan, golden, tolerance=args.tolerance
                )
                for d in diffs:
                    say(f"   MEMORY DIFF: {d}")
                report["golden"][name] = "stale" if diffs else "ok"
                failures += len(diffs)

    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
        say(f"report -> {args.json}")

    states = set(report["golden"].values())
    ev_mod.from_env().emit(
        "memcheck",
        programs=len(names),
        golden=(
            "blessed" if "blessed" in states
            else "missing" if "missing" in states
            else "stale" if "stale" in states
            else "version-skew" if "version-skew" in states
            else "ok" if states else None
        ),
    )
    say(
        f"\nmemchecked {len(names)} programs: "
        + ("clean" if failures == 0 else f"{failures} failure(s)")
    )
    return 0 if failures == 0 else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
