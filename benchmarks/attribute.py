"""make attribute: plan-vs-measured cost attribution + stage cost tables.

Joins the static analyzer's collective plan with the clock
(`tpu_dist.observe.attribution`):

- For each selected canonical program (default: ``engine_dp_fsdp_int8``,
  the engine's composed-mesh quantized wire) it measures the real step
  wall time, replays every (kind, axes, dtype) collective class on the
  same mesh with the plan's exact payloads, and emits a report whose
  per-class payload BYTES are checked row-exact against the blessed
  golden plan (``tests/goldens/``) while the TIMES and achieved wire
  GB/s are measured.  Reports persist to
  ``benchmarks/results/attribution.jsonl`` and ride the ``attribution``
  telemetry event + Prometheus gauges.
- It measures per-stage forward/backward costs of a deliberately
  UNBALANCED pipeline LM — embedding-heavy stage 0, vocab-head-heavy
  stage n−1 — and persists the rows to
  ``benchmarks/results/stage_costs.jsonl``: the measured cost tables
  ROADMAP item 4's cost-weighted schedule generator consumes.

``--smoke`` (make attribute-smoke, the CI gate) runs a tiny program and
a tiny pipeline, asserting the report validates and the stage-costs
file row-parses.  Exit 1 on golden mismatch, an unmeasured class, or an
invalid report.  CPU-sim GB/s are memcpy numbers — regression guards,
not bandwidth claims (docs/observability.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def build_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--platform", default=os.environ.get("TPU_DIST_PLATFORM")
    )
    ap.add_argument(
        "--programs", default="engine_dp_fsdp_int8",
        help="comma-separated canonical analysis programs to attribute "
        "(tpu_dist.analysis.programs)",
    )
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--stages", type=int, default=4,
                    help="pipeline stages for the unbalanced-LM cost table")
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--microbatch", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny program + tiny pipeline; assert the report "
                    "validates and stage_costs.jsonl row-parses (CI)")
    ap.add_argument("--no-persist", action="store_true")
    ap.add_argument("--skip-stage-costs", action="store_true")
    return ap.parse_args(argv)


def goldens_dir() -> str:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(here, "tests", "goldens")


def attribute_one(name: str, args) -> tuple:
    """Fresh-build one canonical program (executing a donating engine
    step consumes its args — never run the shared cache), attribute it,
    gate the report's bytes against the blessed golden."""
    from tpu_dist.analysis import programs as prog_mod
    from tpu_dist.observe import attribution as attr_mod

    prog = prog_mod.fresh_program(name)
    report = attr_mod.attribute_program(
        prog, iters=args.iters, warmup=args.warmup, measure_step=True
    )
    diffs = attr_mod.check_against_golden(report, goldens_dir())
    errors = list(report.validate())
    if report.golden == "diff":
        errors.extend(f"golden mismatch: {d}" for d in diffs)
    elif report.golden == "skew":
        log(f"[{name}] golden blessed under a different jax — bytes "
            f"compared against the live plan only")
    unmeasured = [
        c.label for c in report.classes
        if c.measured_s is None or c.measured_s <= 0
    ]
    if prog.mesh is not None and unmeasured:
        errors.append(f"unmeasured collective classes: {unmeasured}")
    for line in report.summary_lines():
        log(line)
    log(f"[{name}] golden gate: {report.golden}")
    attr_mod.emit_report(report)
    if not args.no_persist:
        import bench

        bench.persist_event(
            {"metric": "attribution", **report.to_dict()},
            out_name="attribution.jsonl",
        )
    return report, errors


def unbalanced_lm_stages(args):
    """A deliberately unbalanced pipeline LM as per-global-stage fns:
    stage 0 carries the (vocab × dim) embedding table, middle stages are
    plain blocks, stage n−1 carries the (dim × vocab) head + loss — the
    exact imbalance that breaks equal-cost schedule tables."""
    import jax
    import jax.numpy as jnp

    V, D, S, n = args.vocab, args.dim, args.seq, args.stages
    keys = jax.random.split(jax.random.key(0), n + 1)

    def block_params(k, scale=0.1):
        k1, k2 = jax.random.split(k)
        return {
            "w1": jax.random.normal(k1, (D, D)) * scale,
            "w2": jax.random.normal(k2, (D, D)) * scale,
            "b1": jnp.zeros((D,)),
            "b2": jnp.zeros((D,)),
        }

    def block(p, h):
        h = jnp.tanh(h @ p["w1"] + p["b1"])
        return jnp.tanh(h @ p["w2"] + p["b2"])

    def embed_stage(p, tokens):  # embedding-heavy stage 0
        h = p["emb"][tokens]
        return block(p["block"], h)

    def mid_stage(p, h):
        return block(p["block"], h)

    def head_stage(p, h):  # vocab-heavy stage n-1: head matmul + loss
        h = block(p["block"], h)
        logits = h @ p["head"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        tgt = p["targets"]
        return -jnp.mean(
            jnp.take_along_axis(logp, tgt[..., None], axis=-1)
        )

    rng = jax.random
    tokens = rng.randint(keys[n], (args.microbatch, S), 0, V)
    targets = rng.randint(keys[n - 1], (args.microbatch, S), 0, V)
    params = [
        {"emb": rng.normal(keys[0], (V, D)) * 0.02,
         "block": block_params(keys[0])}
    ]
    fns = [embed_stage]
    for s in range(1, n - 1):
        params.append({"block": block_params(keys[s])})
        fns.append(mid_stage)
    params.append({
        "block": block_params(keys[n - 1]),
        "head": rng.normal(keys[n - 1], (D, V)) * 0.02,
        "targets": targets,
    })
    fns.append(head_stage)
    return fns, params, tokens


def run_stage_costs(args) -> tuple[list, list]:
    from tpu_dist.observe import attribution as attr_mod

    fns, params, tokens = unbalanced_lm_stages(args)
    rows = attr_mod.measure_stage_costs(
        fns, params, tokens, iters=args.iters, warmup=args.warmup,
        model=f"unbalanced_lm_v{args.vocab}_d{args.dim}_n{args.stages}",
    )
    errors = []
    log("stage cost table (measured F/B per microbatch):")
    for r in rows:
        log(
            f"  stage {r['stage']}/{r['n_stages']}: "
            f"F {r['fwd_s'] * 1e3:7.3f}ms  B {r['bwd_s'] * 1e3:7.3f}ms  "
            f"params {r['params_bytes'] / 1e6:6.2f}MB"
        )
        if r["fwd_s"] <= 0 or r["bwd_s"] <= 0:
            errors.append(f"stage {r['stage']}: non-positive measured cost")
    if not args.no_persist:
        path = attr_mod.persist_stage_costs(rows)
        log(f"persisted {len(rows)} stage rows -> {path}")
        # row-parse gate through the SHARED loader (the exact read path
        # item 4's generator and the cost model consume): the rows just
        # written must come back with their provenance intact
        back = attr_mod.load_stage_cost_rows(
            path, spec_hash=rows[0].get("spec_hash") if rows else None,
        )[-len(rows):]
        if len(back) != len(rows):
            errors.append(
                f"stage_costs round-trip: wrote {len(rows)} rows, loader "
                f"returned {len(back)} for this spec_hash"
            )
        for rec in back:
            for keyname in ("spec_hash", "mesh_shape"):
                if keyname not in rec:
                    errors.append(f"stage_costs row missing {keyname!r}")
    return rows, errors


def main(argv=None) -> int:
    args = build_args(argv)
    if args.smoke:
        args.programs = "engine_dp"
        args.iters = min(args.iters, 2)
        args.warmup = 1
        args.stages, args.vocab, args.dim, args.seq = 3, 128, 16, 8
    from tpu_dist.utils.platform import select_platform

    select_platform(args.platform, 8)

    errors: list[str] = []
    reports = []
    for name in [p.strip() for p in args.programs.split(",") if p.strip()]:
        report, errs = attribute_one(name, args)
        reports.append(report)
        errors.extend(f"[{name}] {e}" for e in errs)
    if not args.skip_stage_costs:
        _, errs = run_stage_costs(args)
        errors.extend(errs)

    headline = {
        "metric": "attribute",
        "programs": [r.program for r in reports],
        "golden": {r.program: r.golden for r in reports},
        "step_ms": {
            r.program: (round(r.step_time_s * 1e3, 3)
                        if r.step_time_s else None)
            for r in reports
        },
        "compute_share": {
            r.program: (round(r.compute_s / r.step_time_s, 4)
                        if r.step_time_s and r.compute_s is not None
                        else None)
            for r in reports
        },
        "errors": errors,
    }
    print(json.dumps(headline))
    if errors:
        for e in errors:
            log(f"ERROR: {e}")
        return 1
    log("attribute OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
