"""bench-mesh: partition rule sets at EQUAL chips — memory vs goodput.

The partition engine (`tpu_dist.parallel.partition`) claims that
data_parallel / fsdp / zero1 / composed dp×fsdp / dp×tp are rule sets
over ONE train step, and that the sharded weight update buys the ZeRO
memory savings without a dedicated code path.  This bench measures both
halves for a TransformerLM + adamw on the same chip count:

- per-chip bytes of params + optimizer state — counted from the live
  arrays' actual shards on device 0 (`partition.per_device_bytes`),
  plus XLA's compiled temp-buffer plan as the transient high water;
- tokens/s over timed steps (data-dependent chain closed by a host
  readback — the round-2 timing discipline);
- bytes-on-wire of the gradient sync per rank per step, for the exact
  f32 wire AND the engine's compressed int8 wire (``--compress``):
  the same rule set measured with and without the quantized EF bucket
  collectives inside the GSPMD program.

Prints a per-rule-set table to stderr and ONE JSON line to stdout;
persists one record per rule set to ``benchmarks/results/
bench_runs.jsonl`` via `bench.persist_event`.  CPU-sim numbers are
regression guards, not TPU numbers (docs/perf.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def build_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--world", type=int, default=8)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument(
        "--rule-sets", default=None,
        help="semicolon-separated mesh_axes specs, e.g. "
        "'dp=8;dp=2,fsdp=4' (default: dp / zero1 / fsdp / dp×fsdp / "
        "dp×tp at --world chips)",
    )
    ap.add_argument(
        "--compress", default="off,int8",
        help="comma-separated compress settings per rule set: 'off', "
        "'int8' (the engine's quantized EF wire), or both (default)",
    )
    ap.add_argument("--no-persist", action="store_true")
    return ap.parse_args(argv)


def default_rule_sets(world: int) -> list[str]:
    half = world // 2
    sets = [f"dp={world}", f"zero1:dp={world}", f"fsdp={world}"]
    if half >= 2:
        sets += [f"dp=2,fsdp={half}", f"dp=2,tp={half}"]
    return sets


def measure(args, spec: str, compress: str = "off") -> dict:
    import jax
    import numpy as np

    from tpu_dist import parallel
    from tpu_dist.comm import compress as compress_mod
    from tpu_dist.models.transformer_lm import TransformerLM, lm_loss
    from tpu_dist.train import metrics as metrics_mod
    from tpu_dist.train.optim import adamw
    from tpu_dist.utils.platform import host_sync

    mesh = parallel.build_mesh(spec, platform=args.platform)
    rules = parallel.resolve_rules(spec, mesh)
    lm = TransformerLM(
        vocab=args.vocab, dim=args.dim, depth=args.depth,
        heads=args.heads, max_seq=args.seq,
    )
    params, _ = lm.init(jax.random.key(0))

    def loss_fn(p, tokens, key):
        logits, _ = lm.apply(p, {}, tokens)
        return lm_loss(logits.astype(jax.numpy.float32), tokens), {}

    ccfg = compress_mod.parse(compress)
    built = parallel.make_partitioned_train_step(
        loss_fn, adamw(1e-3), mesh, params, rules, compress=ccfg
    )
    from jax.sharding import NamedSharding

    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        rng.integers(0, args.vocab, (args.batch, args.seq), dtype=np.int32),
        NamedSharding(mesh, rules.batch_spec()),
    )
    dev0 = mesh.devices.flat[0]
    # Per-chip state bytes BEFORE donation churns the buffers: the live
    # shard truth of what this rule set keeps resident per device.
    param_bytes = parallel.per_device_bytes(built.params, dev0)
    opt_bytes = parallel.per_device_bytes(built.opt_state, dev0)
    mem = metrics_mod.compiled_memory_analysis(
        lambda p, o, t, k: built.step(p, o, t, k), built.params,
        built.opt_state, tokens, jax.random.key(0),
    )
    p, o = built.params, built.opt_state
    key = jax.random.key(1)
    loss = None
    for _ in range(args.warmup):
        p, o, loss, _ = built.step(p, o, tokens, key)
    if loss is not None:
        host_sync(loss)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        p, o, loss, _ = built.step(p, o, tokens, key)
    final = float(host_sync(loss))
    dt = time.perf_counter() - t0
    step_s = dt / max(args.steps, 1)
    # gradient-sync bytes per rank per step: the engine plan's quantized
    # wire when compressed, the f32 ring lower bound otherwise — BOTH
    # over MODEL-LOCAL leaf shapes (tp-sharded grads reduce over the
    # data axes at their shard shape in either mode), so the off-vs-int8
    # comparison is apples-to-apples.
    if built.flat_plan is not None:
        wire_bytes = built.flat_plan.bytes_on_wire("all_reduce")
    else:
        from tpu_dist.parallel.partition import _local_shape

        n_data = int(np.prod([int(mesh.shape[a]) for a in rules.data_axes]))
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        spec_leaves = treedef.flatten_up_to(built.param_specs)
        local_tmpl = jax.tree_util.tree_unflatten(treedef, [
            jax.ShapeDtypeStruct(
                _local_shape(
                    tuple(leaf.shape), spec, rules.model_axes, mesh
                ),
                leaf.dtype,
            )
            for leaf, spec in zip(p_leaves, spec_leaves)
        ])
        ref = compress_mod.FlatPlan(
            local_tmpl, n_data, compress_mod.parse("int8")
        )
        wire_bytes = ref.bytes_exact("all_reduce")
    from tpu_dist.observe import memory as memory_mod

    # peak footprint (HBM or labeled RSS fallback) joins the persisted
    # row, so bench_runs.jsonl carries the memory trajectory too
    live_mem = memory_mod.memory_snapshot(dev0)
    return {
        "rule_set": rules.name,
        "compress": ccfg.wire if ccfg is not None else "off",
        "peak_memory_bytes": live_mem.get("peak_bytes_in_use"),
        "memory_source": live_mem.get("source"),
        "grad_bytes_on_wire": int(wire_bytes),
        "mesh_axes": spec,
        "axes": {str(k): int(v) for k, v in dict(mesh.shape).items()},
        "chips": int(mesh.devices.size),
        "tokens_per_sec": round(args.batch * args.seq / step_s, 1),
        "step_ms": round(step_s * 1e3, 2),
        "param_bytes_per_chip": int(param_bytes),
        "opt_bytes_per_chip": int(opt_bytes),
        "state_bytes_per_chip": int(param_bytes + opt_bytes),
        "temp_bytes": (mem or {}).get("temp_bytes"),
        "final_loss": final,
    }


def run(args) -> dict:
    import jax

    specs = (
        [s.strip() for s in args.rule_sets.split(";") if s.strip()]
        if args.rule_sets
        else default_rule_sets(args.world)
    )
    if len(jax.devices()) < args.world:
        raise SystemExit(
            f"bench-mesh needs {args.world} devices; have "
            f"{len(jax.devices())}"
        )
    modes = [m.strip() for m in args.compress.split(",") if m.strip()]
    rows = [
        measure(args, spec, compress=mode)
        for spec in specs
        for mode in modes
    ]
    dp_bytes = next(
        (
            r["state_bytes_per_chip"]
            for r in rows
            if r["rule_set"] == "dp" and r["compress"] == "off"
        ),
        None,
    )
    for r in rows:
        r["state_vs_dp"] = (
            round(r["state_bytes_per_chip"] / dp_bytes, 4) if dp_bytes else None
        )
        log(
            f"[{r['rule_set']:>10s}/{r['compress']:>4s}] "
            f"{r['tokens_per_sec']:>10,.0f} tok/s  "
            f"wire {r['grad_bytes_on_wire'] / 1e6:6.2f} MB  "
            f"state/chip {r['state_bytes_per_chip'] / 1e6:6.2f} MB"
            + (
                f" ({r['state_vs_dp']:.2f}x dp)"
                if r["state_vs_dp"] is not None
                else ""
            )
            + (
                f"  temp {r['temp_bytes'] / 1e6:.1f} MB"
                if r["temp_bytes"]
                else ""
            )
        )
    out = {
        "metric": "mesh_rule_sets",
        "value": rows[0]["tokens_per_sec"] if rows else None,
        "unit": "tokens_per_sec",
        "chips": args.world,
        "model": f"lm_d{args.dim}_l{args.depth}",
        "rows": rows,
    }
    if not args.no_persist:
        import bench

        for r in rows:
            path = bench.persist_event({
                "metric": "mesh_rule_set",
                "value": r["tokens_per_sec"],
                "unit": "tokens_per_sec",
                "bench": "mesh",
                **r,
            })
        log(f"persisted {len(rows)} rows -> {path}")
    return out


def main():
    args = build_args()
    from tpu_dist.utils.platform import select_platform

    select_platform(args.platform, max(8, args.world))
    print(json.dumps(run(args)))


if __name__ == "__main__":
    main()
