"""Compute-bound flagship benchmark: TransformerLM train-step MFU.

The reference's latent benchmark scaffold is a communication loop
(/root/reference/allreduce.py:41-47); its TPU-native restatement is the
workload TPUs are built for — a full LM training step (fwd + bwd + adamw
update) on a GPT-2-small-class model (~110M params, bf16 compute, flash
attention, optional remat), swept over (batch, seq) and reported as MFU
(model-FLOPs utilization against the chip's public bf16 peak).

MFU follows the standard convention: the numerator counts the MODEL's
FLOPs (3x forward for fwd+bwd+update; remat's recompute is NOT credited),
so remat can only lower MFU, never inflate it.  XLA's own cost analysis
of the compiled step is printed alongside as a cross-check.

Timing uses the data-dependent chain (params of step i feed step i+1)
closed by a host readback (`utils.platform.host_sync`; see docs/perf.md).
Any config whose computed MFU exceeds 100% is rejected loudly.

Prints a per-config table to stderr and ONE JSON line to stdout with the
best config's numbers.
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


def lm_model_flops(lm, params, batch: int, seq: int) -> float:
    """Analytic forward FLOPs: 2·tokens·(matmul params) for every dense
    projection (weight-tied head counted via the logits matmul) plus the
    causal attention scores/values matmuls."""
    import numpy as np
    import jax

    from tpu_dist.train.flops import attention_flops

    tokens = batch * seq
    block_matmul = sum(
        float(np.prod(a.shape))
        for a in jax.tree.leaves(params["blocks"])
        if getattr(a, "ndim", 0) >= 2
    )
    head = 2.0 * tokens * lm.dim * lm.vocab  # logits = h @ E^T
    proj = 2.0 * tokens * block_matmul
    attn = len(lm.blocks) * attention_flops(
        batch, lm.heads, seq, seq, lm.dim // lm.heads, causal=True
    )
    return proj + head + attn


def build_args(argv=None):
    """Parse the sweep's CLI (pass ``argv=[]`` for defaults)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--dim", type=int, default=768)
    ap.add_argument("--depth", type=int, default=12)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument(
        "--configs", default="16x512,16x1024,8x2048,8x4096",
        help="comma-separated BATCHxSEQ cases",
    )
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument(
        "--remat-from", type=int, default=4096,
        help="use jax.checkpoint for seq >= this (memory headroom)",
    )
    # --pipeline switches to the pipeline-parallel goodput bench: a
    # pipe x dp mesh vs pure dp at EQUAL chips, with the schedule
    # engine's measured bubble fraction in the record.
    ap.add_argument(
        "--pipeline", choices=["gpipe", "1f1b"], default=None,
        help="run the pipeline goodput bench with this schedule instead "
        "of the MFU sweep",
    )
    ap.add_argument("--pipe-world", type=int, default=4)
    ap.add_argument("--dp-world", type=int, default=2)
    ap.add_argument("--pipe-microbatches", type=int, default=8)
    ap.add_argument("--pipe-interleave", type=int, default=2)
    ap.add_argument(
        "--pipe-blocks", type=int, default=1,
        help="transformer blocks per virtual-stage chunk (model depth = "
        "pipe-world x interleave x this)",
    )
    ap.add_argument("--pipe-dim", type=int, default=128)
    ap.add_argument("--pipe-heads", type=int, default=4)
    ap.add_argument("--pipe-vocab", type=int, default=512)
    ap.add_argument("--pipe-seq", type=int, default=128)
    ap.add_argument("--pipe-batch", type=int, default=32)
    ap.add_argument("--pipe-steps", type=int, default=6)
    ap.add_argument("--no-persist", action="store_true")
    return ap.parse_args(argv)


def main():
    args = build_args()

    # pipeline mode needs pipe_world x dp_world simulated devices
    n_devices = (
        max(8, args.pipe_world * args.dp_world) if args.pipeline else None
    )
    from tpu_dist.utils.platform import select_platform

    select_platform(args.platform, n_devices)

    if args.pipeline:
        print(json.dumps(pipeline_sweep(args)))
        return
    print(json.dumps(sweep(args)))


def _measure_steps(trainer, batch, steps: int, warmup: int):
    """Mean step seconds over ``steps`` timed iterations (data-dependent
    chain closed by a host readback)."""
    import jax

    from tpu_dist.utils.platform import host_sync

    p, ms, os_ = trainer.params, trainer._model_state, trainer.opt_state
    key = jax.random.key(0)
    loss = None
    for _ in range(warmup):
        p, ms, os_, loss, _ = trainer.step(p, ms, os_, batch, key)
    if loss is not None:  # --warmup 0: nothing dispatched yet to sync on
        host_sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        p, ms, os_, loss, _ = trainer.step(p, ms, os_, batch, key)
    final = float(host_sync(loss))
    dt = time.perf_counter() - t0
    return dt / steps, final


def pipeline_sweep(args) -> dict:
    """Pipeline-parallel goodput vs pure dp at EQUAL chips.

    Three trainers on the live backend: pure dp over all
    ``pipe_world x dp_world`` chips, and the requested pipeline schedule
    on a (data x pipe) mesh — same model, same global batch, same
    optimizer.  Reports tokens/s goodput, the schedule engine's MEASURED
    bubble fraction (idle cells of the executed table), and the
    activation-stash depth; persists one record per mode to
    ``benchmarks/results/bench_runs.jsonl``."""
    import numpy as np
    import jax

    from tpu_dist import comm, models, parallel, train
    from tpu_dist.parallel.pipeline import build_schedule

    pw, dpw = args.pipe_world, args.dp_world
    chips = pw * dpw
    if len(jax.devices()) < chips:
        raise SystemExit(
            f"pipeline bench needs {chips} devices "
            f"(pipe {pw} x dp {dpw}); have {len(jax.devices())}"
        )
    vi = args.pipe_interleave if args.pipeline == "1f1b" else 1
    depth = pw * vi * args.pipe_blocks
    M = args.pipe_microbatches
    B, S = args.pipe_batch, args.pipe_seq
    log(
        f"pipeline bench: {args.pipeline} n={pw} dp={dpw} M={M} v={vi} "
        f"depth={depth} dim={args.pipe_dim} batch={B} seq={S}"
    )

    def make_lm():
        return models.TransformerLM(
            vocab=args.pipe_vocab, dim=args.pipe_dim, depth=depth,
            heads=args.pipe_heads, max_seq=S,
        )

    rng = np.random.default_rng(0)
    toks = rng.integers(0, args.pipe_vocab, (B, S)).astype(np.int32)

    rows = {}
    # pure dp baseline at equal chips
    dp_mesh = comm.make_mesh(chips, ("data",), mesh_devices=jax.devices()[:chips])
    dp_tr = train.LMTrainer(
        make_lm(), dp_mesh,
        train.LMTrainConfig(global_batch=B, log=log),
    )
    dp_batch = parallel.shard_batch((toks,), dp_mesh)
    step_s, loss = _measure_steps(dp_tr, dp_batch, args.pipe_steps, args.warmup)
    rows["dp"] = {
        "mode": "dp", "chips": chips, "step_ms": round(step_s * 1e3, 2),
        "tokens_per_sec": round(B * S / step_s, 1), "loss": round(loss, 4),
        "bubble_fraction": None,
    }

    # the pipeline mode under test on the (data x pipe) mesh
    pipe_mesh = comm.make_mesh(
        (dpw, pw), ("data", "pipe"), mesh_devices=jax.devices()[:chips]
    )
    pipe_tr = train.LMTrainer(
        make_lm(), pipe_mesh,
        train.LMTrainConfig(
            global_batch=B, pipeline=args.pipeline,
            pipe_microbatches=M, pipe_interleave=args.pipe_interleave,
            log=log,
        ),
    )
    pipe_batch = parallel.shard_batch((toks,), pipe_mesh)
    step_s, loss = _measure_steps(
        pipe_tr, pipe_batch, args.pipe_steps, args.warmup
    )
    summary = pipe_tr._pipe_summary
    rows[args.pipeline] = {
        "mode": args.pipeline, "chips": chips, "pipe_world": pw,
        "dp_world": dpw, "microbatches": M, "interleave": vi,
        "schedule_kind": summary["kind"],
        "schedule_ticks": summary["ticks"],
        "stash_depth": summary["stash_depth"],
        "step_ms": round(step_s * 1e3, 2),
        "tokens_per_sec": round(B * S / step_s, 1),
        "loss": round(loss, 4),
        "bubble_fraction": summary["bubble_fraction"],
    }
    # the GPipe flush bubble at the SAME (n, M): the number the 1F1B
    # drain is measured against
    gpipe_bubble = round(
        build_schedule(pw, M, 1, "gpipe").bubble_fraction(), 6
    )
    out = {
        "metric": "lm_pipeline_goodput",
        "value": rows[args.pipeline]["tokens_per_sec"],
        "unit": "tokens_per_sec",
        "platform": jax.devices()[0].platform,
        "pipeline": args.pipeline,
        "model": {
            "dim": args.pipe_dim, "depth": depth, "heads": args.pipe_heads,
            "vocab": args.pipe_vocab, "seq": S, "global_batch": B,
        },
        "goodput_vs_dp": round(
            rows[args.pipeline]["tokens_per_sec"]
            / rows["dp"]["tokens_per_sec"], 4,
        ),
        "gpipe_bubble_at_same_nM": gpipe_bubble,
        # null when gpipe IS the mode under test (comparing it to
        # itself would read as a regression)
        "bubble_below_gpipe": (
            rows[args.pipeline]["bubble_fraction"] < gpipe_bubble
            if args.pipeline != "gpipe"
            else None
        ),
        "rows": rows,
    }
    for name, row in rows.items():
        bub = row.get("bubble_fraction")
        log(
            f"[{name}] {row['step_ms']:.1f} ms/step  "
            f"{row['tokens_per_sec']:,.0f} tok/s"
            + (f"  bubble {bub:.1%}" if bub is not None else "")
        )
    if not args.no_persist:
        import bench

        for name, row in rows.items():
            bench.persist_event({
                "metric": "lm_pipeline_goodput",
                "value": row["tokens_per_sec"],
                "unit": "tokens_per_sec",
                "bench": "lm_train_pipeline",
                **row,
            })
    return out


def sweep(args) -> dict:
    """Run the (batch, seq) sweep on the current backend and return the
    result record (the caller prints/embeds it).  Platform selection is
    the script entry's job."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from tpu_dist import comm, models, parallel, train
    from tpu_dist.train import flops as flops_mod
    from tpu_dist.utils.platform import host_sync

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    log(f"backend: {dev.platform} ({dev.device_kind})")
    peak = flops_mod.peak_flops(dev)
    if peak:
        log(f"bf16 peak: {peak / 1e12:.1f} TF/s")

    cases = []
    for tok in args.configs.split(","):
        b, s = tok.lower().split("x")
        cases.append((int(b), int(s)))
    max_seq = max(s for _, s in cases)

    mesh = comm.make_mesh(1, ("data",), mesh_devices=jax.devices()[:1])
    # a failed case (OOM, compile refusal) raises: it fails the run
    results = [
        run_case(args, batch, seq, mesh, max_seq, on_tpu, dev)
        for batch, seq in cases
    ]

    valid = [r for r in results if not r.get("rejected")]
    with_mfu = [r for r in valid if r.get("mfu") is not None]
    # off-TPU there is no public peak, so mfu is None for every row —
    # rank by tokens/s so `best` still carries the sweep winner
    best = (
        max(with_mfu, key=lambda r: r["mfu"])
        if with_mfu
        else max(valid, key=lambda r: r.get("tokens_per_sec") or 0.0)
        if valid
        else None
    )
    out = {
        "metric": "lm_train_mfu",
        # never publish a rejected (>100%) row as the headline
        "value": best["mfu"] if best else None,
        "unit": "mfu_fraction",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "best": best,
        "sweep": results,
    }
    return out


def run_case(args, batch, seq, mesh, max_seq, on_tpu, dev):
    """Measure one (batch, seq) config; returns its result row."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from tpu_dist import models, parallel, train
    from tpu_dist.train import flops as flops_mod
    from tpu_dist.utils.platform import host_sync

    remat = seq >= args.remat_from
    lm = models.TransformerLM(
        vocab=args.vocab, dim=args.dim, depth=args.depth,
        heads=args.heads, max_seq=max_seq, pos_embedding="rope",
        remat=remat,
    )
    cfg = train.LMTrainConfig(
        global_batch=batch, compute_dtype="bfloat16", log=log
    )
    trainer = train.LMTrainer(lm, mesh, cfg)
    n_params = sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(trainer.params)
    )
    model_flops = flops_mod.train_step_flops_estimate(
        lm_model_flops(lm, trainer.params, batch, seq)
    )

    rng = np.random.default_rng(0)
    toks = jnp.asarray(
        rng.integers(0, args.vocab, (batch, seq), dtype=np.int64),
        jnp.int32,
    )
    tbatch = parallel.shard_batch((toks,), mesh)
    key = jax.random.key(0)
    p, ms, os_ = trainer.params, trainer._model_state, trainer.opt_state
    t_c0 = time.perf_counter()
    for _ in range(args.warmup):
        p, ms, os_, loss, _ = trainer.step(p, ms, os_, tbatch, key)
    log(
        f"[{batch}x{seq}] params={n_params / 1e6:.1f}M remat={remat} "
        f"warmup+compile {time.perf_counter() - t_c0:.1f}s "
        f"loss={host_sync(loss):.4f}"
    )
    steps = args.steps if on_tpu else max(2, args.steps // 10)
    t0 = time.perf_counter()
    for _ in range(steps):
        p, ms, os_, loss, _ = trainer.step(p, ms, os_, tbatch, key)
    host_sync(loss)
    dt = time.perf_counter() - t0
    step_s = dt / steps
    tps = batch * seq / step_s
    util = flops_mod.mfu(model_flops, step_s, device=dev)
    xla = flops_mod.xla_flops(trainer.step, p, ms, os_, tbatch, key)
    row = {
        "batch": batch,
        "seq": seq,
        "params_m": round(n_params / 1e6, 1),
        "remat": remat,
        "step_ms": round(step_s * 1e3, 2),
        "tokens_per_sec": round(tps, 0),
        "model_tflops_per_step": round(model_flops / 1e12, 3),
        "achieved_tflops": round(model_flops / step_s / 1e12, 2),
        "xla_tflops_per_step": round(xla / 1e12, 3) if xla else None,
        "mfu": round(util, 4) if util is not None else None,
    }
    if util is not None and util > 1.0:
        log(
            f"[{batch}x{seq}] REJECTED: MFU {util:.2%} > 100% is "
            "physically impossible — timing/accounting broken"
        )
        row["rejected"] = True
    log(
        f"[{batch}x{seq}] {step_s * 1e3:.1f} ms/step, "
        f"{tps:,.0f} tok/s, "
        f"{model_flops / step_s / 1e12:.1f} TF/s"
        + (f", MFU {util:.2%}" if util is not None else "")
    )
    try:
        from tpu_dist.train import metrics as metrics_mod

        stats = metrics_mod.device_memory_stats(dev)
        if stats and stats.get("peak_bytes_in_use"):
            row["hbm_peak_mb"] = round(stats["peak_bytes_in_use"] / 1e6, 1)
    except Exception:
        pass
    return row


if __name__ == "__main__":
    main()
