"""Serving under Poisson load: continuous vs static batching.

Replays one seeded trace of requests — Poisson arrivals, mixed prompt
lengths, bimodal output lengths (mostly short, a long tail) — through
two schedulers at equal chips:

- **continuous**: `tpu_dist.serve.ServeEngine` — paged KV pool,
  admit/evict every step, chunked prefill interleaved with decode;
  runs ``--slots`` decode slots over a pool holding EXACTLY the KV
  bytes the static server's ``max_batch`` full-length caches occupy
  (equal chips, equal KV memory — the paged pool turns the same bytes
  into more in-flight requests because most requests are short, which
  is PagedAttention's actual claim);
- **static**: the classic fixed-batch server — requests grouped in
  arrival order into `max_batch`-sized batches, each batch decoded by
  `TransformerLM.generate` for its own maximum output length rounded
  up to a power-of-two bucket (each bucket precompiled outside the
  clock; prompts right-padded), next batch starts when the previous
  finishes AND all its members have arrived.  Length-bucketing makes
  this a STRONGER baseline than the fixed-max-length static server:
  the measured gap is the admit/evict-per-step gap, not a strawman's.

Reported per mode: useful tokens/s (only each request's requested
output counts), TTFT p50/p99, and p50/p99 NORMALIZED per-token latency
— ``(finish - arrival) / output_tokens`` per request, the
vLLM-methodology number that charges batch-formation waits and padded
decode steps to the tokens they delay.  Static batching delivers a
request's tokens at batch completion (a `lax.scan` has no per-token
observability), which the metric reflects.

Run: ``python benchmarks/serve.py [--platform cpu]`` / ``make
bench-serve``.  Results persist to benchmarks/results/bench_runs.jsonl
via `bench.persist_event`.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_trace(args):
    import numpy as np

    rng = np.random.default_rng(args.seed)
    n = args.requests
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, size=n))
    prompt_lens = rng.integers(args.prompt_min, args.prompt_max + 1, size=n)
    long = rng.random(n) < args.long_frac
    out_lens = np.where(
        long,
        rng.integers(args.long_lo, args.long_hi + 1, size=n),
        rng.integers(args.short_lo, args.short_hi + 1, size=n),
    )
    prompts = rng.integers(0, args.vocab, size=(n, args.prompt_max))
    return arrivals, prompt_lens, out_lens, prompts.astype(np.int32)


def percentiles(xs):
    import numpy as np

    xs = np.asarray(xs, float)
    return round(float(np.percentile(xs, 50)), 5), round(
        float(np.percentile(xs, 99)), 5
    )


def run_continuous(lm, params, args, trace):
    import numpy as np

    from tpu_dist import serve

    arrivals, prompt_lens, out_lens, prompts = trace
    n = args.requests
    ctx = args.prompt_max + args.long_hi
    num_blocks = args.num_blocks
    if num_blocks is None:
        # equal-KV-memory contract: the pool holds exactly as many
        # token positions as the static server's max_batch full caches
        num_blocks = args.max_batch * (
            -(-ctx // args.block_size)
        )
    cfg = serve.ServeConfig(
        max_batch=args.slots,
        block_size=args.block_size,
        num_blocks=num_blocks,
        max_seq=ctx,
        prefill_chunk=args.prefill_chunk,
        prefill_batch=args.prefill_batch,
    )
    eng = serve.ServeEngine(lm, params, cfg, now=time.perf_counter)
    eng.warmup()
    rid2idx = {}
    t0 = time.perf_counter()
    i = 0
    while i < n or eng.pending:
        now = time.perf_counter() - t0
        while i < n and arrivals[i] <= now:
            rid = eng.submit(prompts[i, : prompt_lens[i]], int(out_lens[i]))
            rid2idx[rid] = i
            i += 1
        if eng.pending:
            eng.step()
        elif i < n:
            time.sleep(min(arrivals[i] - now, 0.01))
    elapsed = time.perf_counter() - t0

    ttfts, norm = [], []
    useful = 0
    for rid, res in eng.results.items():
        idx = rid2idx[rid]
        arr = arrivals[idx]
        useful += res.emitted
        ttfts.append((res.first_token_time - t0) - arr)
        norm.append(((res.finish_time - t0) - arr) / res.emitted)
    t50, t99 = percentiles(ttfts)
    l50, l99 = percentiles(norm)
    return {
        "mode": "continuous",
        "tokens_per_sec": round(useful / elapsed, 1),
        "useful_tokens": int(useful),
        "wall_s": round(elapsed, 3),
        "ttft_p50": t50,
        "ttft_p99": t99,
        "latency_per_token_p50": l50,
        "latency_per_token_p99": l99,
        "engine_steps": eng.step_count,
        "kv_block_high_water": eng.allocator.high_water,
    }


def run_static(lm, params, args, trace):
    import numpy as np

    import jax

    from tpu_dist.utils.platform import host_sync

    arrivals, prompt_lens, out_lens, prompts = trace
    n, B = args.requests, args.max_batch
    ctx = args.prompt_max + args.long_hi
    # per-batch decode budget = max requested output in the batch,
    # rounded up to a multiple-of-`bucket_quantum` bucket (compiled
    # once each, warm) — finer than power-of-two so the static server
    # is not handicapped by bucket granularity
    q = args.bucket_quantum

    def bucket(steps):
        # quantum-rounded, capped at the trace's max output (the cache
        # budget only covers prompt_max + long_hi)
        return min(((steps + q - 1) // q) * q, args.long_hi)

    gens = {}

    def gen_for(steps):
        if steps not in gens:
            gens[steps] = jax.jit(
                functools.partial(lm.generate, steps=steps, cache_len=ctx)
            )
        return gens[steps]

    warm = jax.numpy.asarray(prompts[:B])
    distinct = {
        bucket(int(out_lens[b0 : b0 + B].max())) for b0 in range(0, n, B)
    }
    for s in sorted(distinct):
        host_sync(gen_for(s)(params, warm))  # compile outside the clock

    finish = np.zeros(n)
    decode_steps = 0
    t0 = time.perf_counter()
    for b0 in range(0, n, B):
        idxs = list(range(b0, min(b0 + B, n)))
        batch = np.zeros((B, args.prompt_max), np.int32)
        batch[: len(idxs)] = prompts[idxs]
        steps = bucket(int(out_lens[idxs].max()))
        decode_steps += steps
        ready = arrivals[idxs[-1]]
        while (now := time.perf_counter() - t0) < ready:
            time.sleep(min(ready - now, 0.01))
        host_sync(gen_for(steps)(params, jax.numpy.asarray(batch)))
        t_end = time.perf_counter() - t0
        for i in idxs:
            finish[i] = t_end
    elapsed = time.perf_counter() - t0

    useful = int(out_lens.sum())
    ttfts = finish - arrivals  # tokens delivered at batch completion
    norm = ttfts / out_lens
    t50, t99 = percentiles(ttfts)
    l50, l99 = percentiles(norm)
    return {
        "mode": "static",
        "tokens_per_sec": round(useful / elapsed, 1),
        "useful_tokens": useful,
        "wall_s": round(elapsed, 3),
        "ttft_p50": t50,
        "ttft_p99": t99,
        "latency_per_token_p50": l50,
        "latency_per_token_p99": l99,
        "decode_steps": decode_steps,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--requests", type=int, default=128)
    ap.add_argument("--rate", type=float, default=800.0,
                    help="Poisson arrival rate (req/s); keep it above "
                    "service capacity so the comparison measures the "
                    "schedulers, not the arrival process")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt-min", type=int, default=4)
    ap.add_argument("--prompt-max", type=int, default=12)
    ap.add_argument("--short-lo", type=int, default=2)
    ap.add_argument("--short-hi", type=int, default=4)
    ap.add_argument("--long-lo", type=int, default=56)
    ap.add_argument("--long-hi", type=int, default=64)
    ap.add_argument("--long-frac", type=float, default=0.15)
    ap.add_argument("--max-batch", type=int, default=8,
                    help="static server's batch size; also fixes the "
                    "shared KV memory budget (max_batch full caches)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="replays per mode; best run reported")
    ap.add_argument("--slots", type=int, default=12,
                    help="continuous engine's decode slots (sharing "
                    "the SAME KV byte budget through the paged pool)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="override the equal-memory pool size")
    ap.add_argument("--prefill-chunk", type=int, default=12)
    ap.add_argument("--prefill-batch", type=int, default=8)
    ap.add_argument("--bucket-quantum", type=int, default=16,
                    help="static mode's decode budget rounds up to a "
                    "multiple of this (each bucket precompiled)")
    ap.add_argument("--modes", nargs="+",
                    default=["continuous", "static"],
                    choices=["continuous", "static"])
    args = ap.parse_args()
    from tpu_dist.utils.platform import select_platform

    select_platform(args.platform)

    import jax

    import bench
    from tpu_dist import models

    dev = jax.devices()[0]
    print(f"backend: {dev.platform} ({dev.device_kind})", file=sys.stderr)
    max_seq = args.prompt_max + args.long_hi
    lm = models.TransformerLM(
        vocab=args.vocab, dim=args.dim, depth=args.depth,
        heads=args.heads, max_seq=max_seq,
    )
    params, _ = lm.init(jax.random.key(0))
    trace = build_trace(args)
    print(
        f"trace: {args.requests} requests over "
        f"{trace[0][-1]:.2f}s, prompts {args.prompt_min}-{args.prompt_max}, "
        f"outputs {args.short_lo}-{args.short_hi} "
        f"({1 - args.long_frac:.0%}) / {args.long_lo}-{args.long_hi} "
        f"({args.long_frac:.0%}), {int(trace[2].sum())} useful tokens",
        file=sys.stderr,
    )

    rows = []
    for mode in args.modes:
        run = run_continuous if mode == "continuous" else run_static
        # best-of-N replays of the SAME trace: host noise (CI
        # contention) hits both modes, and min-wall is the standard
        # noise rejection (same as decode.py's min-of-3)
        best = None
        for _ in range(args.repeats):
            row = run(lm, params, args, trace)
            if best is None or row["tokens_per_sec"] > best["tokens_per_sec"]:
                best = row
        rows.append(best)
        row = best
        print(
            f"{mode:>11}: {row['tokens_per_sec']:8,.1f} tok/s  "
            f"ttft p50/p99 {row['ttft_p50']:.3f}/{row['ttft_p99']:.3f}s  "
            f"latency/token p50/p99 {row['latency_per_token_p50'] * 1e3:.1f}"
            f"/{row['latency_per_token_p99'] * 1e3:.1f} ms",
            file=sys.stderr,
        )

    record = {
        "metric": "serve_tokens_per_sec",
        "platform": dev.platform,
        "model": f"dim{args.dim}xL{args.depth}h{args.heads}",
        "requests": args.requests,
        "rate": args.rate,
        "seed": args.seed,
        "max_batch": args.max_batch,
        "block_size": args.block_size,
        "rows": rows,
    }
    by_mode = {r["mode"]: r for r in rows}
    if "continuous" in by_mode and "static" in by_mode:
        c, s = by_mode["continuous"], by_mode["static"]
        record["speedup"] = round(
            c["tokens_per_sec"] / s["tokens_per_sec"], 2
        )
        record["latency_ok"] = bool(
            c["latency_per_token_p99"] <= s["latency_per_token_p99"]
        )
        print(
            f"continuous vs static: {record['speedup']}x tokens/s, p99 "
            f"latency/token "
            f"{'better' if record['latency_ok'] else 'WORSE'}",
            file=sys.stderr,
        )
    bench.persist_event({"bench": "serve", **record})
    print(json.dumps(record))


if __name__ == "__main__":
    main()
