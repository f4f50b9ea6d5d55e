"""Autoregressive decode throughput: tokens/s through the KV-cache path.

Measures `TransformerLM.generate` (prefill + scanned single-token steps)
at a few batch sizes, reporting decode tokens/s and ms/token — the
serving-side counterpart of the training benches.  Decode is memory-bound
(every step re-reads the KV cache + weights), so this is the HBM
bandwidth probe among the benchmarks.

Run: ``python benchmarks/decode.py [--platform cpu]``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--steps", type=int, default=128)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--batches", type=int, nargs="+", default=[1, 8, 32])
    ap.add_argument(
        "--mode", default="dense", choices=["dense", "tp", "cp"],
        help="dense = single-program decode; tp = sharded-heads decode "
        "(generate_tensor_parallel); cp = context-parallel decode "
        "(generate_seq_parallel, prompt KV sequence-sharded).  tp/cp "
        "need >=2 devices on one ICI domain to mean anything.",
    )
    args = ap.parse_args()
    n_sim = 8 if args.mode != "dense" else None  # sharded smoke needs a mesh
    from tpu_dist.utils.platform import select_platform

    select_platform(args.platform, n_sim)

    import jax

    from tpu_dist import models

    import numpy as np

    from tpu_dist.train.flops import hbm_bandwidth

    dev = jax.devices()[0]
    print(f"backend: {dev.platform} ({dev.device_kind})", file=sys.stderr)
    lm = models.TransformerLM(
        vocab=args.vocab, dim=args.dim, depth=args.depth,
        heads=args.heads, max_seq=args.max_seq,
    )
    params, _ = lm.init(jax.random.key(0))
    param_bytes = sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(params)
    )
    bw = hbm_bandwidth(dev)
    rows = []
    for b in args.batches:
        prompt = jax.random.randint(
            jax.random.key(1), (b, args.prompt), 0, args.vocab
        )
        from tpu_dist.utils.platform import host_sync

        if args.mode == "dense":
            gen = jax.jit(functools.partial(lm.generate, steps=args.steps))
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from tpu_dist import comm

            world = len(jax.devices())
            axis = "model" if args.mode == "tp" else "seq"
            mesh = comm.make_mesh(world, (axis,))
            if args.mode == "cp" and args.prompt % world:
                raise SystemExit(
                    f"--mode cp needs prompt {args.prompt} divisible by "
                    f"world {world}"
                )
            body = (
                (lambda p, t: lm.generate_tensor_parallel(
                    p, t, args.steps, axis))
                if args.mode == "tp"
                else (lambda p, t: lm.generate_seq_parallel(
                    p, t, args.steps, axis))
            )
            prompt_spec = P() if args.mode == "tp" else P(None, axis)
            mapped = jax.shard_map(
                body, mesh=mesh, in_specs=(P(), prompt_spec),
                out_specs=P(), check_vma=False,
            )

            jitted = jax.jit(mapped)  # one wrapper: warm fastpath in the
            # timed loop (a fresh jax.jit per call pays cold python
            # dispatch inside the measured region)

            def gen(params, prm, _j=jitted, _mesh=mesh, _ps=prompt_spec):
                return _j(
                    jax.device_put(params, NamedSharding(_mesh, P())),
                    jax.device_put(prm, NamedSharding(_mesh, _ps)),
                )
        host_sync(gen(params, prompt))  # compile + warm (true completion)
        dt = float("inf")
        for r in range(1, 4):  # distinct prompts: no run can be a cache hit
            prm = (prompt + r) % args.vocab
            t0 = time.perf_counter()
            out = gen(params, prm)
            host_sync(out)  # element readback: see host_sync doc
            dt = min(dt, time.perf_counter() - t0)
        toks = b * args.steps
        row = {
            "batch": b,
            "tokens_per_sec": round(toks / dt, 1),
            "ms_per_token_step": round(dt / args.steps * 1e3, 3),
        }
        if bw is not None:
            # HBM roofline (mirror of the MFU>100% guard): every decode
            # step must at minimum re-read the weights plus this batch's
            # live KV cache, so tok/s cannot exceed b · BW / bytes_step.
            # KV bytes use the MEAN live cache length over the run (the
            # cache fills as it decodes) — a lower bound on traffic,
            # hence an upper bound on credible tok/s.
            cache = lm.init_cache(b, args.max_seq)
            kv_full = sum(
                a.size * a.dtype.itemsize for a in jax.tree.leaves(cache)
            )
            mean_len = args.prompt + args.steps / 2
            kv_bytes = kv_full * mean_len / args.max_seq
            bytes_step = param_bytes + kv_bytes
            ceiling = b * bw / bytes_step
            row["roofline_tokens_per_sec"] = round(ceiling, 1)
            if row["tokens_per_sec"] > ceiling:
                row["suspect"] = True
                print(
                    f"batch {b}: REJECTED {toks / dt:,.0f} tok/s exceeds "
                    f"the HBM roofline {ceiling:,.0f} (bytes/step "
                    f"{bytes_step / 1e6:.1f} MB @ {bw / 1e9:.0f} GB/s) — "
                    "timing untrustworthy",
                    file=sys.stderr,
                )
        rows.append(row)
        print(
            f"batch {b:4d}: {toks / dt:10,.0f} tok/s  "
            f"({dt / args.steps * 1e3:.2f} ms/step)"
            + (
                f"  [roofline {row['roofline_tokens_per_sec']:,.0f}]"
                if "roofline_tokens_per_sec" in row
                else ""
            ),
            file=sys.stderr,
        )
    record = {
        "metric": "lm_decode_tokens_per_sec",
        "mode": args.mode,
        "platform": dev.platform,
        "model": f"dim{args.dim}xL{args.depth}h{args.heads}",
        "prompt": args.prompt, "steps": args.steps,
        "rows": rows,
    }
    import bench

    # durable trace, parity with grad_reduce.py / lm_train.py
    bench.persist_event({"bench": "decode", **record})
    print(json.dumps(record))


if __name__ == "__main__":
    main()
