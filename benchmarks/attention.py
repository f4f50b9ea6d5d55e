"""Long-context attention benchmark: ring vs Ulysses vs full.

Sweeps global sequence length on an N-way sequence-parallel mesh and
times the three strategies (full attention runs unsharded as the
reference point and memory ceiling — it materializes the (S, S) score
matrix; the sharded paths never do).  Prints a table + one JSON line.

Run: ``python benchmarks/attention.py [--platform cpu] [--world 8]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from tpu_dist.utils.timing import bench_chain  # chained in-program timing


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--world", type=int, default=8)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seqs", type=int, nargs="+", default=[1024, 4096, 8192])
    ap.add_argument("--causal", action="store_true")
    ap.add_argument(
        "--window", type=int, default=None,
        help="sliding-window band width: adds flash_window (O(S·w) work "
             "— the local-attention win) and ring_window (window applied "
             "as a mask; every K/V block still rotates, so O(S²/n) "
             "compute+comm per rank) rows",
    )
    args = ap.parse_args()
    if args.window is not None and args.window < 1:
        raise SystemExit(f"--window must be >= 1, got {args.window}")
    from tpu_dist.utils.platform import select_platform

    select_platform(args.platform, args.world)
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_dist import comm, parallel
    from tpu_dist.nn.attention import dense_attention

    mesh = comm.make_mesh(args.world, ("seq",), platform=args.platform)
    shard = NamedSharding(mesh, P(None, None, "seq", None))
    results = {}
    for S in args.seqs:
        if S % args.world:
            print(f"skip S={S} (not divisible by world)", file=sys.stderr)
            continue
        shape = (args.batch, args.heads, S, args.dim)
        q = jax.device_put(
            jax.random.normal(jax.random.key(0), shape, jnp.bfloat16), shard
        )

        def sharded(fn_name):
            interp = args.platform == "cpu"  # Pallas needs interpret off-TPU
            fn = {
                "ring": parallel.ring_attention,
                "ulysses": parallel.ulysses_attention,
                "ring_flash": lambda a, b, c, ax, causal: (
                    parallel.ring_attention_flash(
                        a, b, c, ax, causal=causal, interpret=interp
                    )
                ),
                "ring_window": lambda a, b, c, ax, causal: (
                    parallel.ring_attention(
                        a, b, c, ax, causal=causal, window=args.window
                    )
                ),
            }[fn_name]
            mapped = jax.jit(
                jax.shard_map(
                    lambda a, b, c: fn(a, b, c, "seq", causal=args.causal),
                    mesh=mesh,
                    in_specs=(P(None, None, "seq"),) * 3,
                    out_specs=P(None, None, "seq"),
                    check_vma=False,
                )
            )
            return lambda y: mapped(y, y, y)

        cases = [
            ("full", lambda y: dense_attention(y, y, y, causal=args.causal)),
            ("ring", sharded("ring")),
            ("ring_flash", sharded("ring_flash")),
            ("ulysses", sharded("ulysses")),
        ]
        if args.window is not None:
            from tpu_dist.ops.flash_attention import flash_attention

            interp = args.platform == "cpu"
            w = args.window
            cases.append((
                "flash_window",
                lambda y: flash_attention(
                    y, y, y, causal=args.causal, window=w, interpret=interp
                ),
            ))
            cases.append(("ring_window", sharded("ring_window")))
        row = {}
        for name, step in cases:
            try:
                # self-attention is shape-preserving: chain out -> q
                row[name] = bench_chain(step, q, iters=5) * 1e3
            except Exception as e:  # OOM for full at long S is expected
                row[name] = None
                print(f"S={S} {name}: {type(e).__name__}", file=sys.stderr)
        results[S] = row
        cells = "  ".join(
            f"{k}={v:8.2f}ms" if v is not None else f"{k}=     OOM"
            for k, v in row.items()
        )
        print(f"S={S:6d}  {cells}", file=sys.stderr)
    print(json.dumps({"metric": "attention_ms", "world": args.world,
                      "causal": args.causal, "window": args.window,
                      "results": results}))


if __name__ == "__main__":
    main()
