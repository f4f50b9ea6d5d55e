"""Collective-matmul benchmark: overlapped vs blocking sequence-parallel
MLP (parallel/overlap.py vs all_gather -> matmul -> psum_scatter).

The overlapped form decomposes the gather/scatter into ppermute hops the
scheduler can hide behind the chunk matmuls; the blocking form pays the
full collective latency before/after the matmuls.  Needs >=2 devices on
one ICI domain for the comparison to mean anything — on a single chip it
verifies numerics and refuses to print timing rows (world=1 has no
communication to overlap, like demos/allreduce.py --bench).

Run ``python benchmarks/overlap.py`` on hardware, or smoke the harness on
the simulated mesh with ``--platform cpu --dim 64 --hidden 128`` (all 8
"devices" share one CPU: timings are meaningless, math is checked).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--world", type=int, default=None)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seq-per-rank", type=int, nargs="+", default=[512, 2048])
    ap.add_argument("--dim", type=int, default=2048)
    ap.add_argument("--hidden", type=int, default=8192)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()

    from tpu_dist.utils.platform import select_platform

    select_platform(args.platform, 8)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_dist import comm, parallel
    from tpu_dist.parallel.tensor_parallel import shard_dim
    from tpu_dist.utils.platform import host_sync

    devs = jax.devices()
    world = args.world or len(devs)
    world = min(world, len(devs))
    dev = devs[0]
    print(
        f"backend: {dev.platform} ({dev.device_kind}), world={world}",
        file=sys.stderr,
    )
    dtype = jnp.dtype(args.dtype)
    mesh = comm.make_mesh(world, ("model",), mesh_devices=devs[:world])
    axis = "model"

    def mlp_blocking(x, params):
        w1 = shard_dim(params["fc1"]["w"], axis, 1)
        b1 = shard_dim(params["fc1"]["b"], axis, 0)
        w2 = shard_dim(params["fc2"]["w"], axis, 0)
        xg = lax.all_gather(x, axis, axis=0, tiled=True)
        h = jax.nn.gelu(xg @ w1 + b1)
        out = lax.psum_scatter(h @ w2, axis, scatter_dimension=0, tiled=True)
        return out + params["fc2"]["b"]

    def mlp_overlapped(x, params):
        return parallel.tp_mlp_overlapped(x, params, axis)

    def mlp_overlapped_bidir(x, params):
        # same layout with both ring directions carrying half-chunks
        w1 = shard_dim(params["fc1"]["w"], axis, 1)
        b1 = shard_dim(params["fc1"]["b"], axis, 0)
        w2 = shard_dim(params["fc2"]["w"], axis, 0)
        x2d = x.reshape(-1, x.shape[-1])
        hdn = jax.nn.gelu(
            parallel.allgather_matmul(x2d, w1, axis, bidirectional=True) + b1
        )
        out = parallel.matmul_reduce_scatter(
            hdn, w2, axis, bidirectional=True
        )
        return (out + params["fc2"]["b"]).reshape(x.shape[:-1] + (-1,))

    def build(fn):
        return jax.jit(
            jax.shard_map(
                fn,
                mesh=mesh,
                in_specs=(P(axis), P()),
                out_specs=P(axis),
                check_vma=False,
            )
        )

    results = {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "world": world,
        "dim": args.dim,
        "hidden": args.hidden,
        "rows": [],
    }

    key = jax.random.key(0)
    k1, k2, k3 = jax.random.split(key, 3)
    params = {
        "fc1": {
            "w": (jax.random.normal(k1, (args.dim, args.hidden)) * 0.02).astype(dtype),
            "b": jnp.zeros((args.hidden,), dtype),
        },
        "fc2": {
            "w": (jax.random.normal(k2, (args.hidden, args.dim)) * 0.02).astype(dtype),
            "b": jnp.zeros((args.dim,), dtype),
        },
    }
    p_repl = jax.device_put(params, NamedSharding(mesh, P()))

    # numerics first: both formulations must agree (and, on small shapes,
    # match the dense MLP) before any timing row is believable
    xs = jax.device_put(
        (jax.random.normal(k3, (world * 8, args.dim)) * 0.1).astype(dtype),
        NamedSharding(mesh, P(axis)),
    )
    blocking, overlapped = build(mlp_blocking), build(mlp_overlapped)
    overlapped_bidir = build(mlp_overlapped_bidir)
    a, b = np.asarray(blocking(xs, p_repl)), np.asarray(overlapped(xs, p_repl))
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    if not np.allclose(a.astype(np.float32), b.astype(np.float32), rtol=tol, atol=tol):
        raise AssertionError(
            f"overlapped != blocking (max delta "
            f"{np.abs(a.astype(np.float32) - b.astype(np.float32)).max():.3e})"
        )
    print("numerics: overlapped == blocking", file=sys.stderr)

    if world < 2:
        print(
            "world=1: nothing to overlap — refusing to print timing rows "
            "(run with >=2 devices on one ICI domain)",
            file=sys.stderr,
        )
        print(json.dumps({**results, "note": "world=1, timing refused"}))
        return

    for s_l in args.seq_per_rank:
        x0 = jax.device_put(
            (jax.random.normal(k3, (world * s_l, args.dim)) * 0.1).astype(dtype),
            NamedSharding(mesh, P(axis)),
        )
        # per-chip flops: full MLP is 4*S*d*h over n chips
        flops = 4 * s_l * args.dim * args.hidden
        row = {"seq_per_rank": s_l}
        for name, fn in (
            ("blocking", blocking),
            ("overlapped", overlapped),
            ("overlapped_bidir", overlapped_bidir),
        ):
            # chained shape-preserving steps closed by a host readback
            # (bench_chain methodology; see utils/timing.py)
            @jax.jit
            def chain(x, _fn=fn):
                return lax.fori_loop(
                    0, args.iters, lambda i, y: _fn(y, p_repl) * 0.5 + y * 0.5, x
                )

            host_sync(chain(x0))  # compile + warm
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                host_sync(chain(x0))
                best = min(best, time.perf_counter() - t0)
            per_step = best / args.iters
            row[name + "_ms"] = round(per_step * 1e3, 4)
            row[name + "_tflops"] = round(flops / per_step / 1e12, 2)
        row["speedup"] = round(row["blocking_ms"] / row["overlapped_ms"], 3)
        row["speedup_bidir"] = round(
            row["blocking_ms"] / row["overlapped_bidir_ms"], 3
        )
        results["rows"].append(row)
        print(
            f"s/rank={s_l:6d}: blocking {row['blocking_ms']:9.3f} ms "
            f"({row['blocking_tflops']:6.2f} TF/s/chip)  overlapped "
            f"{row['overlapped_ms']:9.3f} ms ({row['overlapped_tflops']:6.2f} "
            f"TF/s/chip, x{row['speedup']})  bidir "
            f"{row['overlapped_bidir_ms']:9.3f} ms (x{row['speedup_bidir']})",
            file=sys.stderr,
        )

    print(json.dumps(results))


if __name__ == "__main__":
    main()
