"""Gradient-reduction backend benchmark: exact vs per-leaf quantized vs
the bucketed compressed-sync engine.

Times the full fused ResNet-18 train step (the BASELINE 'larger grads
over ICI' workload — ~45 MB of gradients) under each gradient-sync
backend, reporting ms/step, bytes-on-wire per rank, and effective wire
GB/s (bytes-on-wire / step time — on real chips this isolates how the
collective implementation affects step time; on CPU-sim it is a
regression guard for the collective STRUCTURE, not a bandwidth claim).

Backends:

- ``psum``   — exact XLA AllReduce (production default)
- ``ring``   — the hand-rolled chunked ppermute ring (exact)
- ``int8``   — per-leaf quantized allreduce (`comm.all_reduce_quantized`,
  one collective per parameter tensor — the pre-bucketing toy)
- ``bucket_int8`` / ``bucket_fp8`` / ``bucket_bf16`` — the bucketed
  error-feedback wire inside the partition engine's GSPMD step
  (`make_partitioned_train_step(compress=...)`, one collective pair per
  ~bucket)

``--bucket-sweep`` additionally sweeps the bucketed int8 backend over
1 / 4 / 16 MB buckets.  Every run appends a structured record (with
platform provenance) to ``benchmarks/results/bench_runs.jsonl`` like
``bench.py`` does — numbers survive the terminal scrollback.

Run: ``python benchmarks/grad_reduce.py [--platform cpu] [--world 8]``
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
    ap.add_argument("--world", type=int, default=8)
    ap.add_argument("--batch-per-chip", type=int, default=16)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument(
        "--model", default="resnet18", choices=("resnet18", "mnist"),
        help="gradient payload: resnet18 (~45 MB) or mnist (tiny smoke)",
    )
    ap.add_argument(
        "--bucket-sweep", action="store_true",
        help="also sweep bucketed int8 over 1/4/16 MB buckets",
    )
    args = ap.parse_args()
    from tpu_dist.utils.platform import select_platform

    select_platform(args.platform, args.world)
    import jax
    import jax.numpy as jnp

    import bench
    from tpu_dist import comm, models, nn, parallel, train
    from tpu_dist.comm import compress as compress_mod
    from tpu_dist.utils import tree_bytes

    mesh = comm.make_mesh(args.world, ("data",), platform=args.platform)
    n = args.world
    if args.model == "resnet18":
        model = models.resnet18(num_classes=10)
        in_shape = (32, 32, 3)
    else:
        model = models.mnist_net()
        in_shape = models.IN_SHAPE
    params, state = model.init(jax.random.key(0), in_shape)
    opt = train.sgd(0.1, momentum=0.9)
    gbytes = tree_bytes(params)
    print(f"gradient payload: {gbytes/1e6:.1f} MB over {args.world} ranks",
          file=sys.stderr)

    def loss_fn(p, s, batch, key):
        x, y = batch
        scores, s2 = model.apply(p, s, x, train=True, key=key)
        return nn.cross_entropy(scores, y), (s2, {})

    gb = args.batch_per_chip * args.world
    batch_host = (
        jnp.zeros((gb,) + in_shape, jnp.float32),
        jnp.zeros((gb,), jnp.int32),
    )

    def exact_wire_bytes() -> int:
        # ring lower bound for the uncompressed allreduce
        return int(2 * (n - 1) / n * gbytes)

    # The compressed backends ride the partition engine's GSPMD step
    # (the only compressed wire since the legacy builders retired); the
    # engine is stateless, so its loss runs BN in inference mode — the
    # gradient payload (what this bench times) is unchanged.
    rules = parallel.resolve_rules(f"dp={n}", mesh, bind={"dp": "data"})

    def engine_loss(p, batch, key):
        x, y = batch
        scores, _ = model.apply(p, state, x, train=False)
        return nn.cross_entropy(scores, y), {}

    def bench_backend(name: str, *, grad_reduce="psum", grad_compress=None):
        ccfg = compress_mod.parse(grad_compress)
        if ccfg is not None:
            built = parallel.make_partitioned_train_step(
                engine_loss, opt, mesh, params, rules, donate=False,
                compress=ccfg,
            )
            p, o, s = built.params, built.opt_state, None

            def step(p, s, o, batch, key):
                p2, o2, loss, aux = built.step(p, o, batch, key)
                return p2, s, o2, loss, aux

            wire = built.flat_plan.bytes_on_wire("all_reduce")
            buckets = built.flat_plan.n_buckets
        else:
            step = parallel.make_spmd_train_step(
                loss_fn, opt, mesh, donate=False, grad_reduce=grad_reduce,
            )
            p = parallel.replicate(params, mesh)
            s = parallel.replicate(state, mesh)
            o = parallel.replicate(opt.init(params), mesh)
            wire = exact_wire_bytes()
            if grad_reduce in ("int8", "fp8"):  # per-leaf 1-byte payload
                wire = exact_wire_bytes() // 4
            buckets = None
        batch = parallel.shard_batch(batch_host, mesh)
        key = jax.random.key(1)
        p, s, o, loss, _ = step(p, s, o, batch, key)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            p, s, o, loss, _ = step(p, s, o, batch, key)
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / args.steps
        rec = {
            "ms_per_step": round(dt * 1e3, 2),
            "bytes_on_wire": wire,
            "wire_gbps": round(wire / dt / 1e9, 3),
        }
        if buckets is not None:
            rec["buckets"] = buckets
        print(
            f"{name:12s}: {dt*1e3:8.1f} ms/step  "
            f"{wire/1e6:7.2f} MB wire  {rec['wire_gbps']:7.3f} GB/s"
            + (f"  ({buckets} buckets)" if buckets else ""),
            file=sys.stderr,
        )
        return rec

    results = {}
    for name, kw in (
        ("psum", dict()),
        ("ring", dict(grad_reduce="ring")),
        ("int8", dict(grad_reduce="int8")),
        ("bucket_int8", dict(grad_compress="int8")),
        ("bucket_fp8", dict(grad_compress="fp8")),
        ("bucket_bf16", dict(grad_compress="bf16")),
    ):
        results[name] = bench_backend(name, **kw)
    if args.bucket_sweep:
        for mb in (1, 4, 16):
            results[f"bucket_int8_{mb}mb"] = bench_backend(
                f"int8 {mb:2d}MB", grad_compress=f"int8,bucket_mb={mb}"
            )

    record = {
        "event": "bench",
        "metric": f"{args.model}_step_by_grad_sync",
        # headline value (schema requires one): bucketed-int8 ms/step
        "value": results["bucket_int8"]["ms_per_step"],
        "unit": "ms/step",
        "world": args.world,
        "grad_mb": round(gbytes / 1e6, 1),
        "bytes_exact_wire": exact_wire_bytes(),
        "results": results,
    }
    print(json.dumps(record))
    try:
        bench.persist_event(record)
    except Exception as e:  # a bench must still print if the disk is odd
        print(f"could not persist bench record: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
