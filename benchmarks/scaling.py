"""Scaling-efficiency harness — the 1→N-chip target (BASELINE.md: ≥90%
efficiency 1→8 chips on the MNIST DP workload).

Measures fused-train-step throughput at world sizes 1, 2, 4, ..., N with
CONSTANT per-chip batch (weak scaling — the regime where the gradient
allreduce is the only added cost, so efficiency isolates interconnect +
compile quality).  Prints a table plus one JSON line for machines.

Run: ``python benchmarks/scaling.py [--platform cpu] [--batch-per-chip N]``
(CPU simulation exercises the harness; the numbers that matter come from
real chips, where ICI carries the pmean.)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_model(name: str):
    from tpu_dist import models

    if name == "mnist":
        return models.mnist_net(), models.IN_SHAPE
    if name == "resnet18":
        return models.resnet18(num_classes=10), (32, 32, 3)
    if name == "vit":
        # ViT-Ti/16 at ImageNet resolution — BASELINE.json config 5
        return models.vit_tiny(image_size=224, patch=16, num_classes=1000), (
            224, 224, 3,
        )
    if name == "lm":
        # byte-vocab TransformerLM: the long-context family's DP
        # scaling number (tokens/s = samples/s x seq)
        return models.TransformerLM(
            vocab=256, dim=256, depth=4, heads=8, max_seq=512
        ), (512,)
    raise SystemExit(f"unknown --model {name!r}")


def measure(
    world: int,
    batch_per_chip: int,
    steps: int,
    platform: str | None,
    model_name: str = "mnist",
):
    import jax
    import jax.numpy as jnp

    from tpu_dist import comm, models, nn, parallel, train

    mesh = comm.make_mesh(world, ("data",), platform=platform)
    model, in_shape = _build_model(model_name)
    params, state = model.init(jax.random.key(0), in_shape)
    opt = train.sgd(0.01, momentum=0.5)

    # name must not collide with the step-output `loss` below — the
    # closure resolves at trace time in this scope
    loss_metric = nn.nll_loss if model_name == "mnist" else nn.cross_entropy

    if model_name == "lm":
        def loss_fn(p, s, batch, key):
            (tokens,) = batch
            logits, _ = model.apply(p, s, tokens, train=True, key=key)
            return models.lm_loss(logits, tokens), ({}, {})
    else:
        def loss_fn(p, s, batch, key):
            x, y = batch
            scores, s2 = model.apply(p, s, x, train=True, key=key)
            return loss_metric(scores, y), (s2, {})

    step = parallel.make_spmd_train_step(loss_fn, opt, mesh)
    p = parallel.replicate(params, mesh)
    ms = parallel.replicate(state, mesh)
    os_ = parallel.replicate(opt.init(params), mesh)
    global_batch = batch_per_chip * world
    if model_name == "lm":
        batch = parallel.shard_batch(
            (jnp.zeros((global_batch,) + in_shape, jnp.int32),), mesh
        )
    else:
        batch = parallel.shard_batch(
            (
                jnp.zeros((global_batch,) + in_shape, jnp.float32),
                jnp.zeros((global_batch,), jnp.int32),
            ),
            mesh,
        )
    from tpu_dist.utils.platform import host_sync

    key = jax.random.key(1)
    for _ in range(3):
        p, ms, os_, loss, _ = step(p, ms, os_, batch, key)
    host_sync(loss)  # scalar readback: true completion, see host_sync doc
    t0 = time.perf_counter()
    for _ in range(steps):
        p, ms, os_, loss, _ = step(p, ms, os_, batch, key)
    host_sync(loss)
    dt = time.perf_counter() - t0
    sps = steps * global_batch / dt

    # XLA cost analysis reports the PER-DEVICE partitioned program, so
    # per-device flops vs one chip's peak is the per-chip MFU (== world
    # MFU for even SPMD sharding); the world-total TFLOP/s scales by N.
    per_dev_flops = train.flops.xla_flops(step, p, ms, os_, batch, key)
    util = train.flops.mfu(
        per_dev_flops, dt / steps, n_devices=1, device=mesh.devices.flat[0]
    )
    tflops = (
        per_dev_flops * world / (dt / steps) / 1e12 if per_dev_flops else None
    )
    if util is not None and util > 1.0:
        print(
            f"WARNING: {model_name} world={world} MFU {util:.2f} > 1 is "
            "physically impossible — timing or FLOPs accounting is broken; "
            "do not trust this row",
            file=sys.stderr,
        )
    return sps, tflops, util


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--batch-per-chip", type=int, default=64)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--max-world", type=int, default=None)
    ap.add_argument("--model", default="mnist", help="mnist | resnet18 | vit")
    args = ap.parse_args()
    from tpu_dist.utils.platform import select_platform

    select_platform(args.platform, args.max_world or 8)
    import jax

    n_dev = len(jax.devices(args.platform) if args.platform else jax.devices())
    max_world = min(args.max_world or n_dev, n_dev)
    worlds = [w for w in (1, 2, 4, 8, 16, 32) if w <= max_world]

    results = {}
    stats = {}
    for w in worlds:
        sps, tflops, util = measure(w, args.batch_per_chip, args.steps,
                                    args.platform, model_name=args.model)
        results[w] = sps
        stats[w] = (tflops, util)
        print(
            f"world={w:3d}  {sps:12,.0f} samples/s  "
            f"({sps / w:10,.0f} /chip)"
            + (f"  {tflops:8.3f} TFLOP/s" if tflops else "")
            + (f"  MFU {util:6.2%}" if util is not None else ""),
            file=sys.stderr,
        )
    base = results[worlds[0]]
    table = {
        str(w): {
            "samples_per_sec": round(results[w], 1),
            "efficiency": round(results[w] / (base * w / worlds[0]), 4),
            "tflops": round(stats[w][0], 4) if stats[w][0] else None,
            "mfu": round(stats[w][1], 4) if stats[w][1] is not None else None,
        }
        for w in worlds
    }
    eff_last = table[str(worlds[-1])]["efficiency"]
    print(
        f"scaling efficiency {worlds[0]}->{worlds[-1]}: {eff_last:.1%}",
        file=sys.stderr,
    )
    platform = jax.devices()[0].platform
    # VERDICT r4 #9: on a shared-host simulation every "chip" competes
    # for the same cores, so the efficiency column measures host
    # contention, not interconnect — mark the artifact itself untrusted
    # so no round mistakes simulated efficiency for the >=90% target.
    trusted = platform == "tpu"
    if not trusted:
        print(
            f"NOTE: platform={platform} shares one host across all "
            "simulated chips — efficiency numbers are NOT scaling "
            "evidence (trusted=false in the JSON)",
            file=sys.stderr,
        )
    print(json.dumps({"metric": "dp_weak_scaling", "model": args.model,
                      "platform": platform, "trusted": trusted,
                      "worlds": table}))


if __name__ == "__main__":
    main()
