"""Headline benchmark: MNIST data-parallel train-step throughput per chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "samples/sec/chip", "vs_baseline": R}

The workload is the reference's north-star config (BASELINE.json config 3 /
train_dist.py): the LeNet-style ConvNet, global batch 128, SGD(0.01, 0.5),
full fused train step (forward + NLL + backward + gradient allreduce +
update).  ``vs_baseline`` compares against the reference implementation's
stack measured in-container: the same model/step in torch (CPU — the
reference's Gloo-on-CPU dev path, train_dist.py:130), since the reference
publishes no numbers (BASELINE.md).

All progress chatter goes to stderr; stdout carries exactly the one JSON
line the driver records.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def results_root() -> str:
    import os

    return os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks", "results"
    )


def persist_event(record: dict, *, root: str | None = None,
                  out_name: str = "bench_runs.jsonl") -> str:
    """Append one structured record to ``benchmarks/results/<out_name>``
    with timestamp, run id, and platform provenance attached — every
    bench invocation leaves a durable, machine-parseable trace.
    Returns the file path."""
    import json as _json
    import os
    import time as _time

    from tpu_dist.observe import events as ev_mod

    root = root or results_root()
    os.makedirs(root, exist_ok=True)
    path = os.path.join(root, out_name)
    rec = {
        "time": _time.time(),
        "run_id": os.environ.get(ev_mod.ENV_RUN_ID),
        **record,
        "provenance": ev_mod.platform_provenance(),
    }
    with open(path, "a", encoding="utf-8") as fh:
        fh.write(_json.dumps(rec, default=str) + "\n")
    return path


BATCH = 128
TIMED_STEPS = 60
WARMUP = 5


def bench_tpu_dist() -> tuple[float, dict]:
    import jax
    import jax.numpy as jnp

    from tpu_dist import comm, data, models, parallel, train
    from tpu_dist.train import flops as flops_mod

    devs = jax.devices()
    log(f"devices: {devs}")
    mesh = comm.make_mesh(1, ("data",), mesh_devices=devs[:1])

    model = models.mnist_net()
    cfg = train.TrainConfig()
    trainer = train.Trainer(model, models.IN_SHAPE, mesh, cfg)

    ds = data.load_mnist("train", synthetic_size=BATCH * 4)
    x = np.stack([ds[i][0] for i in range(BATCH)])
    y = np.asarray([ds[i][1] for i in range(BATCH)], np.int32)
    batch = parallel.shard_batch((jnp.asarray(x), jnp.asarray(y)), mesh)

    import jax.random as jrandom

    key = jrandom.key(0)
    from tpu_dist.utils.platform import host_sync

    p, ms, os_ = trainer.params, trainer.model_state, trainer.opt_state
    for i in range(WARMUP):
        p, ms, os_, loss, _ = trainer.step(p, ms, os_, batch, key)
    # host readback seals the warmup boundary (see host_sync doc)
    log(f"warmup done, loss={host_sync(loss):.4f}")

    t0 = time.perf_counter()
    for i in range(TIMED_STEPS):
        p, ms, os_, loss, _ = trainer.step(p, ms, os_, batch, key)
    host_sync(loss)  # scalar readback: true completion, see host_sync doc
    dt = time.perf_counter() - t0
    sps = TIMED_STEPS * BATCH / dt
    log(f"tpu_dist: {TIMED_STEPS} steps in {dt:.3f}s -> {sps:,.0f} samples/s/chip")

    # MFU: XLA-measured FLOPs of the whole compiled step (fwd+bwd+update)
    # against the chip's public bf16 peak (None on CPU; an unknown TPU
    # kind raises in flops.peak_flops).
    step_flops = flops_mod.xla_flops(trainer.step, p, ms, os_, batch, key)
    flops_source = "xla"
    if not step_flops:  # cost analysis unavailable on this backend
        step_flops = flops_mod.train_step_flops_estimate(
            flops_mod.mnist_net_forward_flops(BATCH)
        )
        flops_source = "estimate"
    step_s = dt / TIMED_STEPS
    achieved = step_flops / step_s
    util = flops_mod.mfu(step_flops, step_s, device=devs[0])
    log(
        f"step flops={step_flops:.3e}, achieved {achieved / 1e12:.4f} TFLOP/s"
        + (f", MFU {util:.2%}" if util is not None else " (no peak for this platform)")
    )
    if util is not None and util > 1.0:
        log(
            "WARNING: MFU > 100% is physically impossible — the timing or "
            "FLOPs accounting is broken; do not trust this number"
        )
    extras = {
        "tflops": round(achieved / 1e12, 4),
        "mfu": round(util, 4) if util is not None else None,
        "flops_source": flops_source,
        "platform": devs[0].platform,
    }
    from tpu_dist.observe import memory as memory_mod

    # Peak footprint rides the same persisted record as throughput: HBM
    # where the backend tracks it, host-RSS fallback on CPU (labeled —
    # an RSS number must never read as a chip number in the trajectory).
    mem = memory_mod.memory_snapshot(devs[0])
    if mem.get("peak_bytes_in_use"):
        extras["peak_memory_bytes"] = int(mem["peak_bytes_in_use"])
        extras["memory_source"] = mem["source"]
        if mem["source"] == "hbm":
            extras["hbm_peak_mb"] = round(mem["peak_bytes_in_use"] / 1e6, 1)
    return sps, extras


def bench_torch_reference() -> float:
    """The reference stack's throughput on the same workload (torch CPU —
    its dev backend).  Architecture re-stated per train_dist.py:53-71."""
    import torch
    import torch.nn as tnn
    import torch.nn.functional as F

    torch.manual_seed(1234)
    torch.set_num_threads(max(torch.get_num_threads(), 4))

    class Net(tnn.Module):
        def __init__(self):
            super().__init__()
            self.c1 = tnn.Conv2d(1, 10, 5)
            self.c2 = tnn.Conv2d(10, 20, 5)
            self.drop2d = tnn.Dropout2d()
            self.f1 = tnn.Linear(320, 50)
            self.f2 = tnn.Linear(50, 10)

        def forward(self, x):
            x = F.relu(F.max_pool2d(self.c1(x), 2))
            x = F.relu(F.max_pool2d(self.drop2d(self.c2(x)), 2))
            x = x.flatten(1)
            x = F.dropout(F.relu(self.f1(x)), training=self.training)
            return F.log_softmax(self.f2(x), dim=1)

    net = Net()
    opt = torch.optim.SGD(net.parameters(), lr=0.01, momentum=0.5)
    x = torch.randn(BATCH, 1, 28, 28)
    y = torch.randint(0, 10, (BATCH,))

    def step():
        opt.zero_grad()
        loss = F.nll_loss(net(x), y)
        loss.backward()
        opt.step()

    for _ in range(3):
        step()
    n = 20
    t0 = time.perf_counter()
    for _ in range(n):
        step()
    dt = time.perf_counter() - t0
    sps = n * BATCH / dt
    log(f"torch-cpu reference: {n} steps in {dt:.3f}s -> {sps:,.0f} samples/s")
    return sps


def main():
    import os

    from tpu_dist.utils.platform import select_platform

    # TPU_DIST_PLATFORM=cpu is the test suite's explicit request for the
    # CPU; anything else runs on the default backend, untouched.
    select_platform(os.environ.get("TPU_DIST_PLATFORM"))
    value, extras = bench_tpu_dist()
    baseline = bench_torch_reference()
    result = {
        "metric": "mnist_dp_train_samples_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(value / baseline, 2),
        **extras,
    }
    persist_event({"event": "bench", **result})
    print(json.dumps(result))


if __name__ == "__main__":
    main()
