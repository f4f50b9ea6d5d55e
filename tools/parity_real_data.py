"""Accuracy parity on REAL handwritten pixels: this framework vs the
reference stack, identical data and hyperparameters, side by side.

The reference's headline result is "train MNIST with sync-SGD, losses
identical across ranks, accuracy comes out right"
(/root/reference/train_dist.py:76-127).  This container has no egress,
so real MNIST can't be fetched (tools/fetch_mnist.py documents the
retry); the real-pixel corpus that IS available is sklearn's bundled
handwritten-digits scans (1797 genuine 8x8 handwriting images, upsampled
through the same normalization — `tpu_dist.data.load_real_digits`).

This script trains BOTH stacks on that corpus with the reference's exact
hyperparameters (SGD lr=0.01 momentum=0.5, global batch 128, NLL loss,
the same ConvNet graph, train_dist.py:53-71,85,110):

- ours: `tpu_dist.train.Trainer` (the full distributed train step);
- reference: torch, the architecture restated line-for-line as in
  bench.py (the reference implementation's own stack).

The corpus is ~33x smaller than MNIST, so epochs are scaled so both
stacks see a comparable number of SGD steps (--epochs, default 120
~ 1,320 steps vs the reference's ~4,690); both get the identical split.
Prints one JSON line; run by the battery / committed into docs/perf.md.
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


def ours(train_ds, test_ds, epochs: int, platform: str | None):
    from tpu_dist import comm, models, train

    mesh = comm.make_mesh(1, ("data",), platform=platform)
    cfg = train.TrainConfig(
        epochs=epochs, global_batch=128, seed=1234, lr=0.01, momentum=0.5
    )
    trainer = train.Trainer(models.mnist_net(), models.IN_SHAPE, mesh, cfg)
    t0 = time.perf_counter()
    stats = trainer.fit(train_ds)
    dt = time.perf_counter() - t0
    acc = trainer.evaluate(test_ds)
    return acc, stats[-1].mean_loss, dt


def reference(train_ds, test_ds, epochs: int):
    import numpy as np
    import torch
    import torch.nn as tnn
    import torch.nn.functional as F

    torch.manual_seed(1234)

    class Net(tnn.Module):  # train_dist.py:53-71 restated
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

    # NHWC (ours) -> NCHW (torch)
    xs = torch.from_numpy(
        np.moveaxis(train_ds.images, -1, 1).copy()
    )
    ys = torch.from_numpy(train_ds.labels.astype(np.int64))
    net = Net()
    opt = torch.optim.SGD(net.parameters(), lr=0.01, momentum=0.5)
    g = torch.Generator().manual_seed(1234)
    t0 = time.perf_counter()
    last = None
    for epoch in range(epochs):
        order = torch.randperm(len(xs), generator=g)
        total, steps = 0.0, 0
        for b in range(0, len(xs) - 127, 128):
            idx = order[b : b + 128]
            opt.zero_grad()
            loss = F.nll_loss(net(xs[idx]), ys[idx])
            loss.backward()
            opt.step()
            total += float(loss)
            steps += 1
        last = total / max(steps, 1)
    dt = time.perf_counter() - t0
    net.eval()
    with torch.no_grad():
        tx = torch.from_numpy(np.moveaxis(test_ds.images, -1, 1).copy())
        ty = torch.from_numpy(test_ds.labels.astype(np.int64))
        acc = float((net(tx).argmax(1) == ty).float().mean())
    return acc, last, dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=120)
    ap.add_argument("--platform", default=None)
    args = ap.parse_args()

    platform = args.platform
    from tpu_dist.utils.platform import select_platform

    select_platform(platform)

    from tpu_dist import data

    train_ds = data.load_real_digits("train")
    test_ds = data.load_real_digits("test")
    assert not train_ds.synthetic
    log(f"real handwritten digits: {len(train_ds)} train / {len(test_ds)} test")

    acc_o, loss_o, dt_o = ours(train_ds, test_ds, args.epochs, platform)
    log(f"tpu_dist: acc {acc_o:.4f} (final loss {loss_o:.4f}, {dt_o:.0f}s)")
    acc_r, loss_r, dt_r = reference(train_ds, test_ds, args.epochs)
    log(f"torch ref: acc {acc_r:.4f} (final loss {loss_r:.4f}, {dt_r:.0f}s)")

    print(json.dumps({
        "metric": "real_pixels_accuracy_parity",
        "data": "sklearn handwritten digits (1797 real scans, 80/20)",
        "hyperparams": "SGD lr=0.01 momentum=0.5, batch 128, NLL "
                       f"({args.epochs} epochs)",
        "ours_accuracy": round(acc_o, 4),
        "reference_accuracy": round(acc_r, 4),
        "delta": round(acc_o - acc_r, 4),
        "parity": bool(acc_o >= acc_r - 0.01),
    }))


if __name__ == "__main__":
    main()
