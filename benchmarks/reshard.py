"""Elastic-resume redistribution benchmark (`make bench-reshard`).

Times `train.reshard.redistribute` — the kill → resume-on-a-different-
topology path — over representative swaps of a ~32 MB transformer-shaped
state: dp → fsdp (same chip count), dp → dp×fsdp, and dp×tp → dp×fsdp
with a chip-count change.  Reports redistribution throughput (MB/s of
state moved) and the measured peak transient host bytes next to the
plan's asserted bound (2× the largest bucket) — the "never materialize
a full replica" claim as a number, not an adjective.

Every case appends a structured record to
``benchmarks/results/bench_runs.jsonl`` via `bench.persist_event`, so
`make regress` gates redistribution wall time and peak bytes like any
other series.

Run: ``python benchmarks/reshard.py [--platform cpu] [--mb 32]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


class _Capture:
    """Event logger stand-in: the redistribution's own `reshard` event
    (bytes moved, peak bytes, wall time) IS the measurement."""

    def __init__(self):
        self.records = []

    def emit(self, event, **fields):
        self.records.append({"event": event, **fields})
        return self.records[-1]


def state_tree(mb: int):
    import numpy as np

    # Transformer-shaped names so realistic rule sets bind; sized so the
    # embedding dominates (the leaf a naive restore would replicate).
    scale = max(1, mb // 32)
    rng = np.random.default_rng(0)
    return {
        "embed": {"table": rng.normal(
            size=(4096 * scale, 1024)).astype(np.float32)},
        "attn": {"qkv": {"w": rng.normal(
            size=(1024, 3072 * scale)).astype(np.float32)}},
        "mlp": {"fc1": {"w": rng.normal(
            size=(1024, 1024 * scale)).astype(np.float32)}},
        "step": np.int32(0),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--world", type=int, default=8)
    ap.add_argument("--mb", type=int, default=32,
                    help="approximate state size to redistribute")
    ap.add_argument("--bucket-mb", type=int, default=4)
    ap.add_argument("--no-persist", action="store_true")
    args = ap.parse_args()
    from tpu_dist.utils.platform import select_platform

    select_platform(args.platform, args.world)
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    import bench
    from tpu_dist.parallel import partition as part
    from tpu_dist.train import checkpoint, reshard

    devs = jax.devices()
    tree = state_tree(args.mb)
    nbytes = sum(a.nbytes for a in jax.tree.leaves(tree))
    log(f"state: {nbytes / 1e6:.1f} MB over {len(devs)} devices")

    rules = {
        "dp": [(".*", P())],
        "fsdp": [
            ("embed/table", P("fsdp", None)),
            ("attn/qkv/w", P(None, "fsdp")),
            ("mlp/fc1/w", P(None, "fsdp")),
            (".*", P()),
        ],
        "tp": [
            ("embed/table", P("tp", None)),
            ("attn/qkv/w", P(None, "tp")),
            ("mlp/fc1/w", P(None, "tp")),
            (".*", P()),
        ],
    }
    n = len(devs)
    cases = [
        ("dp_to_fsdp", f"dp={n}", "dp", f"fsdp={n}", n, "fsdp"),
        ("dp_to_dp_fsdp", f"dp={n}", "dp",
         f"dp=2,fsdp={n // 2}", n, "fsdp"),
        ("dp_tp_to_dp_fsdp", f"dp=2,tp={n // 2}", "tp",
         f"dp=2,fsdp={n // 4}", n // 2, "fsdp"),
    ]

    def place(spec, rkey, mesh):
        specs = part.match_partition_rules(rules[rkey], tree, mesh)
        from jax.sharding import NamedSharding

        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            tree, specs,
        )

    out_records = []
    with tempfile.TemporaryDirectory() as td:
        for name, src_spec, src_rules, tgt_spec, tgt_ndev, tgt_rules in cases:
            mesh_src = part.build_mesh(src_spec, mesh_devices=devs[:n])
            mesh_tgt = part.build_mesh(
                tgt_spec, mesh_devices=devs[:tgt_ndev]
            )
            src = place(src_spec, src_rules, mesh_src)
            ck = Path(td) / f"ckpt_{name}"
            checkpoint.save_sharded(
                ck, src, step=0,
                partition={"rules": src_rules, "axes": {"dp": n}},
            )
            tmpl = reshard.target_templates(
                tree, rules[tgt_rules], mesh_tgt
            )
            cap = _Capture()
            out, _ = reshard.redistribute(
                ck, tmpl, bucket_bytes=args.bucket_mb << 20, logger=cap
            )
            jax.block_until_ready(out)
            ev = cap.records[-1]
            assert ev["status"] == "ok", ev
            rec = {
                "event": "bench",
                "metric": f"reshard_{name}",
                "value": round(ev["bytes_moved"] / 1e6 / ev["seconds"], 3),
                "unit": "MB/s",
                "seconds": round(ev["seconds"], 4),
                "peak_transient_bytes": ev["peak_bytes"],
                "bytes_moved": ev["bytes_moved"],
                "bound_ratio": round(
                    ev["peak_bytes"] / ev["bound_bytes"], 3
                ),
                "world": n,
                "source": src_spec,
                "target": tgt_spec,
                "state_mb": round(nbytes / 1e6, 1),
                "bucket_mb": args.bucket_mb,
            }
            log(
                f"{name:20s}: {rec['value']:9.1f} MB/s  "
                f"peak {ev['peak_bytes'] / 1e6:7.2f} MB "
                f"(bound {ev['bound_bytes'] / 1e6:.2f} MB)"
            )
            out_records.append(rec)
            if not args.no_persist:
                try:
                    bench.persist_event(rec)
                except Exception as e:
                    log(f"could not persist bench record: {e}")
    print(json.dumps(out_records))


if __name__ == "__main__":
    main()
