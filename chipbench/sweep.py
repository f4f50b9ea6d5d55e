"""Find an open-loop cell's knee: one process, one engine, a window at each
of several offered rates.

    python -m chipbench.sweep --workload serve-medium-chat-rate \\
        --rates 2 3 4 5 6 8 --seconds 30 --seeds 1

For each rate and seed (the weights stay those of the first seed) it prints the requests sent and finished, TTFT and TPOT
percentiles, the queue wait in the window's first and last thirds, and the
backlog when the window closed.  The knee is the highest rate whose backlog
does not grow through the window (queue wait in the last third about that
of the first).  The cell's fixed rate, a share of the knee, is then written
as a number into its traffic file by hand (chipbench/README.md).
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import sys
import time
from pathlib import Path

from chipbench.arithmetic import percentile

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    args = ap.parse_args(argv)

    from chipbench.manifest import Manifest

    cell = Manifest(ROOT).cell(args.workload)
    if cell.traffic["kind"] != "serve-open":
        raise SystemExit("only a serve-open cell has a knee to sweep")
    from chipbench.harness import claim_chips

    devices = claim_chips(cell)
    from chipbench.harness import RunContext
    from chipbench.kinds import serve
    from chipbench.spans import CompileCounter

    ctx = RunContext(root=ROOT, cell=cell, seed=args.seeds[0], seconds=args.seconds,
                     trace=False, control=False, devices=devices,
                     t0=time.perf_counter(), compiles=CompileCounter())
    engine = serve._build_engine(ctx)
    for rate, seed in ((r, s) for r in args.rates for s in args.seeds):
        traffic = copy.deepcopy(cell.traffic)
        traffic["rate_per_s"] = rate
        one = dataclasses.replace(
            ctx, seed=seed, cell=dataclasses.replace(cell, traffic=traffic))
        one.rec.spans.clear()
        drv = serve.Driver(one, engine)
        out = serve._open(one, drv)
        f = out.facts
        start = f["window_start"]
        sent = sorted(drv.done + drv.live, key=lambda t: t.due)
        third = args.seconds / 3
        wait = lambda ts: (  # noqa: E731
            sum((t.admitted or start + args.seconds) - t.due for t in ts) / max(len(ts), 1)
        )
        row = {
            "rate_per_s": rate, "seed": seed, "sent": len(sent), "finished": len(drv.done),
            "backlog_no_first_token": sum(t.first is None for t in sent),
            "ttft_p50_ms": percentile(f["ttft_ms"], 50), "ttft_p90_ms": percentile(f["ttft_ms"], 90),
            "tpot_p50_ms": percentile(f["tpot_ms"], 50), "tpot_p90_ms": percentile(f["tpot_ms"], 90),
            "queue_wait_first_third_s": wait([t for t in sent if t.due - start < third]),
            "queue_wait_last_third_s": wait([t for t in sent if t.due - start >= 2 * third]),
            "prefill_step_ms_p50": percentile(f["prefill_step_ms"], 50) if f["prefill_step_ms"] else None,
            "decode_step_ms_p50": percentile(f["decode_step_ms"], 50) if f["decode_step_ms"] else None,
            "engine_steps": f["engine_steps"], "compiles": f["compiles_in_window"],
        }
        print("sweep " + json.dumps(row), flush=True)
        engine.run_until_drained()
        engine.results.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
