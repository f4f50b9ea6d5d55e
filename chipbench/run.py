"""One run of one cell:

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, checks the timed path against
the plain reference, and prints the result object as the last line of its
standard output.  Exits non-zero and prints no result where JAX finds no
TPU or fewer chips than the cell asks for.  ``--control 1`` (not used by
the driver) also reads the lower-precision control's numbers.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench.harness import claim_chips, run_cell
    from chipbench.manifest import Manifest

    cell = Manifest(ROOT).cell(args.workload)
    devices = claim_chips(cell)
    result = run_cell(
        ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
        devices=devices, t0=_T0, control=bool(args.control),
    )
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
