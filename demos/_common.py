"""Shared demo plumbing: platform/world flags.

Every reference demo is a ``__main__`` that forks ``size`` local processes
(e.g. train_dist.py:138-147).  Here the analog is a device mesh; these
flags pick its size and platform ('cpu' simulates a cluster on one host
exactly like the reference's loopback forks — SURVEY.md §4.2).

Run with no flags on a TPU host to use all chips; run with
``--platform cpu --world 8`` anywhere.  A CPU run is something asked for:
without ``--platform cpu`` (or ``TPU_DIST_PLATFORM=cpu``) the default
backend is used untouched, and a demo on a machine whose backend cannot
start fails instead of quietly simulating.
"""

from __future__ import annotations

import argparse
import os
import sys

# Demos are runnable from demos/ or the repo root without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(default_world: int | None = None, **extra):
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--world", type=int, default=default_world,
        help="number of ranks (devices); default: all available",
    )
    parser.add_argument(
        "--platform", default=os.environ.get("TPU_DIST_PLATFORM"),
        help="'tpu' | 'cpu' (backend-string analog); default: JAX's default backend",
    )
    for name, (tp, default, help_) in extra.items():
        parser.add_argument(f"--{name}", type=tp, default=default, help=help_)
    args = parser.parse_args()
    from tpu_dist.utils.platform import select_platform

    select_platform(args.platform, args.world or 8)
    return args
