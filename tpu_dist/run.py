"""``python -m tpu_dist.run`` — the external script launcher.

The reference launches distributed jobs two ways: an in-script fork-join
``__main__`` (train_dist.py:138-147) and an EXTERNAL launcher
(``mpirun -n 4 python myscript.py``, tuto.md:393-398) that sets rank and
world size for an unmodified script.  `tpu_dist.comm.launch` is the
first; this module is the second — the torchrun/mpirun analog:

    python -m tpu_dist.run --nproc 4 myscript.py --arg value

It spawns ``nproc`` copies of the script with the reference's rendezvous
environment contract set (MASTER_ADDR / MASTER_PORT / WORLD_SIZE / RANK
— tuto.md:421-428); the script reads them via `comm.InitConfig.from_env`
(or plain ``os.environ``) exactly like a reference script reads them
under mpirun.  ``--rankless`` omits RANK so ranks are assigned
first-come-first-served by the native rendezvous (the ``mpirun``-style
rank-less init of allreduce.py:54).

All ``nproc`` children land on THIS host, so ``--nproc`` > 1 is the CPU
loopback harness: it needs ``--platform cpu`` (exported to the children
as ``JAX_PLATFORMS``/``TPU_DIST_PLATFORM``) or an environment that
already says so, and refuses otherwise — on a TPU host every child would
claim every chip.  On hardware, run one process per host.

Fail-stop semantics (the reference's failure model): the first child
that exits non-zero causes the launcher to terminate the rest and exit
with that code.  Child stdout/stderr pass through, line-buffered, with
a ``[rank N]`` prefix (``--no-tag`` disables).
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import threading


def _stream(proc, rank: int, tag: bool):
    prefix = f"[rank {rank}] " if tag else ""
    for line in proc.stdout:
        sys.stdout.write(f"{prefix}{line}")
        sys.stdout.flush()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tpu_dist.run",
        description="Launch N copies of a script with the distributed "
        "rendezvous environment set (torchrun/mpirun analog).",
    )
    ap.add_argument("--nproc", type=int, required=True, help="world size")
    ap.add_argument("--master-addr", default="127.0.0.1")
    ap.add_argument(
        "--master-port", type=int, default=0,
        help="0 = pick a free port",
    )
    ap.add_argument(
        "--rankless", action="store_true",
        help="omit RANK; ranks assigned FCFS by the native rendezvous",
    )
    ap.add_argument("--no-tag", action="store_true",
                    help="don't prefix child output with [rank N]")
    ap.add_argument(
        "--platform",
        default=os.environ.get("JAX_PLATFORMS")
        or os.environ.get("TPU_DIST_PLATFORM"),
        help="platform exported to the children; --nproc > 1 requires "
        "'cpu' (default: JAX_PLATFORMS, then TPU_DIST_PLATFORM)",
    )
    ap.add_argument("script", help="python script to run per rank")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if args.nproc < 1:
        ap.error("--nproc must be >= 1")
    from tpu_dist.comm.launch import refuse_multiprocess_off_cpu

    try:
        refuse_multiprocess_off_cpu(
            "tpu_dist.run", args.nproc, args.platform
        )
    except ValueError as e:
        ap.error(str(e))

    port = args.master_port
    if not port:
        from tpu_dist import runtime

        port = runtime.free_port()

    procs: list[subprocess.Popen] = []
    threads = []
    for rank in range(args.nproc):
        env = dict(os.environ)
        env["MASTER_ADDR"] = args.master_addr
        env["MASTER_PORT"] = str(port)
        env["WORLD_SIZE"] = str(args.nproc)
        if args.platform:
            env["JAX_PLATFORMS"] = env["TPU_DIST_PLATFORM"] = args.platform
        if args.rankless:
            env.pop("RANK", None)
        else:
            env["RANK"] = str(rank)
        p = subprocess.Popen(
            [sys.executable, args.script, *args.script_args],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            bufsize=1,
        )
        procs.append(p)
        t = threading.Thread(
            target=_stream, args=(p, rank, not args.no_tag), daemon=True
        )
        t.start()
        threads.append(t)

    # fail-stop: first non-zero exit kills the rest (reference failure
    # model: blocked peers + join, SURVEY.md §5)
    rc = 0
    alive = set(range(args.nproc))
    while alive:
        for r in sorted(alive):
            code = procs[r].poll()
            if code is None:
                continue
            alive.discard(r)
            if code != 0 and rc == 0:
                rc = code
                sys.stderr.write(
                    f"[tpu_dist.run] rank {r} exited with {code}; "
                    f"terminating remaining ranks\n"
                )
                for other in alive:
                    procs[other].terminate()
        if alive:
            try:
                procs[next(iter(alive))].wait(timeout=0.1)
            except subprocess.TimeoutExpired:
                pass
    for t in threads:
        t.join(timeout=5)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
