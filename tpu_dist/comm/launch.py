"""Multi-process launcher — the fork-join ``__main__`` template, natively
bootstrapped.

The reference spawns ``size`` local processes, each running
``init_processes(rank, size, fn)``, then joins them (train_dist.py:138-147
and the other three scripts).  `launch` reproduces that shape: it forks
``world`` OS processes with the MASTER_ADDR/PORT/WORLD_SIZE/RANK env
contract (tuto.md:421-428), each child runs `tpu_dist.comm.init` — whose
multi-process path does the native C++ rendezvous (startup barrier + rank
assignment, `tpu_dist.runtime`) and then ``jax.distributed.initialize`` —
and finally calls ``fn(rank, world)``.

`launch` is the LOOPBACK development harness (the reference's
fork-over-loopback strategy, SURVEY.md §4.2): every child lands on THIS
host, so ``world > 1`` needs ``platform='cpu'``.  A TPU chip belongs to
one process at a time and the children are given no per-child device
visibility — on a TPU host every child would claim every chip and the
gang would hang — so that combination is refused up front.  On real
hardware the model is one process per TPU HOST driving all its chips:
run the script once per host with the env contract set.  The external
``mpirun``-style launch (tuto.md:393-398) is covered by setting the env
vars outside and calling ``init()`` with no arguments (rank -1 lets the
native rendezvous assign one, mirroring rank-less MPI init,
allreduce.py:54).
"""

from __future__ import annotations

import multiprocessing as mp
import os
import pickle
import sys
import traceback
from typing import Any, Callable


def _child(fn, rank, world, addr, port, platform, conn, devices_per_proc,
           init_method=None, assign_ranks=True, chaos_attempt=0):
    try:
        # Chaos hooks first (import-light, pre-JAX): a `delay=` clause
        # sleeps this rank, a `kill=` clause hard-exits it — the parent
        # observes a child that died without reporting, which is exactly
        # the failure mode the supervisor exists to detect.
        from tpu_dist.resilience import chaos as _chaos
        from tpu_dist.observe import events as _events

        # Pin the telemetry rank before anything can open an event log or
        # heartbeat file: the jax-level rank isn't known yet, and every
        # rank writing to events.jsonl (rank 0's file) would interleave.
        os.environ[_events.ENV_RANK] = str(rank)
        os.environ[_chaos.ATTEMPT_ENV_VAR] = str(chaos_attempt)
        _chaos.at_launch(rank)
        if init_method:
            os.environ["TPU_DIST_INIT_METHOD"] = init_method
        else:
            # an inherited env var must not override this launch's TCP
            # bootstrap (explicit configuration wins)
            os.environ.pop("TPU_DIST_INIT_METHOD", None)
            os.environ["MASTER_ADDR"] = addr
            os.environ["MASTER_PORT"] = str(port)
        os.environ["WORLD_SIZE"] = str(world)
        if assign_ranks:
            os.environ["RANK"] = str(rank)
        else:
            # mpirun-style: ranks come from the rendezvous master election
            # (allreduce.py:54's rank-less init)
            os.environ.pop("RANK", None)
        if platform == "cpu" and devices_per_proc:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={devices_per_proc}"
            )
        from tpu_dist import comm

        comm.init(platform=platform)
        result = fn(rank, world)
        conn.send(("ok", pickle.dumps(result)))
    except BaseException as e:  # report child failures to the parent
        # The exception is caught here, so the excepthook-based flight
        # dump never fires — dump the ring explicitly: the crashing
        # rank's last steps are exactly what the merge CLI needs.
        try:
            from tpu_dist.observe import flightrec as _flightrec

            _flightrec.crash_dump(f"exception:{type(e).__name__}")
        except Exception:
            pass
        conn.send(("error", f"rank {rank}: {type(e).__name__}: {e}\n"
                   f"{traceback.format_exc()}"))
    finally:
        conn.close()


def launch(
    fn: Callable[[int, int], Any],
    world: int,
    *,
    platform: str | None = None,
    addr: str = "127.0.0.1",
    port: int | None = None,
    devices_per_proc: int = 1,
    timeout: float = 300.0,
    init_method: str | None = None,
    assign_ranks: bool = True,
    restarts: int = 0,
    probe_world: Callable[[], int | None] | None = None,
) -> list[Any]:
    """Fork-join ``world`` processes running ``fn(rank, world)``.

    ``fn`` must be picklable (module-level).  Returns each rank's result,
    index = LAUNCH slot (== jax rank when ``assign_ranks``).  Any child
    failure raises, fail-stop, after terminating the others (the
    reference's failure model: blocked peers + ``join()``, SURVEY.md §5).
    ``init_method='file:///path'`` bootstraps through the fcntl file
    rendezvous instead of the TCP master (tuto.md:430-437).
    ``assign_ranks=False`` leaves RANK unset — every child does the
    MPI-style rank-less init and the rendezvous election assigns ranks
    (allreduce.py:54 analog).

    ``restarts=N`` turns the fail-stop into a supervisor: when a child
    dies (or fails) the whole gang is reaped and relaunched, up to N
    times — a fork-join collective group has no single-rank recovery
    (the survivors hold dead collective state), so the restart unit is
    the gang.  Each attempt gets a fresh rendezvous port (when ``port``
    is None) and exports its attempt index to the children
    (`resilience.chaos.ATTEMPT_ENV_VAR`) so chaos kill clauses can be
    scoped to one attempt.  Exhausted restarts raise
    `resilience.WorkerFailed` with the last failure.

    ``probe_world`` makes the relaunch ELASTIC: before each relaunch the
    supervisor re-probes how many workers the machine can actually field
    (a preemption may have taken chips with it) instead of replaying the
    original world size — the callable returns the new world (None =
    keep the current one).  Without it, the env var
    ``TPU_DIST_PROBE_WORLD`` (an integer, read fresh per relaunch) is
    honored, else the world is replayed unchanged.  Each supervisor
    event carries ``relaunch_world`` — the world the NEXT attempt will
    run (None once restarts are exhausted) — so the event stream shows
    the topology change next to the failure that forced it.  Elastic
    workloads resume their checkpoints through
    `train.reshard.redistribute`, which maps the old topology's shards
    onto whatever mesh the re-probed world builds.
    """
    from tpu_dist.observe import events as events_mod
    from tpu_dist.resilience.retry import WorkerFailed, logger

    refuse_multiprocess_off_cpu("comm.launch", world, platform)
    # The gang supervisor's own event stream (events_supervisor.jsonl):
    # restarts and final failure become machine-parseable records instead
    # of vanishing into stderr.  NULL logger when telemetry is off.
    elog = events_mod.from_env(role="supervisor")
    last_error: Exception | None = None
    attempt_world = world
    for attempt in range(restarts + 1):
        try:
            results = _launch_once(
                fn, attempt_world, platform=platform, addr=addr, port=port,
                devices_per_proc=devices_per_proc, timeout=timeout,
                init_method=init_method, assign_ranks=assign_ranks,
                attempt=attempt,
            )
            if attempt > 0:
                elog.emit(
                    "retry", what="gang_relaunch", attempt=attempt + 1,
                    max_attempts=restarts + 1, error=None,
                    world=attempt_world, relaunch_world=attempt_world,
                    outcome="succeeded",
                )
            return results
        except WorkerFailed as e:
            last_error = e
            # Forensics before anything else: gather the per-rank flight
            # dumps (chaos kills, crashed children, and watchdog fires
            # all dump into the telemetry dir) into an attempt-scoped
            # subdir so a relaunch's fresh dumps can't overwrite them,
            # and record where they went.  `python -m
            # tpu_dist.observe.flightrec merge <dir>` names the
            # divergent rank from the gathered set.
            _gather_flight_dumps(elog, attempt)
            exhausted = attempt >= restarts
            next_world = (
                None if exhausted
                else _reprobe_world(probe_world, attempt_world)
            )
            elog.emit(
                "retry", what="gang_relaunch", attempt=attempt + 1,
                max_attempts=restarts + 1, error=str(e),
                world=attempt_world, relaunch_world=next_world,
                outcome="exhausted" if exhausted else "relaunching",
            )
            if exhausted:
                break
            if next_world != attempt_world:
                logger.warning(
                    "elastic relaunch: world %d -> %d (re-probed)",
                    attempt_world, next_world,
                )
            attempt_world = next_world
            logger.warning(
                "launch attempt %d/%d failed (%s); relaunching the gang",
                attempt + 1, restarts + 1, e,
            )
    assert last_error is not None
    raise last_error


def refuse_multiprocess_off_cpu(
    who: str, world: int, platform: str | None
) -> None:
    """`launch` and ``python -m tpu_dist.run`` fork ``world`` processes
    onto ONE host.  That is sound on the CPU platform only: a TPU chip
    belongs to one process at a time, the children get no per-child
    device visibility, and a second child that reaches for the chips
    fails or hangs.  Refuse, and say what to do instead."""
    if world > 1 and platform != "cpu":
        raise ValueError(
            f"{who}: {world} processes on one host is the CPU loopback "
            f"harness, but the platform is {platform or 'the default backend'!r}"
            " — every child would claim every local chip.  Pass "
            "platform='cpu' (CLI: --platform cpu) for the simulation; on "
            "TPU hardware run ONE process per host (it drives all the "
            "host's chips) with MASTER_ADDR/MASTER_PORT/WORLD_SIZE/RANK set"
        )


def _reprobe_world(
    probe_world: Callable[[], int | None] | None, current: int
) -> int:
    """The world size the next relaunch attempt should run.  A probe
    callable wins (its errors propagate — a broken probe must be loud);
    else ``TPU_DIST_PROBE_WORLD`` (garbage raises, same reasoning); else
    the current world, unchanged.  Clamped to >= 1."""
    if probe_world is not None:
        probed = probe_world()
        return max(1, int(probed)) if probed is not None else current
    env = os.environ.get("TPU_DIST_PROBE_WORLD")
    if env is not None:
        return max(1, int(env))
    return current


def _gather_flight_dumps(elog, attempt: int) -> None:
    """Move per-rank flight-recorder dumps from the telemetry dir root
    into ``flight/attempt<k>/`` and record a ``flight_dump`` event —
    best-effort (a gang failure must surface even if the gather can't)."""
    try:
        from tpu_dist.observe import events as events_mod
        from tpu_dist.observe import flightrec as flightrec_mod

        # Same dir precedence the recorders dump under: children write
        # to TPU_DIST_FLIGHTREC_DIR when telemetry is off, and those
        # dumps must be attempt-scoped too or a relaunch overwrites them.
        dirpath = (os.environ.get(events_mod.ENV_DIR)
                   or os.environ.get(flightrec_mod.ENV_DIR))
        if not dirpath:
            return
        ranks, dest = flightrec_mod.gather_dumps(dirpath, attempt)
        if dest is not None:
            elog.emit(
                "flight_dump", reason="gang_failure", ranks=ranks,
                dir=dest, attempt=attempt,
            )
    except Exception:
        pass


def _launch_once(
    fn: Callable[[int, int], Any],
    world: int,
    *,
    platform: str | None,
    addr: str,
    port: int | None,
    devices_per_proc: int,
    timeout: float,
    init_method: str | None,
    assign_ranks: bool,
    attempt: int = 0,
) -> list[Any]:
    """One supervised fork-join attempt (the pre-`restarts` launch body)."""
    from tpu_dist import runtime
    from tpu_dist.resilience.retry import WorkerFailed

    if port is None:
        # Fresh port per attempt: a relaunch must not race the dying
        # gang's master socket (TIME_WAIT / stale registrations).
        port = runtime.free_port()
    ctx = mp.get_context("spawn")
    procs, conns = [], []
    for rank in range(world):
        parent_conn, child_conn = ctx.Pipe(duplex=False)
        p = ctx.Process(
            target=_child,
            args=(fn, rank, world, addr, port, platform, child_conn,
                  devices_per_proc, init_method, assign_ranks, attempt),
        )
        p.start()
        # Close the parent's copy of the child end NOW: with it open, a
        # child that dies without reporting never EOFs its pipe and the
        # supervisor would only notice at the full timeout — dead-child
        # detection must be event-driven (pipe EOF), not timeout-driven.
        child_conn.close()
        procs.append(p)
        conns.append(parent_conn)
    results: list[Any] = [None] * world
    error = None
    # Collect from ALL pipes concurrently: one dead rank leaves the others
    # blocked in collectives/coordination barriers, so rank-by-rank
    # polling would burn the full timeout before the real error surfaced.
    # Fail-stop: after the first reported error, survivors get a short
    # grace period, then are terminated.
    import time as _time
    from multiprocessing.connection import wait as mp_wait

    pending = {conn: rank for rank, conn in enumerate(conns)}
    deadline = _time.monotonic() + timeout
    while pending:
        limit = min(deadline, _time.monotonic() + 5.0) if error else deadline
        wait_s = limit - _time.monotonic()
        ready = mp_wait(list(pending), timeout=max(wait_s, 0)) if wait_s > 0 else []
        if not ready:
            break
        for conn in ready:
            rank = pending.pop(conn)  # type: ignore[arg-type]
            try:
                status, payload = conn.recv()
            except EOFError:
                error = error or f"rank {rank}: died without reporting a result"
                continue
            if status == "ok":
                results[rank] = pickle.loads(payload)
            else:
                error = error or payload
    for conn, rank in pending.items():
        error = error or f"rank {rank}: no result before timeout/fail-stop"
    for p in procs:
        if (error is not None or pending) and p.is_alive():
            p.terminate()
        p.join(timeout=10)
        if p.is_alive():
            p.kill()
    if error is not None:
        # WorkerFailed subclasses RuntimeError, so pre-supervisor callers
        # catching RuntimeError (and matching "launch failed") still work.
        raise WorkerFailed(f"launch failed — {error}")
    return results
