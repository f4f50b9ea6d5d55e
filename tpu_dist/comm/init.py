"""Process/runtime bootstrap — ``dist.init_process_group`` analog.

The reference's init contract (tuto.md:404-428, exercised at
train_dist.py:130-135): set ``MASTER_ADDR``/``MASTER_PORT``, call
``init_process_group(backend, rank, world_size)``; rank 0 acts as master,
workers rendezvous through it, ending fully connected.  Config comes from
env vars ``MASTER_PORT/MASTER_ADDR/WORLD_SIZE/RANK`` (tuto.md:421-428).

TPU-native equivalent: ``jax.distributed.initialize(coordinator_address,
num_processes, process_id)`` — the coordinator is the MASTER_ADDR/PORT
analog, and the XLA runtime plays THD's role (channel setup, peer
discovery, collective transport over ICI/DCN).  On a single host (or under
CPU simulation) no coordinator is needed and init is a no-op, mirroring how
every reference demo also runs single-machine over loopback (SURVEY.md §4).

The MPI-style rank-less init (``allreduce.py:54`` — rank assigned by
``mpirun``) maps to TPU pod launch, where process ids come from the
environment; ``init()`` with no arguments covers it.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import jax

from tpu_dist.resilience import chaos as _chaos
from tpu_dist.resilience.retry import RendezvousTimeout, RetryPolicy, retry_call


@dataclass(frozen=True)
class InitConfig:
    """Resolved bootstrap configuration (the four env vars of
    tuto.md:421-428, plus platform as the backend-string analog)."""

    coordinator_address: str | None = None
    num_processes: int | None = None
    process_id: int | None = None
    platform: str | None = None

    @staticmethod
    def from_env() -> "InitConfig":
        addr = os.environ.get("MASTER_ADDR")
        port = os.environ.get("MASTER_PORT")
        coordinator = f"{addr}:{port}" if addr and port else None
        world = os.environ.get("WORLD_SIZE")
        rank_ = os.environ.get("RANK")
        return InitConfig(
            coordinator_address=coordinator,
            num_processes=int(world) if world is not None else None,
            process_id=int(rank_) if rank_ is not None else None,
            platform=os.environ.get("TPU_DIST_PLATFORM"),
        )


def _addr_is_remote(addr: str) -> bool:
    """True only when ``addr`` definitely names another machine: not
    loopback, not this hostname, and not resolving to any of this host's
    addresses.  Unresolvable addresses are treated as local-unknown
    (warn-free pass) — a guard must not produce false positives."""
    import socket

    if addr in ("127.0.0.1", "localhost", "::1") or addr == socket.gethostname():
        return False
    try:
        target = {ai[4][0] for ai in socket.getaddrinfo(addr, None)}
    except OSError:
        return False
    if any(ip.startswith("127.") or ip == "::1" for ip in target):
        return False
    try:
        local = {
            ai[4][0] for ai in socket.getaddrinfo(socket.gethostname(), None)
        }
    except OSError:
        local = set()
    if target & local:
        return False
    # gethostname() may only map to loopback (Debian-style 127.0.1.1
    # /etc/hosts) while MASTER_ADDR carries the real interface IP: the
    # source address the kernel would route FROM to reach the target is
    # the target itself iff the target is one of our interfaces.  (UDP
    # connect assigns a route without sending any packet.)
    for ip in target:
        fam = socket.AF_INET6 if ":" in ip else socket.AF_INET
        try:
            s = socket.socket(fam, socket.SOCK_DGRAM)
            try:
                s.connect((ip, 9))
                if s.getsockname()[0] == ip:
                    return False
            finally:
                s.close()
        except OSError:
            continue
    return True


_initialized = False


def init(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    *,
    platform: str | None = None,
) -> InitConfig:
    """Initialize the distributed runtime.

    Arguments default from the reference's env-var contract
    (``MASTER_ADDR``/``MASTER_PORT``/``WORLD_SIZE``/``RANK``,
    tuto.md:421-428).  Single-process (num_processes in (None, 1)): no-op —
    the runtime is already live.  Multi-process (one process per TPU host):
    wraps ``jax.distributed.initialize``, the rendezvous of tuto.md:404-419.
    """
    global _initialized
    env = InitConfig.from_env()
    cfg = InitConfig(
        coordinator_address=coordinator_address or env.coordinator_address,
        num_processes=num_processes or env.num_processes,
        process_id=process_id if process_id is not None else env.process_id,
        platform=platform or env.platform,
    )
    if cfg.platform != "cpu":
        # Persistent compile cache on every init flavor that may compile
        # for the chip, including the single-process no-op path (it only
        # touches jax.config, safe before OR after backend init).  A
        # restarted job (preemption resume, the gang supervisor's
        # relaunch) then pays compile time once, not on every boot.
        from tpu_dist.utils.platform import setup_compile_cache

        setup_compile_cache()
    if _initialized:
        return cfg
    if cfg.platform is not None:
        # The backend-string analog ('tcp'/'gloo'/'mpi' → 'cpu'/'tpu'):
        # restrict JAX to the chosen platform.  Must happen before any
        # backend initialization to take effect.
        jax.config.update("jax_platforms", cfg.platform)
    if cfg.num_processes and cfg.num_processes > 1:
        from tpu_dist import runtime

        rank = cfg.process_id if cfg.process_id is not None else -1
        # Precedence matches every other parameter: an EXPLICIT
        # coordinator_address argument beats the env-var init method (a
        # stale exported TPU_DIST_INIT_METHOD must not hijack a job that
        # names its coordinator).
        init_method = (
            "" if coordinator_address is not None
            else os.environ.get("TPU_DIST_INIT_METHOD", "")
        )
        if init_method.startswith("file://"):
            # file:// init (tuto.md:430-437): rank assignment + startup
            # barrier through an fcntl-locked file; the process that gets
            # rank 0 publishes the JAX coordinator address as its payload
            # (every payload carries a candidate; rank 0's wins).
            path = init_method[len("file://"):]
            # file:// rendezvous is single-host only (fcntl on a local
            # file; the published coordinator is loopback).  A MASTER_ADDR
            # that resolves OFF this host signals a multi-host job this
            # init method cannot serve — fail fast instead of hanging
            # later in jax.distributed.initialize.  Launchers that export
            # the local host's own IP/hostname (SLURM-style boilerplate)
            # are legitimately single-host and pass.
            master = os.environ.get("MASTER_ADDR")
            if master and _addr_is_remote(master):
                raise ValueError(
                    f"TPU_DIST_INIT_METHOD=file:// is single-host only "
                    f"(loopback coordinator), but MASTER_ADDR={master!r} "
                    f"resolves off this host — use the TCP init path "
                    f"(tuto.md:421-428 contract) instead"
                )
            candidate = f"127.0.0.1:{runtime.free_port()}"
            my_rank, peers = runtime.file_rendezvous(
                path, cfg.num_processes, rank, payload=candidate
            )
            coordinator = peers[0]
        else:
            if cfg.coordinator_address is None:
                raise ValueError(
                    "multi-process init needs MASTER_ADDR/MASTER_PORT, an "
                    "explicit coordinator_address (tuto.md:421-428 "
                    "contract), or TPU_DIST_INIT_METHOD=file:///path"
                )
            addr, _, port_s = cfg.coordinator_address.partition(":")
            port = int(port_s)
            # Native TCP bootstrap (tpu_dist/runtime/rendezvous.cc):
            # startup barrier + rank assignment (process_id=None →
            # master-assigned, the MPI-style rank-less path of
            # allreduce.py:54).  Retried under bounded exponential
            # backoff (TPU_DIST_RDZV_* / TPU_DIST_STARTUP_DEADLINE
            # knobs): a flaky coordinator or a slow-booting peer is the
            # common case at pod scale, and every process runs the same
            # schedule so the gang re-converges on a later attempt.  The
            # chaos gate (`TPU_DIST_CHAOS=rdzv_fail=N`) injects failures
            # through the identical path.
            policy = RetryPolicy.from_env()

            def _rendezvous(attempt):
                _chaos.rendezvous_attempt(attempt)
                return runtime.rendezvous(
                    addr, port, cfg.num_processes, rank,
                    payload=os.uname().nodename,
                )

            my_rank, _peers = retry_call(
                _rendezvous,
                policy=policy,
                retry_on=(RuntimeError, OSError),
                describe=f"rendezvous at {addr}:{port}",
                error_type=RendezvousTimeout,
            )
            # Steady-state coordinator: one port above the rendezvous
            # port — both come from the same MASTER contract.
            coordinator = f"{addr}:{port + 1}"
        cfg = InitConfig(
            coordinator_address=coordinator,
            num_processes=cfg.num_processes,
            process_id=my_rank,
            platform=cfg.platform,
        )
        retry_call(
            lambda _attempt: jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=cfg.num_processes,
                process_id=my_rank,
            ),
            policy=RetryPolicy.from_env(),
            retry_on=(RuntimeError,),
            describe=f"jax.distributed.initialize via {coordinator}",
            error_type=RendezvousTimeout,
        )
    _initialized = True
    return cfg


def process_rank() -> int:
    """Host-level ``dist.get_rank()`` (outside SPMD code)."""
    return jax.process_index()


def process_count() -> int:
    """Host-level ``dist.get_world_size()`` (outside SPMD code)."""
    return jax.process_count()
