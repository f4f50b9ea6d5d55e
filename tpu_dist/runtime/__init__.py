"""`tpu_dist.runtime` — native (C++) runtime components.

The reference's native layer is THD's C++ transport/rendezvous
(tuto.md:404-419); ours is `rendezvous.cc`, loaded via ctypes (no pybind11
in this image).  The library is built lazily with ``make`` (g++) on first
use into ``build/`` (git-ignored): every load goes through ``make``, so a
``.so`` older than its ``.cc`` is rebuilt and a stale one is never trusted
merely because it exists.

API:
  - `rendezvous(addr, port, world, rank=-1, payload="", timeout_ms=...)`
    → ``(my_rank, {rank: payload})`` — master/worker bootstrap with rank
    assignment and a startup barrier.
  - `free_port()` → an available loopback TCP port.
  - `read_idx(path)` → numpy array via the native mmap reader
    (`idx_reader.cc`) — the data-loading native fast path; the pure-numpy
    parser in `tpu_dist.data.mnist` is the fallback.
"""

from __future__ import annotations

import ctypes
import subprocess
import threading
from pathlib import Path

_HERE = Path(__file__).parent
_LIB_PATH = _HERE / "build" / "librendezvous.so"
_lock = threading.Lock()
_lib = None


def _build() -> None:
    """Bring ``build/*.so`` up to date with the tracked ``.cc`` sources
    (a no-op when they already are — ``make`` compares the mtimes)."""
    subprocess.run(
        ["make", "-s", "-C", str(_HERE)],
        check=True,
        capture_output=True,
        text=True,
    )


def _load():
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        _build()
        lib = ctypes.CDLL(str(_LIB_PATH))
        lib.td_rendezvous.restype = ctypes.c_int
        lib.td_rendezvous.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_char_p,
            ctypes.c_int,
            ctypes.c_char_p,
            ctypes.c_int,
        ]
        lib.td_free_port.restype = ctypes.c_int
        lib.td_last_error.restype = ctypes.c_char_p
        _lib = lib
        return lib


_idx_lib = None


def _load_idx():
    global _idx_lib
    with _lock:
        if _idx_lib is not None:
            return _idx_lib
        _build()
        lib = ctypes.CDLL(str(_HERE / "build" / "libidxreader.so"))
        lib.td_idx_open.restype = ctypes.c_void_p
        lib.td_idx_open.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_ubyte)),
        ]
        lib.td_idx_close.argtypes = [ctypes.c_void_p]
        lib.td_idx_last_error.restype = ctypes.c_char_p
        _idx_lib = lib
        return lib


def read_idx(path):
    """Parse an IDX file via the native mmap reader.

    Returns a numpy uint8 array: ``(n, rows, cols)`` for image files,
    ``(n,)`` for label files.  The data is copied out of the mapping
    (so the handle can be closed immediately); for the 60k MNIST train
    set this is one 45 MB memcpy from page cache — no Python-level
    byte shuffling.
    """
    import numpy as np

    lib = _load_idx()
    dims = (ctypes.c_int64 * 4)()
    data = ctypes.POINTER(ctypes.c_ubyte)()
    handle = lib.td_idx_open(str(path).encode(), dims, ctypes.byref(data))
    if not handle:
        err = lib.td_idx_last_error().decode() or "unknown idx error"
        raise ValueError(f"native IDX read failed: {err}")
    try:
        n, rows, cols, payload = dims[0], dims[1], dims[2], dims[3]
        # Read exactly the byte count C++ validated against the mapping —
        # never re-derive it here (an undersized read bound is the only
        # thing standing between a crafted header and a SIGBUS).
        arr = np.ctypeslib.as_array(data, shape=(payload,)).copy()
    finally:
        lib.td_idx_close(handle)
    return arr.reshape((n, rows, cols) if rows else (n,))


def free_port() -> int:
    """A loopback TCP port where BOTH ``port`` and ``port + 1`` are free —
    the bootstrap uses the pair (rendezvous / JAX coordinator)."""
    port = _load().td_free_port()
    if port == 0:
        raise OSError("could not find a free port pair")
    return port


def file_rendezvous(
    path,
    world: int,
    rank: int = -1,
    payload: str = "",
    timeout_s: float = 30.0,
) -> tuple[int, dict[int, str]]:
    """Shared-filesystem rendezvous — the ``file://`` init method
    (tuto.md:430-437): processes coordinate through one file guarded by
    ``fcntl`` advisory locks (the same syscall the reference's C path
    uses; Python's ``fcntl`` module is a direct wrapper).

    Each process appends a ``rank payload`` registration under an
    exclusive lock (``rank=-1`` takes the next free slot, FCFS like the
    TCP master) and then polls until all ``world`` registrations exist.
    Returns ``(my_rank, {rank: payload})``.  Single-host/multi-process
    dev only — multi-host jobs should use the TCP `rendezvous`.
    """
    import fcntl
    import time
    from pathlib import Path as _Path

    path = _Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    deadline = time.monotonic() + timeout_s

    def read_table(f) -> dict[int, str]:
        f.seek(0)
        table: dict[int, str] = {}
        for line in f.read().decode().splitlines():
            r, _, pl = line.partition(" ")
            table[int(r)] = pl
        return table

    my_rank = None
    with open(path, "a+b") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            table = read_table(f)
            if len(table) >= world:
                raise RuntimeError(
                    f"file rendezvous: {path} already has {len(table)} "
                    f"registrations for world {world} (stale file?)"
                )
            if rank >= 0:
                if rank >= world:
                    raise RuntimeError(
                        f"file rendezvous: rank {rank} out of range for "
                        f"world {world}"
                    )
                if rank in table:
                    raise RuntimeError(
                        f"file rendezvous: rank {rank} already registered "
                        f"in {path}"
                    )
                my_rank = rank
            else:
                my_rank = next(
                    (r for r in range(world) if r not in table), None
                )
                if my_rank is None:
                    raise RuntimeError(
                        f"file rendezvous: no free rank slot in {path} "
                        f"for world {world} (stale file?)"
                    )
            f.write(f"{my_rank} {payload}\n".encode())
            f.flush()
        finally:
            fcntl.flock(f, fcntl.LOCK_UN)
    # Startup barrier: wait until every slot is registered.
    while True:
        with open(path, "rb") as f:
            fcntl.flock(f, fcntl.LOCK_SH)
            try:
                table = read_table(f)
            finally:
                fcntl.flock(f, fcntl.LOCK_UN)
        if len(table) >= world:
            return my_rank, table
        if time.monotonic() > deadline:
            raise RuntimeError(
                f"file rendezvous: only {len(table)}/{world} processes "
                f"registered in {path} before timeout"
            )
        time.sleep(0.05)


def rendezvous(
    addr: str,
    port: int,
    world: int,
    rank: int = -1,
    payload: str = "",
    timeout_ms: int = 30_000,
) -> tuple[int, dict[int, str]]:
    """Master/worker bootstrap (tuto.md:404-419 contract, natively).

    ``rank=0`` acts as master (binds ``addr:port``); ``rank=-1`` requests
    master-assigned rank (the MPI-style rank-less init of allreduce.py:54).
    Blocks until all ``world`` processes have joined (startup barrier) or
    the timeout elapses — fail-stop, matching the reference's failure model
    (SURVEY.md §5 'Failure detection').

    Returns ``(my_rank, peer_table)`` where ``peer_table[r]`` is rank r's
    registered payload string.
    """
    lib = _load()
    buf = ctypes.create_string_buffer(1 << 16)
    got = lib.td_rendezvous(
        addr.encode(),
        port,
        world,
        rank,
        payload.encode(),
        timeout_ms,
        buf,
        len(buf),
    )
    if got < 0:
        err = lib.td_last_error().decode() or "unknown rendezvous failure"
        raise RuntimeError(f"rendezvous failed (addr={addr}:{port}): {err}")
    lines = buf.value.decode().strip().split("\n")
    peers: dict[int, str] = {}
    for line in lines[1:]:
        r, _, pl = line.partition(" ")
        peers[int(r)] = pl
    return got, peers
