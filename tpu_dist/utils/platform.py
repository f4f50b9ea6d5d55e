"""Platform selection, compile-cache placement and timed-region closing.

The reference simulates a cluster with loopback process forks
(train_dist.py:138-147); our analog is N simulated XLA host devices in
one process.  Getting that requires two mutations **before JAX
initializes its backends**; `pin_cpu` is the shared implementation of
that sequence for every entry point that is ASKED for the CPU
(``--platform cpu``, the test suite).  Nothing here ever arrives at the
CPU on its own: an entry point that is not asked for it uses the default
backend untouched and fails if that backend does.
"""

from __future__ import annotations

import os
import warnings
from pathlib import Path

# <checkout>/.jax_cache — a FIXED path: the directory is part of the
# persistent cache's key, so one built from a temp name, pid or time
# never hits.
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: nothing is touched — JAX reads the
    variable itself, and whoever set it (the operator, the machine image)
    owns the location.  Unset: the cache goes to `DEFAULT_COMPILE_CACHE`.
    Size/time thresholds stay at JAX's defaults.  Called by every entry
    point that compiles for the chip (``chip_smoke.py``, ``bench.py``,
    ``benchmarks/*.py``, the demos, `comm.init`); idempotent.

    Every cache hit/miss surfaces as telemetry: a ``compile_cache`` event
    (when ``TPU_DIST_TELEMETRY`` is set), the
    ``tpu_dist_compile_cache_{hits,misses}_total`` registry counters and
    the ``cache`` attribute of a kept ``compile.backend`` span, through
    `observe.compile_spans`' `jax.monitoring` listeners.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE)
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
            # jax memoizes its is-the-cache-used decision at the first
            # compile of the process; anything compiled before this call
            # would otherwise pin "no cache" for the process lifetime.
            from jax._src import compilation_cache

            compilation_cache.reset_cache()
    from tpu_dist.observe import compile_spans

    compile_spans.install()
    return path


def select_platform(platform: str | None, n_devices: int | None = None) -> None:
    """The one platform decision every entry point makes from its
    ``--platform`` flag: ``'cpu'`` → `pin_cpu` (``n_devices`` simulated
    host devices); anything else → the default backend, untouched, with
    the compile cache placed (`setup_compile_cache`).  A CPU run is
    something asked for, never something arrived at."""
    if platform == "cpu":
        pin_cpu(n_devices)
    else:
        setup_compile_cache()


def pin_cpu(n_devices: int | None = None) -> bool:
    """Restrict this process to the CPU platform, simulating ``n_devices``
    host devices, and VERIFY the pin took effect.

    Must run before JAX backend init (importing jax is fine).  The
    device-count flag is appended unconditionally — with duplicate XLA
    flags the last one wins, so a stale smaller value in the inherited
    environment is overridden rather than silently kept.

    Returns True if the process is now pinned to ≥``n_devices`` CPU
    devices.  Returns False — with a RuntimeWarning — when the pin had no
    effect (JAX backend was already initialized, in which case both the
    platform pin and the device count are silently ignored by JAX).
    """
    if n_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_devices}"
        )
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # some versions raise post-init; the check below decides
    # The update is a silent no-op once backends exist — verify.  (This
    # initializes the CPU backend, which is cheap, local, and exactly the
    # state every caller wants next.)
    devs = jax.devices()
    if devs and devs[0].platform == "cpu" and (
        not n_devices or len(devs) >= n_devices
    ):
        return True
    warnings.warn(
        f"pin_cpu({n_devices}) had no effect: JAX backend already "
        f"initialized with {len(devs)} {devs[0].platform if devs else '?'} "
        f"device(s) — call pin_cpu before any jax.devices()/jit use",
        RuntimeWarning,
        stacklevel=2,
    )
    return False


def host_sync(x) -> float:
    """Wait for the device work producing ``x`` and return one element of
    it as a Python float.

    JAX dispatch is asynchronous, so a timed region must end with
    something that cannot complete before the device does.  A host
    readback of a value that DEPENDS on the result is that: the bytes
    must exist on the host.  It also hands the caller a number to check
    (finite loss, known answer) at no extra cost.  Use this to close
    every timed region.
    """
    import jax
    import numpy as np

    leaf = jax.tree.leaves(x)[0]
    try:
        ndim = leaf.ndim
    except AttributeError:
        return float(leaf)
    return float(np.asarray(leaf[(0,) * ndim]))
