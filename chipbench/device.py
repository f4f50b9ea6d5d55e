"""The look for the chip, and what the device reports of itself."""

from __future__ import annotations


class NoChip(SystemExit):
    """Raised (exit code 2, nothing printed to stdout) where JAX finds no
    TPU or fewer chips than the cell asks for.  Never a fallback."""


def require_chips(chips: int) -> list:
    import sys

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chipbench: no accelerator: {e}", file=sys.stderr)
        raise NoChip(2) from None
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(
            f"chipbench: the cell needs {chips} TPU chip(s); JAX reports "
            f"{len(devices)} device(s) of platform {devices[0].platform!r}",
            file=sys.stderr,
        )
        raise NoChip(2)
    return list(devices[:chips])


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` on the fullest of ``devices`` (0 where the
    backend keeps no such count, as the CPU does)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks, default=0))


def describe(devices, peak_bytes: int, **extra) -> dict:
    """The result line's ``device``: as JAX reports it (every device it
    sees), with the peak the run read on the chips the cell used."""
    import jax

    d = devices[0]
    return {
        "platform": d.platform, "kind": d.device_kind, "count": len(jax.devices()),
        "memory_peak_bytes": int(peak_bytes), **extra,
    }
