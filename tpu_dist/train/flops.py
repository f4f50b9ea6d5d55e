"""Model-FLOPs accounting and MFU (model FLOPs utilization).

The reference has no perf instrumentation beyond a hand-throttled timing
loop (allreduce.py:41-42); BASELINE.md's targets are throughput-shaped.
Throughput alone can't be judged against hardware — the missing figure is
achieved-FLOP/s as a fraction of the chip's peak (MFU).  Two counters:

1. ``xla_flops`` — the ground truth: XLA's own cost analysis of the
   compiled program (covers fwd+bwd+optimizer, fused exactly as
   executed).
2. Analytic per-layer counters (``conv2d_flops``/``linear_flops``/
   ``attention_flops``) — hardware-independent cross-checks and the
   conventional "model FLOPs" numerator (MFU counts model math only, so
   the XLA number — which includes optimizer/allreduce arithmetic — is a
   slight overestimate of the conventional numerator; both are exposed).

Peak numbers are the public per-chip specs, one table keyed by the exact
``device_kind`` string (`CHIPS`).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable


@dataclass(frozen=True)
class ChipSpec:
    """Published per-chip peaks.  bf16 is the MXU's native matmul dtype
    (fp32 inputs run as bf16x3 passes — far below this peak, so fp32 runs
    legitimately show low MFU against it); HBM bandwidth is the
    decode-side roofline (autoregressive decode re-reads weights + KV
    cache every step, so tok/s is bounded by bandwidth long before the
    MXU matters)."""

    peak_bf16_flops: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


# Keyed by the EXACT ``jax.Device.device_kind`` (the strings libtpu
# 0.0.34 reports for each generation's topology; the v5e one is also what
# chip_smoke.py printed on the chip).  No prefix matching: "TPU v5" is the
# v5p, and a future "TPU v5 <something>" must not inherit its peak.
_CLOUD = "Google Cloud TPU documentation, "
CHIPS: dict[str, ChipSpec] = {
    "TPU v2": ChipSpec(45e12, 700e9, 16e9, _CLOUD + '"TPU v2"'),
    "TPU v3": ChipSpec(123e12, 900e9, 32e9, _CLOUD + '"TPU v3"'),
    "TPU v4": ChipSpec(275e12, 1228e9, 32e9, _CLOUD + '"TPU v4"'),
    "TPU v5 lite": ChipSpec(197e12, 819e9, 16e9, _CLOUD + '"TPU v5e"'),
    "TPU v5": ChipSpec(459e12, 2765e9, 95e9, _CLOUD + '"TPU v5p"'),
    "TPU v6 lite": ChipSpec(918e12, 1640e9, 32e9, _CLOUD + '"TPU v6e"'),
}


def chip_spec(device: Any | None = None) -> ChipSpec | None:
    """The `CHIPS` entry for ``device`` (default: first device).

    ``None`` off-TPU (the CPU has no published peak, so callers report
    MFU only where it means something).  On platform ``tpu`` a
    ``device_kind`` that is not in the table RAISES: a device without a
    peak is an error to fix in the table, not a default to inherit and
    not a silently missing MFU.
    """
    import jax

    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "") or ""
    spec = CHIPS.get(kind)
    if spec is None and getattr(device, "platform", None) == "tpu":
        raise KeyError(
            f"no published peaks for TPU device_kind {kind!r} — add it to "
            f"tpu_dist.train.flops.CHIPS with its source (known: "
            f"{sorted(CHIPS)})"
        )
    return spec


def hbm_bandwidth(device: Any | None = None) -> float | None:
    """Per-chip HBM bandwidth (bytes/s); see `chip_spec` for None/raise."""
    spec = chip_spec(device)
    return spec.hbm_bytes_per_s if spec else None


def peak_flops(device: Any | None = None) -> float | None:
    """Per-chip bf16 peak FLOP/s; see `chip_spec` for None/raise."""
    spec = chip_spec(device)
    return spec.peak_bf16_flops if spec else None


def xla_flops(fn: Callable, *args: Any) -> float | None:
    """FLOPs of ONE invocation of ``fn(*args)`` per XLA cost analysis.

    ``fn`` may be a plain callable or an existing ``jax.jit`` object; it
    is lowered/compiled for the given example args (cached by jit, so
    calling this around a benchmark costs one compile at most).

    NOTE: for a program partitioned over N devices (pjit/shard_map), XLA
    reports the PER-DEVICE partitioned program's flops — multiply by the
    device count for a world total, or pass ``n_devices=1`` to `mfu` to
    get per-chip utilization (per-chip MFU equals whole-world MFU for an
    evenly sharded SPMD program).
    """
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    try:
        compiled = jitted.lower(*args).compile()
        return compiled_flops(compiled)
    except Exception:
        return None


def compiled_flops(compiled: Any) -> float | None:
    """Extract the 'flops' entry from a compiled executable's cost
    analysis (handles the dict and list-of-dicts shapes across JAX
    versions)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return None
    val = ca.get("flops")
    return float(val) if val else None


def mfu(
    flops_per_step: float | None,
    step_seconds: float,
    *,
    n_devices: int = 1,
    device: Any | None = None,
) -> float | None:
    """Achieved / peak FLOP-rate over ``n_devices`` chips; None when
    either side is unknown."""
    if not flops_per_step or step_seconds <= 0:
        return None
    peak = peak_flops(device)
    if not peak:
        return None
    return (flops_per_step / step_seconds) / (peak * n_devices)


# ---------------------------------------------------------------- analytic

def conv2d_flops(
    batch: int, h_out: int, w_out: int, c_in: int, c_out: int, k: int
) -> float:
    """2 · MACs for a k×k valid conv producing (h_out, w_out, c_out)."""
    return 2.0 * batch * h_out * w_out * c_in * c_out * k * k


def linear_flops(batch: int, d_in: int, d_out: int) -> float:
    return 2.0 * batch * d_in * d_out


def attention_flops(
    batch: int, heads: int, seq_q: int, seq_k: int, head_dim: int, *, causal: bool = False
) -> float:
    """QK^T + PV matmul FLOPs (the conventional 4·b·h·sq·sk·d).

    ``causal`` counts only the realizable score entries under the
    bottom-right (suffix) alignment `tpu_dist.nn.dot_product_attention`
    documents: query i (of sq, ending at key position sk) sees
    ``sk - sq + i + 1`` keys, so the fraction is
    ``(sq·sk - sq·(sq-1)/2) / (sq·sk)`` — ≈½ for sq == sk, but ~1 for
    decode-style sq ≪ sk, where halving would badly undercount."""
    f = 2.0 * batch * heads * seq_q * seq_k * head_dim * 2
    if not causal:
        return f
    realizable = seq_q * seq_k - seq_q * (seq_q - 1) / 2
    return f * realizable / (seq_q * seq_k)


def mnist_net_forward_flops(batch: int) -> float:
    """Analytic forward FLOPs of the reference ConvNet
    (train_dist.py:53-71): conv(1→10,k5) on 28² → 24², pool → 12²,
    conv(10→20,k5) → 8², pool → 4², fc 320→50, fc 50→10.
    Matmul/conv terms only (elementwise ops are noise on the MXU)."""
    return (
        conv2d_flops(batch, 24, 24, 1, 10, 5)
        + conv2d_flops(batch, 8, 8, 10, 20, 5)
        + linear_flops(batch, 320, 50)
        + linear_flops(batch, 50, 10)
    )


def train_step_flops_estimate(forward_flops: float) -> float:
    """Standard fwd+bwd estimate: backward ≈ 2× forward ⇒ 3× total."""
    return 3.0 * forward_flops
