"""FLOPs counters + MFU: analytic vs XLA-cost-analysis cross-check.

The analytic counters give the conventional "model FLOPs" numerator;
XLA's cost analysis counts the whole compiled program.  On the forward
pass the two must agree to within the elementwise noise floor.
"""

import jax
import jax.numpy as jnp
import pytest

from tpu_dist import models
from tpu_dist.train import flops


def test_mnist_analytic_value():
    # conv1 288k + conv2 640k + fc1 32k + fc2 1k per sample
    assert flops.mnist_net_forward_flops(1) == pytest.approx(961_000.0)
    assert flops.mnist_net_forward_flops(8) == pytest.approx(8 * 961_000.0)


def test_xla_forward_matches_analytic():
    model = models.mnist_net()
    params, state = model.init(jax.random.key(0), models.IN_SHAPE)
    batch = 16

    def fwd(p, x):
        scores, _ = model.apply(p, state, x, train=False)
        return scores

    x = jnp.zeros((batch,) + models.IN_SHAPE, jnp.float32)
    measured = flops.xla_flops(fwd, params, x)
    assert measured is not None, "CPU cost analysis should report flops"
    analytic = flops.mnist_net_forward_flops(batch)
    # matmul/conv math dominates; XLA adds elementwise/pooling on top.
    assert analytic * 0.9 <= measured <= analytic * 2.0, (measured, analytic)


def test_train_step_estimate_and_mfu_math():
    fwd = flops.mnist_net_forward_flops(128)
    assert flops.train_step_flops_estimate(fwd) == pytest.approx(3 * fwd)

    class FakeDev:
        device_kind = "TPU v5 lite"
        platform = "tpu"

    # 1e12 flops in 10ms on one 197-TFLOP/s chip -> 1e14/1.97e14
    util = flops.mfu(1e12, 0.01, device=FakeDev())
    assert util == pytest.approx(1e14 / 197e12)
    # unknown platform (CPU-sim) -> None, not a bogus number
    assert flops.peak_flops(jax.devices("cpu")[0]) is None
    assert flops.mfu(1e12, 0.01, device=jax.devices("cpu")[0]) is None
    assert flops.mfu(None, 0.01) is None


def test_attention_flops_causal_fraction():
    full = flops.attention_flops(2, 4, 128, 128, 64)
    assert full == pytest.approx(2 * 2 * 4 * 128 * 128 * 64 * 2)
    # self-attention: realizable lower triangle incl. diagonal =
    # (s^2 - s(s-1)/2)/s^2 = (s+1)/(2s)
    s = 128
    assert flops.attention_flops(2, 4, s, s, 64, causal=True) == pytest.approx(
        full * (s + 1) / (2 * s)
    )
    # decode-style sq=1: the single suffix query sees ALL keys — no
    # causal discount (halving here would undercount 2x)
    one = flops.attention_flops(1, 1, 1, 4096, 64)
    assert flops.attention_flops(1, 1, 1, 4096, 64, causal=True) == one


def test_compiled_memory_analysis_reports_plan():
    import jax
    import jax.numpy as jnp

    from tpu_dist.train import metrics

    def f(x, w):
        return jnp.tanh(x @ w) @ w.T

    x = jnp.ones((64, 128))
    w = jnp.ones((128, 128))
    ma = metrics.compiled_memory_analysis(f, x, w)
    assert ma is not None
    assert ma["argument_bytes"] == (64 * 128 + 128 * 128) * 4
    assert ma["output_bytes"] == 64 * 128 * 4
    assert ma["temp_bytes"] >= 0


def test_device_memory_stats_shape():
    from tpu_dist.train import metrics

    stats = metrics.device_memory_stats()
    # CPU-sim backends report nothing; a real chip reports a dict.
    assert stats is None or "bytes_in_use" in stats


def test_peak_table_is_exact_and_unknown_tpu_raises():
    """One table keyed by the exact device_kind: the v5e's kind is
    'TPU v5 lite', 'TPU v5' is the v5p, and a TPU kind that is not in the
    table raises instead of inheriting a prefix's peak (or silently
    losing its MFU).  Off-TPU there is no peak: None."""

    class FakeDev:
        def __init__(self, kind, platform="tpu"):
            self.device_kind = kind
            self.platform = platform

    v5e = flops.chip_spec(FakeDev("TPU v5 lite"))
    assert (v5e.peak_bf16_flops, v5e.hbm_bytes_per_s, v5e.hbm_bytes) == (
        197e12, 819e9, 16e9,
    )
    assert "TPU v5e" in v5e.source
    assert flops.peak_flops(FakeDev("TPU v5")) == 459e12
    assert flops.hbm_bandwidth(FakeDev("TPU v5")) == 2765e9
    for kind in ("TPU v9", "TPU v5 lite pod", "TPU v5e"):
        with pytest.raises(KeyError, match="no published peaks"):
            flops.peak_flops(FakeDev(kind))
        with pytest.raises(KeyError, match="no published peaks"):
            flops.hbm_bandwidth(FakeDev(kind))
    assert flops.peak_flops(FakeDev("cpu", platform="cpu")) is None
    assert flops.peak_flops(jax.devices("cpu")[0]) is None
    assert flops.hbm_bandwidth(jax.devices("cpu")[0]) is None
