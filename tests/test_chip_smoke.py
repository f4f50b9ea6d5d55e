"""chip_smoke.py — the standing check that the main path still starts on
the TPU.  Here, on the CPU, two things can be held: without a chip it
fails before compiling anything, and its rehearsal drives every phase."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import jax

ROOT = Path(__file__).parent.parent


def run_smoke(*args, timeout=120, **env):
    return subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=timeout, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
    )


def test_without_a_tpu_it_fails_before_compiling(capsys):
    """No accelerator: non-zero exit, the platform it found named on
    stderr, NO result on stdout (the pass line can only come from a
    chip), and nothing lowered or compiled on the way out."""
    proc = run_smoke()
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "platform 'cpu'" in proc.stderr
    assert "--rehearse" in proc.stderr

    # same path in-process, with JAX's own compile events counted
    import chip_smoke  # the repo root is on sys.path (tests/ is a package)

    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: lowered.append(event)
    )
    try:
        assert chip_smoke.main([]) == 1
    finally:
        jax.monitoring.clear_event_listeners()
    assert lowered == []
    assert "platform 'cpu'" in capsys.readouterr().err


def test_result_line_has_exactly_the_contract_keys():
    """What the driver parses as the last stdout line of a chip run."""
    import chip_smoke

    dev = jax.devices()[0]
    for ok in (True, False):
        line = json.loads(chip_smoke.result_line(ok, dev, 4))
        assert line == {"ok": ok, "device": {
            "platform": dev.platform, "kind": dev.device_kind, "count": 4}}
        assert type(line["device"]["count"]) is int


@pytest.mark.slow
def test_rehearsal_runs_every_phase_and_never_prints_the_pass_line():
    proc = run_smoke(
        "--rehearse", timeout=900,
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("chip_smoke summary {")  # NOT the bare result line
    summary = json.loads(last.removeprefix("chip_smoke summary "))
    assert summary["rehearsal"] is True and summary["rehearsal_ok"] is True
    assert "ok" not in summary and '"ok": true, "device"' not in proc.stdout
    assert summary["compile_cache_dir"] is None  # the checkout stays clean
    phases = summary["phases"]
    assert list(phases) == [
        "device", "collectives", "mnist_dp", "lm_train_1chip",
        "lm_train_4chip", "serve", "kernels",
    ]
    assert all(row.get("ok") is True for row in phases.values()), phases
    assert phases["kernels"]["ring_all_reduce_pallas"] == "equal"
