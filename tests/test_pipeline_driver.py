"""Step-pipeline tests: the K-deep dispatch ring must be OBSERVABLY
invisible — identical epoch mean loss / final params / bad_steps to the
synchronous loop for every depth, under chaos NaN steps and gradient
accumulation, across preemption, and with the background host loader's
failure modes surfaced instead of hung."""

import os
import signal

import numpy as np
import pytest

import jax

from tpu_dist import comm, data, models, train
from tpu_dist.data.loader import HostLoader
from tpu_dist.resilience import chaos
from tpu_dist.train.pipeline_driver import CompletedStep, PipelineDriver


@pytest.fixture(scope="module")
def mesh():
    return comm.make_mesh(8, ("data",), platform="cpu")


@pytest.fixture(scope="module")
def dataset():
    return data.load_mnist("train", synthetic_size=512)


# ------------------------------------------------------------ driver unit


def _dummy_step(params, model_state, opt_state, batch, key):
    # loss encodes the batch so readback order is checkable
    return params + 1, model_state, opt_state, float(batch), {}


def test_driver_ring_bookkeeping():
    drv = PipelineDriver(depth=2)
    p, completed = 0, []
    for b in range(5):
        p, _, _, done = drv.step(_dummy_step, (p, None, None, b, None))
        completed.extend(done)
    # depth 2: steps 1..3 evicted by dispatches 3..5, 4..5 still in flight
    assert [c.step_id for c in completed] == [1, 2, 3]
    assert drv.in_flight == 2
    drained = drv.drain()
    assert [c.step_id for c in drained] == [4, 5]
    assert [c.loss for c in completed + drained] == [0.0, 1.0, 2.0, 3.0, 4.0]
    assert p == 5  # every step dispatched immediately
    assert drv.drain() == []  # idempotent


def test_driver_depth_zero_is_synchronous():
    drv = PipelineDriver(depth=0)
    for b in range(3):
        _, _, _, done = drv.step(_dummy_step, (0, None, None, b, None))
        assert [c.loss for c in done] == [float(b)]
        assert drv.in_flight == 0


def test_driver_rejects_negative_depth():
    with pytest.raises(ValueError, match="depth"):
        PipelineDriver(depth=-1)


def test_driver_context_drains_on_exit():
    with PipelineDriver(depth=4) as drv:
        for b in range(3):
            drv.step(_dummy_step, (0, None, None, b, None))
        assert drv.in_flight == 3
    assert drv.in_flight == 0


# ------------------------------------------- trainer parity (the contract)


def _fit_mnist(mesh, dataset, **cfg_kw):
    cfg = train.TrainConfig(epochs=2, log=lambda s: None, **cfg_kw)
    t = train.Trainer(models.mnist_net(), models.IN_SHAPE, mesh, cfg)
    hist = t.fit(dataset)
    params = [np.asarray(l) for l in jax.tree.leaves(t.params)]
    return hist, params


def test_pipelined_matches_sync_all_depths(mesh, dataset):
    """K in 1..4 must reproduce the synchronous loop's observables bit
    for bit: same per-epoch mean loss, same final params."""
    ref_hist, ref_params = _fit_mnist(mesh, dataset, inflight_steps=0)
    for k in (1, 2, 4):
        hist, params = _fit_mnist(mesh, dataset, inflight_steps=k)
        assert [h.mean_loss for h in hist] == [h.mean_loss for h in ref_hist]
        assert [h.bad_steps for h in hist] == [h.bad_steps for h in ref_hist]
        for a, b in zip(params, ref_params):
            np.testing.assert_array_equal(a, b)


def test_pipelined_matches_sync_with_chaos_nan_and_accum(
    mesh, dataset, monkeypatch
):
    """The hard case: a chaos-injected NaN step (skipped ON DEVICE by
    the guard — no host decision in the loop) plus accum_steps>1, still
    depth-invariant including the bad_steps count."""
    monkeypatch.setenv(chaos.ENV_VAR, "nan_step=2")
    ref_hist, ref_params = _fit_mnist(
        mesh, dataset, inflight_steps=0, nan_guard=True, accum_steps=2
    )
    assert ref_hist[-1].bad_steps == 1  # the injection landed
    for k in (1, 3):
        hist, params = _fit_mnist(
            mesh, dataset, inflight_steps=k, nan_guard=True, accum_steps=2
        )
        assert [h.mean_loss for h in hist] == [h.mean_loss for h in ref_hist]
        assert hist[-1].bad_steps == 1
        for a, b in zip(params, ref_params):
            np.testing.assert_array_equal(a, b)


def test_lm_trainer_pipelined_matches_sync(mesh):
    lm = models.TransformerLM(vocab=64, dim=32, depth=1, heads=2, max_seq=16)
    windows = np.asarray(
        np.random.default_rng(0).integers(0, 64, (64, 16)), np.int32
    )

    def run(k):
        cfg = train.LMTrainConfig(
            epochs=2, global_batch=16, inflight_steps=k, log=lambda s: None
        )
        t = train.LMTrainer(lm, mesh, cfg)
        hist = t.fit(windows)
        return hist, [np.asarray(l) for l in jax.tree.leaves(t.params)]

    ref_hist, ref_params = run(0)
    hist, params = run(2)
    assert [h.mean_loss for h in hist] == [h.mean_loss for h in ref_hist]
    for a, b in zip(params, ref_params):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------- preemption mid-flight


def _preempted_fit(mesh, dataset, ckpt_dir, inflight):
    """Fit with SIGTERM fired during step-call 3 of epoch 0; returns the
    (empty) history and the trainer."""
    t = train.Trainer(
        models.mnist_net(), models.IN_SHAPE, mesh,
        train.TrainConfig(
            epochs=2, inflight_steps=inflight, log=lambda s: None
        ),
    )
    orig_step, calls = t.step, {"n": 0}

    def stepper(*args):
        calls["n"] += 1
        if calls["n"] == 3:
            os.kill(os.getpid(), signal.SIGTERM)
        return orig_step(*args)

    t.step = stepper
    hist = t.fit(dataset, checkpoint_dir=str(ckpt_dir))
    return hist, t


def test_preemption_mid_flight_drains_and_resumes(mesh, dataset, tmp_path):
    """SIGTERM while K steps are in flight: the driver drains before the
    preempt checkpoint, so the saved state carries EVERY dispatched step
    — bit-identical to the synchronous loop preempted at the same step —
    and the resumed run completes the schedule."""
    sync_dir, pipe_dir = tmp_path / "sync", tmp_path / "pipe"
    hist_s, _ = _preempted_fit(mesh, dataset, sync_dir, inflight=0)
    hist_p, _ = _preempted_fit(mesh, dataset, pipe_dir, inflight=2)
    assert hist_s == [] and hist_p == []  # epoch 0 never completed

    found_s = train.checkpoint.latest_intact(sync_dir)
    found_p = train.checkpoint.latest_intact(pipe_dir)
    assert found_p is not None and "preempt" in str(found_p)

    t_s = train.Trainer(
        models.mnist_net(), models.IN_SHAPE, mesh,
        train.TrainConfig(epochs=2, inflight_steps=0, log=lambda s: None),
    )
    t_p = train.Trainer(
        models.mnist_net(), models.IN_SHAPE, mesh,
        train.TrainConfig(epochs=2, inflight_steps=2, log=lambda s: None),
    )
    assert t_s.restore(found_s) == 0
    assert t_p.restore(found_p) == 0  # the interrupted epoch is the resume point
    for a, b in zip(jax.tree.leaves(t_s.params), jax.tree.leaves(t_p.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
        jax.tree.leaves(t_s.opt_state), jax.tree.leaves(t_p.opt_state)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the resumed pipelined run finishes the schedule, matching a sync
    # resume bit for bit
    hist2_p = t_p.fit(dataset, start_epoch=0)
    hist2_s = t_s.fit(dataset, start_epoch=0)
    assert [h.epoch for h in hist2_p] == [0, 1]
    assert (
        [h.mean_loss for h in hist2_p] == [h.mean_loss for h in hist2_s]
    )
    for a, b in zip(jax.tree.leaves(t_s.params), jax.tree.leaves(t_p.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------- background loader


def test_host_loader_matches_inline_sharding(mesh, dataset):
    """Order and content identical to the inline prefetch path."""
    dl = data.DistributedLoader(dataset, 8, 64)
    inline = list(data.prefetch_to_mesh(dl.epoch(0), mesh))
    with HostLoader(dl.epoch(0), mesh) as hl:
        background = list(hl)
    assert len(background) == len(inline) == dl.steps_per_epoch
    for (xa, ya), (xb, yb) in zip(inline, background):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
        np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))
        assert xb.sharding == xa.sharding


def test_host_loader_propagates_worker_exception(mesh):
    """A crashing worker must surface its exception at the consumer's
    next(), never hang the training loop."""

    def bad_batches():
        yield (np.zeros((8, 1, 28, 28), np.float32),
               np.zeros((8,), np.int32))
        raise RuntimeError("loader boom")

    with HostLoader(bad_batches(), mesh) as hl:
        next(hl)
        with pytest.raises(RuntimeError, match="loader boom"):
            next(hl)
        # after the failure the iterator is done, not wedged
        with pytest.raises(StopIteration):
            next(hl)


def test_host_loader_close_mid_stream_joins_worker(mesh):
    """Abandoning the loader mid-epoch (preemption break) must unblock
    the worker's bounded put and join the thread."""

    def endless():
        while True:
            yield (np.zeros((8, 1, 28, 28), np.float32),
                   np.zeros((8,), np.int32))

    hl = HostLoader(endless(), mesh, depth=2)
    next(hl)
    hl.close()
    assert not hl._thread.is_alive()
    with pytest.raises(StopIteration):
        next(hl)


def test_host_loader_rejects_bad_depth(mesh):
    with pytest.raises(ValueError, match="depth"):
        HostLoader(iter(()), mesh, depth=0)


# ----------------------------------------- telemetry under pipelining


def test_step_events_carry_dispatch_ids_and_phases(tmp_path, monkeypatch, mesh):
    """Events are emitted at READBACK time but carry the step ids
    assigned at DISPATCH time (in order), goodput reports the
    dispatch/readback phase split, and with the guard on the per-step
    bad_steps counts are exact (captured before donation kills the
    opt-state buffers)."""
    from tpu_dist.observe import events

    tdir = str(tmp_path / "telemetry")
    monkeypatch.setenv(events.ENV_DIR, tdir)
    monkeypatch.delenv(events.ENV_RUN_ID, raising=False)
    cfg = train.TrainConfig(
        epochs=1, inflight_steps=3, nan_guard=True, log=lambda s: None
    )
    t = train.Trainer(models.mnist_net(), models.IN_SHAPE, mesh, cfg)
    t.fit(data.load_mnist("train", synthetic_size=512))

    n, errors = events.validate_dir(tdir)
    assert errors == [], errors[:10]
    recs = events.read_events(tdir)
    steps = [r for r in recs if r["event"] == "step"]
    assert [s["step"] for s in steps] == [1, 2, 3, 4]
    assert all(s["bad_steps"] == 0 for s in steps)
    assert all(s["step_time"] > 0 for s in steps)
    epoch = [r for r in recs if r["event"] == "epoch"][-1]
    phases = epoch["goodput"]["phases"]
    assert phases["dispatch"] > 0 and phases["readback"] > 0


def test_steptimer_tick_measures_intervals():
    from tpu_dist.train.metrics import StepTimer

    st = StepTimer(warmup=1)
    st.tick()  # arms
    st.tick()  # warmup interval, discarded
    st.tick()
    st.tick()
    assert len(st.times) == 2
    assert all(dt >= 0 for dt in st.times)


# --------------------------------------------------- bench smoke (tier-1)


def test_dispatch_bench_smoke():
    """The fast CPU dispatch-pipeline smoke: the harness runs, reports
    every requested depth, and the JSON contract holds."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "dispatch.py",
    )
    spec = importlib.util.spec_from_file_location("_bench_dispatch", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.main(["--platform", "cpu", "--steps", "4", "--warmup", "1",
                    "--repeats", "1", "--batch", "32", "--ks", "1,2"])
    assert out["metric"] == "dispatch_pipeline_samples_per_sec"
    assert set(out["rows"]) == {"parity", "latency"}
    for row in out["rows"].values():
        assert set(row["results"]) == {"sync", "k1", "k2"}
        assert all(v > 0 for v in row["results"].values())
    assert out["results"] == out["rows"]["latency"]["results"]
