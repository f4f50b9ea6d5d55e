"""The telemetry subsystem: registry semantics, JSONL schema round-trip,
heartbeat stall attribution (incl. a chaos-delayed rank), goodput math,
Prometheus scrape, span traces, tpu_top rendering, and the trainer
wiring end-to-end (the acceptance run of ISSUE 3)."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from tpu_dist.observe import events, heartbeat, registry, spans


@pytest.fixture()
def telemetry_dir(tmp_path, monkeypatch):
    """Telemetry armed at a scratch dir (fresh run id, rank 0)."""
    d = str(tmp_path / "telemetry")
    monkeypatch.setenv(events.ENV_DIR, d)
    monkeypatch.delenv(events.ENV_RANK, raising=False)
    monkeypatch.delenv(events.ENV_RUN_ID, raising=False)
    monkeypatch.delenv("RANK", raising=False)
    yield d


# ------------------------------------------------------------------ events


def test_null_logger_when_env_unset(monkeypatch):
    monkeypatch.delenv(events.ENV_DIR, raising=False)
    log = events.from_env()
    assert not log.enabled
    assert log.emit("step", anything=1) is None


def test_event_log_roundtrip(telemetry_dir):
    log = events.from_env()
    assert log.enabled
    log.manifest(world=4, config={"lr": 0.01, "log": print},
                 mesh=None, platform={"backend": "cpu"})
    log.emit("checkpoint", path="/tmp/x.npz", epoch=1, seconds=0.5)
    n, errors = events.validate_dir(telemetry_dir)
    assert errors == []
    assert n == 2
    recs = events.read_events(telemetry_dir)
    assert [r["event"] for r in recs] == ["manifest", "checkpoint"]
    # callables are dropped from the config summary, not serialized
    assert "log" not in recs[0]["config"]
    # envelope on every record; one shared run id
    assert {r["run_id"] for r in recs} == {log.run_id}


def test_rank_files_and_env_rank(telemetry_dir, monkeypatch):
    events.from_env().emit("warning", reason="r0")
    monkeypatch.setenv(events.ENV_RANK, "3")
    log3 = events.from_env()
    assert log3.rank == 3
    log3.emit("warning", reason="r3")
    names = sorted(os.listdir(telemetry_dir))
    assert "events.jsonl" in names
    assert "events_rank3.jsonl" in names


def test_validate_flags_missing_step_keys(tmp_path):
    p = tmp_path / "events.jsonl"
    p.write_text(json.dumps({
        "event": "step", "time": 1.0, "rank": 0, "run_id": "x",
        "step": 1, "epoch": 0, "loss": 0.5,
    }) + "\n")
    n, errors = events.validate_file(str(p))
    assert n == 1
    missing = {e.split("'")[1] for e in errors}
    # the acceptance-critical fields must be schema-required
    assert {"step_time", "samples_per_sec_per_chip", "mfu", "bad_steps",
            "loss_scale", "hbm"} <= missing


def test_nonfinite_floats_stay_rfc8259_parseable(telemetry_dir):
    """A NaN loss (the exact case the NaN guard instruments) must not
    produce a bare NaN token that only Python's lenient parser accepts."""
    events.from_env().emit(
        "warning", reason="nan", loss=float("nan"),
        nested={"v": float("inf")}, xs=[1.0, float("-inf")],
    )
    line = open(os.path.join(telemetry_dir, "events.jsonl")).read().strip()
    assert "NaN" not in line and "Infinity" not in line
    rec = json.loads(line)
    assert rec["loss"] == "nan"
    assert rec["nested"]["v"] == "inf"
    assert rec["xs"] == [1.0, "-inf"]
    # numpy non-finite scalars (what a jnp loss readback produces) too
    import numpy as np

    rec2 = events.from_env().emit("warning", reason="npnan",
                                  loss=np.float32("nan"))
    assert rec2 is not None
    last = open(
        os.path.join(telemetry_dir, "events.jsonl")
    ).read().strip().splitlines()[-1]
    assert json.loads(last)["loss"] == "nan"


def test_fresh_run_id_per_telemetry_dir(tmp_path, monkeypatch):
    """Two runs in one process (different dirs) must not share a stale
    run id; children of the current run still inherit via the env var."""
    monkeypatch.delenv(events.ENV_RUN_ID, raising=False)
    monkeypatch.setenv(events.ENV_DIR, str(tmp_path / "run_a"))
    a = events.from_env().run_id
    assert os.environ[events.ENV_RUN_ID] == a
    monkeypatch.setenv(events.ENV_DIR, str(tmp_path / "run_b"))
    b = events.from_env().run_id
    assert b != a
    assert os.environ[events.ENV_RUN_ID] == b


def test_exotic_values_never_crash_emit(telemetry_dir):
    import numpy as np

    rec = events.from_env().emit(
        "warning", reason="exotic", dtype=np.dtype("float32"),
        arr=np.float32(1.5), fn=open,
    )
    assert rec is not None
    n, errors = events.validate_dir(telemetry_dir)
    assert n >= 1 and errors == []


# ---------------------------------------------------------------- registry


def test_counter_gauge_semantics():
    reg = registry.MetricsRegistry()
    c = reg.counter("steps_total", "steps")
    c.inc()
    c.inc(2.0)
    assert c.value() == 3.0
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    g = reg.gauge("loss")
    g.set(0.25)
    assert g.value() == 0.25
    # get-or-create is idempotent; kind mismatch raises
    assert reg.counter("steps_total") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("steps_total")


def test_counter_labels():
    reg = registry.MetricsRegistry()
    c = reg.counter("events_total")
    c.inc(event="retry")
    c.inc(event="retry")
    c.inc(event="stall")
    assert c.value(event="retry") == 2.0
    assert c.value(event="stall") == 1.0
    text = reg.render()
    assert 'events_total{event="retry"} 2.0' in text


def test_histogram_buckets_cumulative():
    reg = registry.MetricsRegistry()
    h = reg.histogram("step_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    text = reg.render()
    assert 'step_seconds_bucket{le="0.1"} 1.0' in text
    assert 'step_seconds_bucket{le="1.0"} 3.0' in text
    assert 'step_seconds_bucket{le="10.0"} 4.0' in text
    assert 'step_seconds_bucket{le="+Inf"} 5.0' in text
    assert "step_seconds_count 5.0" in text
    assert "step_seconds_sum 56.05" in text


def test_render_exposition_format():
    reg = registry.MetricsRegistry()
    reg.counter("a_total", "things").inc()
    text = reg.render()
    assert "# HELP a_total things" in text
    assert "# TYPE a_total counter" in text
    assert text.endswith("\n")


def test_prometheus_endpoint_scrape():
    reg = registry.MetricsRegistry()
    reg.counter("scraped_total", "scrape check").inc(7)
    server = reg.serve(port=0)
    try:
        url = f"http://127.0.0.1:{server.port}/metrics"
        with urllib.request.urlopen(url, timeout=5) as resp:
            assert resp.status == 200
            assert "version=0.0.4" in resp.headers["Content-Type"]
            body = resp.read().decode()
        assert "scraped_total 7.0" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/nope", timeout=5
            )
    finally:
        server.close()


def test_maybe_serve_from_env(monkeypatch):
    monkeypatch.delenv(registry.ENV_PORT, raising=False)
    assert registry.maybe_serve_from_env() is None
    monkeypatch.setenv(registry.ENV_PORT, "0")
    monkeypatch.setattr(registry, "_server", None)
    server = registry.maybe_serve_from_env()
    try:
        assert server is not None
        # idempotent: second call returns the same server
        assert registry.maybe_serve_from_env() is server
    finally:
        server.close()
        registry._server = None


# ------------------------------------------------------------------- spans


def test_span_recorder_chrome_trace(tmp_path):
    rec = spans.SpanRecorder(str(tmp_path / "t.trace.json"), rank=2)
    with rec.span("step", step=7, epoch=0):
        time.sleep(0.01)
    rec.instant("preempt", step=7)
    path = rec.save()
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert len(evs) == 2
    complete = [e for e in evs if e["ph"] == "X"][0]
    assert complete["name"] == "step"
    assert complete["args"]["step"] == 7
    assert complete["dur"] >= 0.01 * 1e6
    assert complete["pid"] == 2
    assert [e for e in evs if e["ph"] == "i"][0]["name"] == "preempt"


def test_spans_from_env_null_when_off(monkeypatch):
    monkeypatch.delenv(events.ENV_DIR, raising=False)
    rec = spans.from_env()
    with rec.span("x"):
        pass
    assert rec.save() is None and not rec.enabled


def test_span_ids_and_parents_nest():
    t0 = time.perf_counter()
    with spans.span("engine.step", step=3) as outer:
        with spans.span("engine.admit") as first:
            pass
        with spans.span("engine.decode_wait") as second:
            with spans.span("inner") as leaf:
                pass
    lone = spans.record("request.queued", t0, t0 + 0.5, request_id=9)
    t1 = time.perf_counter()
    assert outer.parent is None and lone.parent is None
    assert first.parent == second.parent == outer.id and leaf.parent == second.id
    ids = [outer.id, first.id, second.id, leaf.id, lone.id]
    assert len(set(ids)) == 5 and all(isinstance(i, int) for i in ids)
    # a span is kept when it closes: children before their parent
    got = [s for s in spans.recent(since=t0) if s.id in ids]
    assert [s.id for s in got] == [first.id, leaf.id, second.id, outer.id, lone.id]
    assert outer.attrs == {"step": 3} and lone.attrs == {"request_id": 9}
    # one clock: every reading is time.perf_counter's
    for s in got[:4]:
        assert t0 <= s.start <= s.end <= t1
    assert outer.start <= first.start and second.end <= outer.end
    assert lone.ms == pytest.approx(500.0)


def test_a_span_that_raises_is_still_recorded_and_unwinds_the_stack():
    with pytest.raises(KeyError):
        with spans.span("boom") as sp:
            raise KeyError("x")
    assert spans.recent()[-1] is sp and sp.end >= sp.start
    with spans.span("after") as nxt:
        pass
    assert nxt.parent is None


def test_spans_of_another_thread_have_their_own_parents():
    import threading

    seen = {}

    def work():
        with spans.span("other") as sp:
            seen["other"] = sp

    with spans.span("main") as main:
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert seen["other"].parent is None and seen["other"].id != main.id


def test_span_ring_is_bounded_and_drops_the_oldest(monkeypatch):
    import collections

    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=4))
    for i in range(7):
        with spans.span("s", step=i):
            pass
    assert [s.attrs["step"] for s in spans.recent()] == [3, 4, 5, 6]
    assert spans._ring.maxlen == 4
    cut = spans.recent()[2].start
    assert [s.attrs["step"] for s in spans.recent(since=cut)] == [5, 6]


def test_chrome_export_is_on_the_spans_clock(tmp_path):
    """`ts` is `start` moved by the one process-wide offset; `dur` is
    `end - start`: no second clock is read when a span is made."""
    rec = spans.SpanRecorder(str(tmp_path / "t.trace.json"), rank=1)
    with rec.span("dispatch", step=4) as sp:
        time.sleep(0.002)
    rec.instant("preempt", step=4)
    with rec.span("still_open"):
        doc = json.load(open(rec.save()))  # an open span waits for the next save
    ev = {e["name"]: e for e in doc["traceEvents"]}
    assert set(ev) == {"dispatch", "preempt"}
    assert ev["dispatch"]["ts"] == pytest.approx((sp.start + spans.WALL_OFFSET) * 1e6)
    assert ev["dispatch"]["dur"] == pytest.approx((sp.end - sp.start) * 1e6)
    assert ev["dispatch"]["args"] == {"step": 4, "id": sp.id, "parent": None}
    assert abs(ev["dispatch"]["ts"] / 1e6 - time.time()) < 60  # the wall clock
    assert spans.recent()[-1].name == "still_open"  # the ring has them all
    assert len(json.load(open(rec.save()))["traceEvents"]) == 3


def test_merge_traces_aligns_ranks_on_the_wall_clock(tmp_path):
    paths, order = [], []
    for r in (1, 0, 1, 0):
        rec = spans.SpanRecorder(str(tmp_path / f"r{r}_{len(paths)}.json"), rank=r)
        with rec.span("step", step=len(paths)):
            time.sleep(0.001)
        order.append(r)
        paths.append(rec.save())
    merged = spans.merge_traces(paths)
    xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert [e["pid"] for e in xs] == order  # in time order, each in its lane
    assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)


def test_the_null_recorder_still_records_to_the_ring(monkeypatch):
    monkeypatch.delenv(events.ENV_DIR, raising=False)
    rec = spans.from_env()
    with rec.span("readback", step=12) as sp:
        pass
    rec.instant("preempt", step=12)
    last = spans.recent()[-2:]
    assert last[0] is sp and sp.attrs == {"step": 12}
    assert last[1].name == "preempt" and last[1].start == last[1].end
    assert rec.save() is None and not rec.enabled


def test_the_export_is_the_ring_whoever_opened_the_span(tmp_path, monkeypatch):
    """One recorder: a span opened with the module's `span` or `record`
    (the serving engine's way) is in the file like one opened through the
    recorder (the trainers'), from the moment the recorder was made."""
    import collections
    import threading

    with spans.span("before_the_recorder"):
        pass
    rec = spans.SpanRecorder(str(tmp_path / "t.trace.json"), rank=3)
    with spans.span("engine.step", step=1) as step:
        with spans.span("engine.admit") as admit:
            pass
    import numpy as np

    with rec.span("dispatch", step=1, tokens=np.int32(7)) as disp:  # a numpy scalar is written
        pass
    queued = spans.record("request.queued", step.start, step.end, request_id=41)
    decode = spans.record("request.decode", step.end, step.end + 1.0, request_id=41, emitted=5)
    other = spans.record("request.queued", step.start, step.end, request_id=42)
    doc = json.load(open(rec.save()))
    ev = {e["args"]["id"]: e for e in doc["traceEvents"]}
    assert set(ev) == {step.id, admit.id, disp.id, queued.id, decode.id, other.id}
    assert doc["otherData"] == {"producer": "tpu_dist.observe.spans", "rank": 3, "complete": True}
    # the thread's spans share its lane and nest there; a request has a lane of its own
    me = threading.get_ident() & 0xFFFFFF
    assert ev[step.id]["tid"] == ev[admit.id]["tid"] == ev[disp.id]["tid"] == me == step.tid
    assert ev[queued.id]["tid"] == ev[decode.id]["tid"] == 41 and ev[other.id]["tid"] == 42
    assert ev[admit.id]["args"]["parent"] == step.id and ev[decode.id]["args"]["emitted"] == 5
    assert ev[disp.id]["args"]["tokens"] == 7
    assert all(e["pid"] == 3 for e in ev.values())
    # once the ring has wrapped past the recorder's start the file says so
    assert spans.complete_since(rec.since)
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=2))
    for i in range(3):
        with spans.span("s", step=i):
            pass
    assert not spans.complete_since(rec.since)
    doc = json.load(open(rec.save()))
    assert [e["args"]["step"] for e in doc["traceEvents"]] == [1, 2]
    assert doc["otherData"]["complete"] is False
    assert spans.merge_traces([rec.path])["otherData"]["complete"] is False
    assert spans.complete_since(time.perf_counter())  # nothing since now was dropped


def test_a_span_under_the_profiler_is_in_the_traces_host_plane(tmp_path):
    """With a profiler session open a span is a `TraceAnnotation` named
    `tpu_dist/<name>`: it sits in the `.xplane.pb` beside the device's
    operations, on their clock."""
    import glob

    import jax
    from jax.profiler import ProfileData

    with spans.span("before_the_session"):
        pass
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with spans.span("engine.step", step=1) as outer:
            with spans.span("engine.decode_wait"):
                jax.block_until_ready(jax.numpy.ones((8, 8)) @ jax.numpy.ones((8, 8)))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(spans.PREFIX):
                        found[e.name] = e
    assert set(found) == {"tpu_dist/engine.step", "tpu_dist/engine.decode_wait"}
    step, wait = found["tpu_dist/engine.step"], found["tpu_dist/engine.decode_wait"]
    assert step.start_ns <= wait.start_ns
    assert wait.start_ns + wait.duration_ns <= step.start_ns + step.duration_ns
    assert step.duration_ns / 1e6 == pytest.approx(outer.ms, rel=0.2, abs=0.2)


# --------------------------------------------------------------- heartbeat


def test_heartbeat_write_read(telemetry_dir):
    w = heartbeat.HeartbeatWriter(telemetry_dir, rank=1, min_interval_s=0.0)
    w.beat(step=5, phase="train")
    beats = heartbeat.read(telemetry_dir)
    assert beats[1]["step"] == 5 and beats[1]["phase"] == "train"
    w.close()
    assert heartbeat.read(telemetry_dir)[1]["phase"] == "done"


def test_attribute_stall_names_the_straggler(telemetry_dir):
    now = time.time()
    fresh = heartbeat.HeartbeatWriter(telemetry_dir, rank=0, min_interval_s=0.0)
    fresh.beat(step=10, phase="train")
    # rank 1 last beat 5s ago: hand-written record (no sleeping in tier-1)
    stale = {"rank": 1, "time": now - 5.0, "step": 4, "phase": "train"}
    with open(os.path.join(telemetry_dir, "heartbeat_rank1.json"), "w") as fh:
        json.dump(stale, fh)
    behind = heartbeat.attribute_stall(
        telemetry_dir, stale_after_s=2.0, expected_world=3, now=now
    )
    assert [e["rank"] for e in behind] == [2, 1]  # missing first, then lag
    assert behind[0]["missing"] is True
    assert behind[1]["behind_s"] == pytest.approx(5.0, abs=0.2)
    msg = heartbeat.describe_stall(behind)
    assert "rank 2 has no heartbeat" in msg
    assert "rank 1 is 5.0s behind (step 4)" in msg


def test_attribute_stall_ignores_previous_runs_beats(telemetry_dir):
    """A reused telemetry dir must not blame phantom ranks from an
    earlier run: beats are run_id-stamped and filtered."""
    now = time.time()
    os.makedirs(telemetry_dir, exist_ok=True)
    stale = {"rank": 7, "time": now - 3600.0, "step": 10, "phase": "train",
             "run_id": "dead-run"}
    with open(os.path.join(telemetry_dir, "heartbeat_rank7.json"), "w") as fh:
        json.dump(stale, fh)
    w = heartbeat.HeartbeatWriter(telemetry_dir, rank=0, min_interval_s=0.0)
    w.beat(step=1, phase="train")
    behind = heartbeat.attribute_stall(
        telemetry_dir, stale_after_s=2.0, now=now, run_id=w.run_id
    )
    assert behind == []  # rank 7 belongs to "dead-run", rank 0 is fresh
    assert 7 not in heartbeat.read(telemetry_dir, run_id=w.run_id)
    assert 7 in heartbeat.read(telemetry_dir)  # unscoped read still sees it


def test_attribute_stall_ignores_done_ranks(telemetry_dir):
    w = heartbeat.HeartbeatWriter(telemetry_dir, rank=0, min_interval_s=0.0)
    w.beat(step=3)
    w.close()
    behind = heartbeat.attribute_stall(
        telemetry_dir, stale_after_s=0.0, now=time.time() + 100.0
    )
    assert behind == []


def test_goodput_math():
    g = heartbeat.GoodputMeter()
    g.account("compile", 2.0)
    g.account("productive", 6.0)
    g.account("checkpoint", 1.0)
    g.account("productive", 1.0)
    s = g.summary()
    assert s["total_s"] == pytest.approx(10.0)
    assert s["goodput"] == pytest.approx(0.7)
    assert s["seconds"]["compile"] == pytest.approx(2.0)
    assert heartbeat.GoodputMeter().goodput() is None


def test_goodput_measure_context():
    g = heartbeat.GoodputMeter()
    with g.measure("productive"):
        time.sleep(0.02)
    assert g.seconds["productive"] >= 0.015


# ------------------------------------------- stall attribution (watchdog)


def test_watchdog_attributes_chaos_delayed_rank(telemetry_dir, monkeypatch):
    """The acceptance scenario: a TPU_DIST_CHAOS-delayed rank stops
    heartbeating, and the watchdog's stall event names THAT rank within
    the watchdog timeout."""
    from tpu_dist.resilience import chaos
    from tpu_dist.utils.debug import collective_watchdog

    monkeypatch.setenv(chaos.ENV_VAR, "delay=1:1.5")
    stop = threading.Event()

    def rank_loop(rank: int):
        w = heartbeat.HeartbeatWriter(telemetry_dir, rank=rank,
                                      min_interval_s=0.0)
        chaos.at_launch(rank)  # rank 1 sleeps 1.5s here (the injection)
        while not stop.is_set():
            w.beat(step=1, phase="train")
            time.sleep(0.02)

    threads = [
        threading.Thread(target=rank_loop, args=(r,), daemon=True)
        for r in (0, 1)
    ]
    for t in threads:
        t.start()
    time.sleep(0.3)  # both start files exist; rank 1 is asleep in chaos
    try:
        with collective_watchdog(
            timeout_s=0.4, what="test-collective",
            telemetry_dir=telemetry_dir,
        ) as fired:
            time.sleep(0.7)  # overrun: the watchdog must fire
        assert fired.is_set()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=3)
    stalls = [
        r for r in events.read_events(telemetry_dir) if r["event"] == "stall"
    ]
    assert stalls, "watchdog fired but no stall event was emitted"
    behind_ranks = {e["rank"] for e in stalls[0]["ranks_behind"]}
    assert 1 in behind_ranks, "the chaos-delayed rank must be attributed"
    assert 0 not in behind_ranks, "the healthy rank must not be blamed"
    # chaos injection itself is on the record too
    chaos_evs = [
        r for r in events.read_events(telemetry_dir) if r["event"] == "chaos"
    ]
    assert any("delay=1:1.5" in c["clause"] for c in chaos_evs)


# ------------------------------------------------------- retry event wiring


def test_retry_call_emits_retry_events(telemetry_dir):
    from tpu_dist.resilience.retry import RetryPolicy, retry_call

    calls = []

    def flaky(attempt):
        calls.append(attempt)
        if attempt < 2:
            raise OSError(f"boom {attempt}")
        return "ok"

    out = retry_call(
        flaky,
        policy=RetryPolicy(max_attempts=3, base_delay=0.01, jitter=0.0),
        describe="test-rendezvous",
        log=lambda s: None,
        sleep=lambda s: None,
    )
    assert out == "ok"
    retries = [
        r for r in events.read_events(telemetry_dir) if r["event"] == "retry"
    ]
    assert len(retries) == 2
    assert retries[0]["what"] == "test-rendezvous"
    assert retries[0]["attempt"] == 1
    assert "boom 0" in retries[0]["error"]
    n, errors = events.validate_dir(telemetry_dir)
    assert errors == []


# --------------------------------------------- trainer wiring (end-to-end)


@pytest.fixture(scope="module")
def mesh8():
    from tpu_dist import comm

    return comm.make_mesh(8, ("data",), platform="cpu")


def _fit_with_telemetry(telemetry_dir, mesh, tmp_path):
    from tpu_dist import data, models, train

    ds = data.load_mnist("train", synthetic_size=512)
    cfg = train.TrainConfig(
        epochs=2, nan_guard=True, loss_scale=None, log=lambda s: None
    )
    t = train.Trainer(models.mnist_net(), models.IN_SHAPE, mesh, cfg)
    return t.fit(ds, checkpoint_dir=str(tmp_path / "ckpt"))


def test_trainer_telemetry_end_to_end(telemetry_dir, mesh8, tmp_path):
    """The acceptance run: CPU-sim Trainer fit with TPU_DIST_TELEMETRY
    set → events.jsonl validates, manifest + step schema complete,
    spans saved, heartbeat closed, tpu_top renders."""
    history = _fit_with_telemetry(telemetry_dir, mesh8, tmp_path)
    assert len(history) == 2

    n, errors = events.validate_dir(telemetry_dir)
    assert errors == [], errors[:10]
    recs = events.read_events(telemetry_dir)
    by_kind = {}
    for r in recs:
        by_kind.setdefault(r["event"], []).append(r)

    man = by_kind["manifest"][0]
    assert man["world"] == 8
    assert man["config"]["nan_guard"] is True
    assert man["mesh"]["axis_names"] == ["data"]
    assert man["platform"]["backend"] == "cpu"
    assert man["platform"]["device_count"] >= 8

    steps = by_kind["step"]
    assert len(steps) == 8  # 512 samples / 128 batch * 2 epochs
    for s in steps:
        for key in events.STEP_REQUIRED:
            assert key in s
        assert s["loss"] > 0 and s["step_time"] > 0
        assert s["samples_per_sec_per_chip"] > 0
        assert s["bad_steps"] == 0  # guard on, healthy run
        # CPU-sim has no known peak: mfu is present-but-null; hbm is
        # present and backend-dependent (null or a stats dict)
        assert s["mfu"] is None
        assert s["hbm"] is None or isinstance(s["hbm"], dict)
    assert steps[-1]["step"] == 8

    epochs = by_kind["epoch"]
    assert len(epochs) == 2
    g = epochs[-1]["goodput"]
    assert 0.0 < g["goodput"] <= 1.0
    assert g["seconds"]["compile"] > 0  # first step accounted as compile
    assert g["seconds"]["checkpoint"] > 0
    assert len(by_kind["checkpoint"]) == 2

    # spans: chrome-trace JSON with step-correlated host phases, using
    # the SAME step ids as the step records (the perfetto join key)
    trace = json.load(open(os.path.join(telemetry_dir, "spans_rank0.trace.json")))
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"data_next", "dispatch", "readback"} <= names
    span_steps = {
        e["args"]["step"]
        for e in trace["traceEvents"]
        if e["name"] == "dispatch"
    }
    assert span_steps == {s["step"] for s in steps}

    # heartbeat closed as done
    assert heartbeat.read(telemetry_dir)[0]["phase"] == "done"

    # tpu_top renders the dir
    tpu_top = _load_tpu_top()
    out = tpu_top.render(tpu_top.collect(telemetry_dir))
    assert man["run_id"] in out
    assert "step 8" in out
    assert "loss" in out


def _load_tpu_top():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tpu_top",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "tpu_top.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tpu_top_incremental_tail(telemetry_dir):
    """Live-mode frames parse only appended lines; a torn tail line is
    deferred to the next poll."""
    tpu_top = _load_tpu_top()
    log = events.from_env()
    log.emit("warning", reason="one")
    tail = tpu_top.EventTail(telemetry_dir)
    state = tpu_top.empty_state(telemetry_dir)
    tpu_top.update(state, tail.poll())
    assert state["counts"]["warning"] == 1
    assert tail.poll() == []  # nothing new → nothing re-parsed
    log.emit("warning", reason="two")
    # torn (unterminated) line must not be consumed yet
    with open(log.path, "a") as fh:
        fh.write('{"event": "warning", "time": 1, "ran')
    new = tail.poll()
    assert [r["reason"] for r in new] == ["two"]
    with open(log.path, "a") as fh:
        fh.write('k": 0, "run_id": "x", "reason": "three"}\n')
    assert [r["reason"] for r in tail.poll()] == ["three"]
    tpu_top.update(state, new)
    assert state["counts"]["warning"] == 2


def test_lm_trainer_telemetry(telemetry_dir):
    from tpu_dist import comm, train
    from tpu_dist.models.transformer_lm import TransformerLM, synthetic_tokens

    mesh = comm.make_mesh(4, ("data",), platform="cpu")
    lm = TransformerLM(vocab=64, dim=32, heads=2, depth=1, max_seq=16)
    windows = synthetic_tokens(32, 16, vocab=64)
    cfg = train.LMTrainConfig(
        epochs=1, global_batch=16, log=lambda s: None
    )
    trainer = train.LMTrainer(lm, mesh, cfg)
    trainer.fit(windows)
    recs = events.read_events(telemetry_dir)
    steps = [r for r in recs if r["event"] == "step"]
    assert steps and all("tokens_per_sec_per_chip" in s for s in steps)
    man = [r for r in recs if r["event"] == "manifest"][0]
    assert man["trainer"] == "LMTrainer"
    n, errors = events.validate_dir(telemetry_dir)
    assert errors == []


def test_spmd_results_become_events(telemetry_dir):
    import jax.numpy as jnp

    from tpu_dist import comm

    out = comm.spmd(
        lambda: comm.all_reduce(
            comm.rank("ranks") + jnp.float32(1), comm.ReduceOp.SUM, "ranks"
        ),
        world=4,
        platform="cpu",
    )
    assert out.shape[0] == 4
    recs = [
        r for r in events.read_events(telemetry_dir)
        if r["event"] == "spmd_result"
    ]
    assert [r["spmd_rank"] for r in recs] == [0, 1, 2, 3]
    # sum of rank+1 over 4 ranks = 10, identical on every rank
    assert all(r["summary"]["."] == 10.0 for r in recs)


def test_crashed_fit_still_flushes_telemetry(telemetry_dir, mesh8):
    """A fit that raises must still save the span trace and close the
    heartbeat as 'crashed' (attributable, not silently stale)."""
    from tpu_dist import data, models, train

    ds = data.load_mnist("train", synthetic_size=512)
    t = train.Trainer(
        models.mnist_net(), models.IN_SHAPE, mesh8,
        train.TrainConfig(epochs=1, log=lambda s: None),
    )
    real_step = t.step
    calls = []

    def exploding_step(*args):
        if calls:
            raise RuntimeError("injected mid-fit failure")
        calls.append(1)
        return real_step(*args)

    t.step = exploding_step
    with pytest.raises(RuntimeError, match="injected"):
        t.fit(ds)
    assert os.path.exists(os.path.join(telemetry_dir, "spans_rank0.trace.json"))
    assert heartbeat.read(telemetry_dir)[0]["phase"] == "crashed"
    # a crashed rank stays attributable (unlike a 'done' one)
    behind = heartbeat.attribute_stall(
        telemetry_dir, stale_after_s=0.0, now=time.time() + 60.0
    )
    assert [e["rank"] for e in behind] == [0]


def test_telemetry_off_leaves_no_files(tmp_path, monkeypatch, mesh8):
    """The opt-out default: no env var, no files, trainers unaffected."""
    monkeypatch.delenv(events.ENV_DIR, raising=False)
    history = _fit_with_telemetry(None, mesh8, tmp_path)
    assert len(history) == 2


# ---------------------------------------------------- bench persistence


def test_bench_persist_event(tmp_path, monkeypatch):
    import bench

    path = bench.persist_event(
        {"event": "warning", "reason": "cpu_fallback", "detail": "probe hung"},
        root=str(tmp_path / "results"),
    )
    rec = json.loads(open(path).read().strip())
    assert rec["reason"] == "cpu_fallback"
    assert "provenance" in rec and rec["provenance"]["backend"] == "cpu"
    # appends, not truncates
    bench.persist_event({"event": "bench", "metric": "m", "value": 1.0},
                        root=str(tmp_path / "results"))
    assert len(open(path).read().strip().splitlines()) == 2


# ----------------------------------------------------- metrics satellites


def test_step_timer_nan_when_empty():
    import math

    from tpu_dist.train.metrics import StepTimer

    assert math.isnan(StepTimer().samples_per_sec(128))
