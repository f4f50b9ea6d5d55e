"""Set-up under the program's own spans (CPU): JAX's compile stages on the
span ring by program name, the kept class the ring's wrap cannot touch,
and the construction spans of the models, the trainer and the engine."""

import collections
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src import monitoring

from tpu_dist import models, parallel, serve, train
from tpu_dist.observe import compile_spans, spans
from tpu_dist.observe.registry import REGISTRY

REPO = Path(__file__).resolve().parents[1]
STAGES = ("compile.trace", "compile.lower", "compile.backend")


@pytest.fixture(autouse=True)
def listening():
    # another test of this process may have cleared jax.monitoring's listeners
    compile_spans.install()


def _stages(since: float, fun: str) -> dict:
    return {s.name: s for s in spans.kept(since)
            if s.name in STAGES and s.attrs.get("fun") == fun}


def test_a_jitted_function_leaves_its_three_stages_by_name_on_perf_counter():
    @jax.jit
    def a_program_of_this_test(x):
        return jnp.tanh(x) * 3.0

    x = jnp.ones((7,))
    with spans.span("engine.decode_dispatch") as paid:
        t0 = time.perf_counter()
        a_program_of_this_test(x)
        t1 = time.perf_counter()
    got = _stages(t0, "a_program_of_this_test")
    assert sorted(got) == sorted(STAGES)
    for s in got.values():
        # the ring's clock, inside the call's own two readings of it
        assert t0 <= s.start <= s.end <= t1
        assert s.parent == paid.id and s.keep
    assert got["compile.trace"].end <= got["compile.lower"].start
    assert got["compile.lower"].end <= got["compile.backend"].start
    assert got["compile.backend"].attrs["cache"] in ("hit", "miss", "off")
    # the second call compiles nothing
    t2 = time.perf_counter()
    a_program_of_this_test(x)
    assert _stages(t2, "a_program_of_this_test") == {}


def test_only_the_outermost_stage_on_a_thread_is_a_span():
    """JAX reports the trace of every jitted function a program calls
    inside the program's own trace; those are part of the program's."""
    @jax.jit
    def an_inner_of_this_test(x):
        return jnp.where(x > 0, x, 0.0)

    @jax.jit
    def an_outer_of_this_test(x):
        return jax.nn.softmax(an_inner_of_this_test(x)) @ jnp.ones((5, 5))

    seen = []

    def listen(event, seconds, **kw):
        if event in compile_spans.STAGES:
            seen.append(kw.get("fun_name"))

    x = jnp.ones((5, 5))
    monitoring.register_event_duration_secs_listener(listen)
    trace_s = REGISTRY.counter("tpu_dist_compile_seconds_total")
    before = trace_s.value(stage="trace")
    try:
        t0 = time.perf_counter()
        an_outer_of_this_test(x)
        t1 = time.perf_counter()
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert "an_inner_of_this_test" in seen  # JAX did report the inner trace
    since = [s for s in spans.kept(t0) if s.name in STAGES]
    assert [s.attrs["fun"] for s in since] == ["an_outer_of_this_test"] * 3
    # so the spans of a thread never overlap and their seconds add
    assert all(a.end <= b.start for a, b in zip(since, since[1:]))
    assert sum(s.end - s.start for s in since) <= t1 - t0
    traced = trace_s.value(stage="trace") - before
    assert traced == pytest.approx(since[0].end - since[0].start)


def test_a_stage_whose_start_was_not_heard_is_still_a_span():
    t0 = time.perf_counter()
    compile_spans._on_duration(
        "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.25, fun_name="jit(late)")
    (sp,) = _stages(t0 - 1.0, "late").values()
    assert sp.name == "compile.lower" and sp.end - sp.start == pytest.approx(0.25)
    assert compile_spans._thread.depth == 0


def test_the_cache_says_hit_with_what_it_saved_and_off_without_it():
    t0 = time.perf_counter()
    hits = REGISTRY.counter("tpu_dist_compile_cache_hits_total")
    before = hits.value()
    backend = "/jax/core/compile/backend_compile_duration"
    compile_spans._on_enter(backend, 0.0, fun_name="jit(loaded)")
    compile_spans._on_cache("/jax/compilation_cache/cache_hits")
    compile_spans._on_duration("/jax/compilation_cache/compile_time_saved_sec", 41.0)
    compile_spans._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    compile_spans._on_duration(backend, 0.6, fun_name="jit(loaded)")
    compile_spans._on_enter(backend, 0.0, fun_name="jit(plain)")
    compile_spans._on_duration(backend, 0.1, fun_name="jit(plain)")
    (hit,) = _stages(t0 - 1.0, "loaded").values()
    (off,) = _stages(t0 - 1.0, "plain").values()
    assert hit.attrs == {"fun": "loaded", "cache": "hit", "saved_s": 41.0, "load_s": 0.5}
    assert off.attrs == {"fun": "plain", "cache": "off"}
    assert hits.value() == before + 1


CACHED = """
import json, sys, time
import jax, jax.numpy as jnp
import tpu_dist
from tpu_dist.observe import spans
from tpu_dist.utils.platform import setup_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
setup_compile_cache()
@jax.jit
def cached_program(x):
    return jnp.cos(x) @ x.T
cached_program(jnp.ones((8, 8))).block_until_ready()
print(json.dumps([s.attrs for s in spans.kept()
                  if s.name == "compile.backend" and s.attrs["fun"] == "cached_program"]))
"""


def test_the_second_process_reads_a_hit_where_the_first_read_a_miss(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=str(REPO))
    env.pop("TPU_DIST_TELEMETRY", None)
    got = []
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", CACHED], env=env, capture_output=True,
                              text=True, timeout=300, cwd=tmp_path)
        assert proc.returncode == 0, proc.stderr[-2000:]
        (attrs,) = json.loads(proc.stdout.splitlines()[-1])
        got.append(attrs)
    assert got[0] == {"fun": "cached_program", "cache": "miss"}
    assert got[1]["cache"] == "hit" and got[1]["load_s"] > 0.0 and "saved_s" in got[1]


def test_install_is_idempotent_and_repairs_a_cleared_listener():
    compile_spans.install()
    compile_spans.install()
    for held, mine in (
        (monitoring.get_scalar_listeners, compile_spans._on_enter),
        (monitoring.get_event_listeners, compile_spans._on_cache),
        (monitoring.get_event_duration_listeners, compile_spans._on_duration),
    ):
        assert held().count(mine) == 1
    monitoring.unregister_event_duration_listener(compile_spans._on_duration)
    assert compile_spans._on_duration not in monitoring.get_event_duration_listeners()
    compile_spans.install()
    assert monitoring.get_event_duration_listeners().count(compile_spans._on_duration) == 1


def test_kept_spans_outlast_the_rings_wrap():
    t0 = time.perf_counter()
    with spans.span("trainer.init", keep=True) as built:
        stage = spans.record("compile.backend", t0, t0 + 0.5, keep=True, nest=True, fun="f")
    assert stage.parent == built.id
    assert {s.id for s in spans.recent(t0)} >= {built.id, stage.id}
    for i in range(70_000):  # more than the ring holds
        with spans.span("engine.step", step=i):
            pass
    assert len(spans.recent()) == spans.RING_SIZE
    assert not {built.id, stage.id} & {s.id for s in spans.recent()}
    assert [s.id for s in spans.kept(t0)] == [stage.id, built.id]
    assert not spans.complete_since(t0)
    # a hot span never reaches the kept ring
    assert all(s.keep for s in spans.kept())


def test_record_takes_the_open_span_as_parent_only_when_asked():
    with spans.span("engine.step") as step:
        now = time.perf_counter()
        inside = spans.record("compile.trace", now, now, keep=True, nest=True, fun="g")
        queued = spans.record("request.queued", now - 1.0, now, request_id=3)
    alone = spans.record("compile.trace", now, now, nest=True, fun="g")
    assert inside.parent == step.id and queued.parent is None and alone.parent is None
    assert queued.attrs == {"request_id": 3} and not queued.keep and not alone.keep


def test_save_draws_a_kept_span_once_in_the_ring_and_past_it(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=4))
    monkeypatch.setattr(spans, "_kept", collections.deque(maxlen=8))
    rec = spans.SpanRecorder(str(tmp_path / "t.trace.json"))
    with spans.span("engine.init", keep=True) as built:
        pass
    with spans.span("engine.step", step=0):
        pass

    def names():
        return [e["name"] for e in json.load(open(rec.save()))["traceEvents"]]

    assert names() == ["engine.init", "engine.step"]  # held by both rings, drawn once
    for i in range(1, 6):
        with spans.span("engine.step", step=i):
            pass
    assert built.id not in {s.id for s in spans.recent()}
    assert names() == ["engine.init"] + ["engine.step"] * 4


def _by_id() -> dict:
    return {s.id: s for s in spans.kept()}


def _children(parent) -> list[str]:
    return [s.name for s in spans.kept() if s.parent == parent.id and s.name not in STAGES]


def test_a_trainers_construction_is_under_kept_spans():
    lm = models.TransformerLM(vocab=64, dim=32, depth=1, heads=2, max_seq=16)
    mesh = parallel.build_mesh("fsdp=2", mesh_devices=jax.devices()[:2])
    t0 = time.perf_counter()
    train.LMTrainer(lm, mesh, train.LMTrainConfig(global_batch=4, mesh_axes="fsdp=2"))
    built = {s.name: s for s in spans.kept(t0) if s.name not in STAGES}
    assert set(built) == {"trainer.init", "model.init", "trainer.place_state",
                          "partition.place_params", "partition.init_opt"}
    root = built["trainer.init"]
    assert root.parent is None and sorted(_children(root)) == ["model.init", "trainer.place_state"]
    assert sorted(_children(built["trainer.place_state"])) == [
        "partition.init_opt", "partition.place_params"]
    params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(lm.init(jax.random.key(0))[0]))
    assert built["model.init"].attrs == {"model": "TransformerLM", "params": params}
    assert built["partition.place_params"].attrs == {"bytes": 4 * params}
    # the optimizer's init is compiled under the span that pays for it
    assert any(s.name == "compile.backend" and s.parent == built["partition.init_opt"].id
               for s in spans.kept(t0))


def test_a_replicated_trainer_places_its_state_under_the_same_span():
    from tpu_dist import comm

    lm = models.TransformerLM(vocab=64, dim=32, depth=1, heads=2, max_seq=16)
    mesh = comm.make_mesh(2, ("data",), mesh_devices=jax.devices()[:2])
    t0 = time.perf_counter()
    # a loss scale keeps the trainer off the partition engine
    tr = train.LMTrainer(lm, mesh, train.LMTrainConfig(global_batch=4, loss_scale=2.0**10,
                                                       nan_guard=True))
    assert not tr._engine_mode
    built = [s.name for s in spans.kept(t0) if s.name not in STAGES]
    assert built == ["model.init", "trainer.place_state", "trainer.init"]


def test_an_engines_construction_is_under_kept_spans():
    lm = models.TransformerLM(vocab=64, dim=32, depth=1, heads=2, max_seq=32)
    params, _ = lm.init(jax.random.key(0))
    t0 = time.perf_counter()
    eng = serve.ServeEngine(
        lm, params, serve.ServeConfig(max_batch=2, num_blocks=8, block_size=4, max_seq=16))
    built = {s.name: s for s in spans.kept(t0) if s.name not in STAGES}
    assert set(built) == {"engine.init", "engine.init_cache"}
    assert built["engine.init_cache"].parent == built["engine.init"].id
    assert built["engine.init_cache"].attrs == {
        "kv_bytes": eng.kv_pool_bytes, "state_bytes": eng.state_bytes}
    assert eng.kv_pool_bytes > 0 and eng.state_bytes == 0
    # the first request compiles the engine's programs under the dispatch that pays
    t1 = time.perf_counter()
    eng.submit(np.zeros((3,), np.int32), 3)
    eng.run_until_drained()
    ring = {s.id: s for s in spans.recent(t1)}
    paid = {s.attrs["fun"]: ring[s.parent].name for s in spans.kept(t1)
            if s.name == "compile.backend"}
    assert paid["serve_prefill"] == "engine.prefill_dispatch"
    assert paid["serve_decode_greedy"] == "engine.decode_dispatch"


@pytest.mark.parametrize("family", ["transformer", "hybrid"])
def test_a_subclass_that_draws_its_own_weights_is_under_model_init(family):
    """The benchmark's families override `init` with a seeded generator."""
    if family == "transformer":
        base, kw = models.TransformerLM, dict(vocab=32, dim=16, depth=1, heads=2, max_seq=8)
    else:
        from tpu_dist.models.hybrid_lm import HybridLM

        base, kw = HybridLM, dict(
            vocab=32, dim=16, layer_types=["attention"], heads=2, kv_heads=1, n_experts=2,
            experts_per_token=1, expert_width=8, shared_width=8, max_seq=8)

    class Seeded(base):
        def init(self, key=None, input_shape=None):
            return {"w": jnp.zeros((3, 5))}, {}

    t0 = time.perf_counter()
    Seeded(**kw).init()
    (sp,) = [s for s in spans.kept(t0) if s.name == "model.init"]
    assert sp.attrs == {"model": "Seeded", "params": 15}
    t1 = time.perf_counter()
    base(**kw).init(jax.random.key(1))
    (own,) = [s for s in spans.kept(t1) if s.name == "model.init"]
    assert own.attrs["model"] == base.__name__ and own.attrs["params"] > 15
