"""`chipbench.scopes` (CPU): device time by program and by the program's own
scopes, on a trace recorded on the chip.

`chipbench/fixtures/scoped_tpu.xplane.pb`: three rounds of two small jitted
programs on one v5e chip, `fixture_serve` (scopes `attn/kv_gather`,
`attn/scores`) under `tpu_dist.observe.spans`' `engine.step` and its phases,
and `fixture_train` (`block/attn`, `block/mlp`, forward and backward) under a
`dispatch` span; the profiler's Python tracer off, as in the harness.
"""

from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import scopes, xplane

REPO = Path(__file__).resolve().parents[2]
FIXTURE = str(REPO / "chipbench/fixtures/scoped_tpu.xplane.pb")
OLD_FIXTURE = str(REPO / "chipbench/fixtures/small_tpu.xplane.pb")


@pytest.fixture(scope="module")
def table():
    return scopes.table(FIXTURE)


@pytest.mark.parametrize("path", [FIXTURE, OLD_FIXTURE])
def test_the_by_scope_table_sums_to_the_reducers_total(path):
    devices, _ = xplane.load(path)
    want = sum(xplane.self_times(devices[sorted(devices)[0]]).values())
    t = scopes.table(path)
    assert t["total_self_s"] == pytest.approx(want, rel=1e-9) and want > 0
    assert sum(s for *_, s in t["by_scope"]) == pytest.approx(want, rel=1e-9)


def test_both_programs_are_found_by_name(table):
    assert {name for name, _ in table["programs"]} == {"fixture_train", "fixture_serve"}
    assert all(s > 0 for _, s in table["programs"])
    assert {prog for prog, *_ in table["by_scope"]} == {"fixture_train", "fixture_serve"}


def test_every_scope_is_found_and_forward_is_split_from_backward(table):
    rows = {(prog, scope, which): s for prog, scope, which, s in table["by_scope"]}
    for key in [("fixture_serve", "attn/kv_gather", "fwd"),
                ("fixture_serve", "attn/scores", "fwd"),
                ("fixture_train", "block/attn", "fwd"),
                ("fixture_train", "block/attn", "bwd"),
                ("fixture_train", "block/mlp", "fwd"),
                ("fixture_train", "block/mlp", "bwd")]:
        assert rows.get(key, 0.0) > 0, key
    assert not any(which == "bwd" for prog, _, which in rows if prog == "fixture_serve")
    assert not any(which == "remat" for _, _, which in rows)
    # every scope here is the operation's own: nothing was charged by a fallback rule
    assert 75.0 < table["scoped_share_pct"] <= 100.0 and table["fallback_share_pct"] == 0.0


def test_every_run_of_a_program_keeps_its_own_duration(table):
    runs = table["program_runs"]
    assert set(runs) == {"fixture_train", "fixture_serve"}
    assert all(len(r) == 3 and min(r) > 0 for r in runs.values())   # three rounds
    assert {n: pytest.approx(sum(r)) for n, r in runs.items()} == dict(table["programs"])
    assert table["kernels"] == []   # plain XLA programs: no Pallas kernel in them


def test_a_familys_scope_joins_the_vocabulary_and_counts_as_own(table):
    """A vocabulary entry brought from outside (a family file's `SCOPES`):
    the time under it is the operation's own scope, not `(unscoped)` and
    not a fallback's, and nothing else of the table moves."""
    family = SimpleNamespace(SCOPES=("attn/scores/div", "attn/scores"), KERNELS=("fusion",))
    vocab, kernels = scopes.vocabulary(family, SimpleNamespace())
    assert vocab == (*scopes.SCOPES, "attn/scores/div") and kernels == (*scopes.KERNELS, "fusion")
    assert scopes.vocabulary() == (scopes.SCOPES, scopes.KERNELS)
    assert scopes.scope_of("jit(f)/attn/scores/div/mul") == "attn/scores"
    assert scopes.scope_of("jit(f)/attn/scores/div/mul", vocab) == "attn/scores/div"

    t = scopes.table(FIXTURE, families=[family])
    rows = {(prog, scope): s for prog, scope, _, s in t["by_scope"]}
    base = {(prog, scope): s for prog, scope, _, s in table["by_scope"]}
    assert rows["fixture_serve", "attn/scores/div"] > 0
    assert rows["fixture_serve", "attn/scores/div"] + rows["fixture_serve", "attn/scores"] == (
        pytest.approx(base["fixture_serve", "attn/scores"]))
    assert {how for _, scope, how, _ in t["by_op_scope"] if scope == "attn/scores/div"} == {scopes.OWN}
    assert t["scoped_share_pct"] == pytest.approx(table["scoped_share_pct"])
    assert t["fallback_share_pct"] == 0.0 and t["total_self_s"] == table["total_self_s"]
    # a kernel the family lists by its instruction's base name is found by it
    assert [name for name, *_ in t["kernels"]] == ["fusion"]


def test_idle_gaps_are_named_after_the_programs_spans(table):
    gaps = dict(table["idle_gaps"])
    assert any(name.startswith("tpu_dist/engine.") for name in gaps)
    assert "tpu_dist/dispatch" in gaps
    assert sum(gaps.values()) == pytest.approx(
        table["window_s"] - _busy_inside(table), rel=1e-6)
    other = scopes.table(FIXTURE, device_ahead_ms=1.0)
    assert other["device_ahead_ms"] == 1.0 and other["total_self_s"] == table["total_self_s"]
    # the fixture's programs are not the serving engine's: nothing bounds the clocks
    assert table["device_ahead_bounds_ms"] is None and table["device_ahead_ms"] == 0.0


def test_the_clock_offset_is_bounded_by_dispatches_and_readbacks():
    planes = scopes.read_xspace(FIXTURE)
    chip = next(p for p in planes if p.name == "/device:TPU:0")
    runs = [(scopes.program_name(chip.metas[m].name), s, d)
            for ln in chip.lines if ln.name == scopes.MODULES_LINE for m, s, d in ln.events]
    host = scopes._host_spans(planes)
    low, high = scopes.clock_bounds(
        host, runs,
        dispatches={"tpu_dist/engine.decode_dispatch": "fixture_serve",
                    "tpu_dist/dispatch": "fixture_train"},
        readbacks={"tpu_dist/engine.decode_wait": "fixture_serve"})
    # in this file the device's clock is about a millisecond BEHIND the host's:
    # each run "starts" 0.7-0.9 ms before the span that dispatched it
    assert -2.0 < low < high < -0.5
    # a span per run or nothing: with one run missing the pair says nothing
    assert scopes.clock_bounds(host, runs[2:], {"tpu_dist/dispatch": "fixture_train"},
                               {"tpu_dist/engine.decode_wait": "fixture_serve"}) is None
    # bounds that cross (the readback paired with the wrong program) are no bounds
    assert scopes.clock_bounds(host, runs, {"tpu_dist/engine.decode_dispatch": "fixture_serve"},
                               {"tpu_dist/engine.decode_wait": "fixture_train"}) is None


def _busy_inside(table) -> float:
    """Device busy seconds inside the table's window, recomputed apart."""
    devices, _ = xplane.load(FIXTURE)
    planes = scopes.read_xspace(FIXTURE)
    marks = [(s, s + d) for p in planes if p.name == xplane.HOST_PLANE
             for ln in p.lines for m, s, d in ln.events
             if p.metas[m].name.startswith(scopes.PROGRAM_SPANS)]
    lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
    shift = table["device_ahead_ms"] * 1e6
    return sum(max(0.0, min(b - shift, hi) - max(a - shift, lo))
               for a, b in xplane.busy_intervals(devices[sorted(devices)[0]])) / 1e9


def test_the_old_fixture_has_one_unnamed_program_and_no_scope():
    t = scopes.table(OLD_FIXTURE)
    assert [name for name, _ in t["programs"]] == ["_lambda"]
    assert t["scoped_share_pct"] == 0.0 and t["fallback_share_pct"] == 0.0
    assert {name for name, _ in t["idle_gaps"]} <= {"chipbench/step", "chipbench/idle_wait",
                                                    "unannotated"}


@pytest.mark.parametrize("op_name,scope,which", [
    ("jit(serve_prefill)/attn/kv_gather/gather", "attn/kv_gather", "fwd"),
    ("jit(train_step)/grad_accum/while/body/closed_call/jvp(block/mlp)/dot_general",
     "block/mlp", "fwd"),
    ("jit(train_step)/grad_accum/while/body/closed_call/transpose(jvp(block/attn))/mul",
     "block/attn", "bwd"),
    ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/block/attn/jit(_var)/mul",
     "block/attn", "remat"),
    ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/block/mlp/dot_general", "block/mlp", "bwd"),
    ("jit(train_step)/grad_accum/while/body/closed_call/convert_element_type", "grad_accum", "fwd"),
    ("jit(train_step)/optimizer/mul;jit(train_step)/optimizer/add", "optimizer", "fwd"),
    ("jit(serve_decode_greedy)/mlp/dot_general", "mlp", "fwd"),
    ("cache[17]['k']", "arg:cache", "fwd"),
    ("jit(f)/dot_general", None, "fwd"),
    ("gather", None, "fwd"),
])
def test_a_scope_and_a_pass_are_read_from_an_op_name(op_name, scope, which):
    assert scopes.scope_of(op_name) == scope
    assert scopes.pass_of(op_name) == which


def _msg(*fields) -> bytes:
    """A protobuf message from (number, value): an int is a varint, bytes
    or str a length-delimited field."""
    def varint(v: int) -> bytes:
        out = bytearray()
        while True:
            out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
            v >>= 7
            if not v:
                return bytes(out)
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += varint(num << 3) + varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += varint(num << 3 | 2) + varint(len(val)) + val
    return out


def _plane(name: str, lines: dict, tf_ops: dict | None = None) -> bytes:
    """An ``XPlane``: {line: [(event name, start us, dur us)]}; events of
    the ``XLA Ops`` line carry ``program_id`` 7 and the ``tf_op`` given."""
    names = sorted({ev for evs in lines.values() for ev, _, _ in evs})
    ids = {ev: i + 1 for i, ev in enumerate(names)}
    metas = []
    for ev, i in ids.items():
        stats = []
        if name.startswith("/device"):
            stats.append((5, _msg((1, 1), (3, 7))))
            if (tf_ops or {}).get(ev):
                stats.append((5, _msg((1, 2), (5, tf_ops[ev]))))
        metas.append((4, _msg((1, i), (2, _msg((1, i), (2, ev), *stats)))))
    stat_names = [(5, _msg((1, i), (2, _msg((1, i), (2, n)))))
                  for i, n in ((1, "program_id"), (2, "tf_op"))]
    body = [(3, _msg((2, line), (3, 0), *[
        (4, _msg((1, ids[ev]), (2, int(start * 1e6)), (3, int(dur * 1e6))))
        for ev, start, dur in evs])) for line, evs in lines.items()]
    return _msg((2, name), *body, *metas, *stat_names)


def test_time_charged_by_a_fallback_rule_is_counted_apart(tmp_path):
    """One run of ``serve_decode_greedy`` on a hand-made trace: 40 us under
    the scatter's own scope, 30 us in a copy of an argument, 20 us in a copy
    that reads the scatter, 10 us in an instruction of the compiler's own."""
    scatter = "%fusion.1 = bf16[8]{0} fusion(%p.0), kind=kLoop"
    arg_copy = "%copy.2 = bf16[8]{0} copy(%p.1)"
    out_copy = "%copy.3 = bf16[8]{0} copy(%fusion.1)"
    bare = "%bitcast.4 = bf16[8]{0} bitcast(%p.2)"
    device = _plane("/device:TPU:0", {
        "XLA Modules": [("jit_serve_decode_greedy(7)", 1000, 100)],
        "XLA Ops": [(scatter, 1000, 40), (arg_copy, 1040, 30), (out_copy, 1070, 20),
                    (bare, 1090, 10)],
    }, tf_ops={scatter: "jit(serve_decode_greedy)/attn/kv_scatter/scatter",
               arg_copy: "cache[3]['k']"})
    host = _plane("/host:CPU", {"main": [
        ("tpu_dist/engine.decode_dispatch", 1500, 300),   # the device's clock is behind
        ("tpu_dist/engine.decode_wait", 1800, 400),
    ]})
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host)))
    t = scopes.table(str(path))
    assert t["total_self_s"] == pytest.approx(100e-6)
    assert t["scoped_share_pct"] == pytest.approx(40.0)
    assert t["fallback_share_pct"] == pytest.approx(50.0)
    rows = {(op, scope): (how, s) for op, scope, how, s in t["by_op_scope"]}
    assert rows["fusion", "attn/kv_scatter"][0] == scopes.OWN
    assert rows["copy", "attn/kv_scatter"] == (scopes.FALLBACK, pytest.approx(20e-6))
    assert rows["copy", "arg:cache"][0] == scopes.FALLBACK
    assert rows["bitcast", scopes.UNSCOPED][0] == scopes.UNSCOPED
    # run 1000..1100 us, dispatched from 1500 us, read back by 2200 us
    assert t["device_ahead_bounds_ms"] == pytest.approx([-1.1, -0.5])
    assert t["device_ahead_ms"] == pytest.approx(-0.8)
    assert "charged by a fallback rule" in scopes.render(t) and "copy / arg:cache *" in scopes.render(t)


def test_named_kernels_are_counted_by_their_pallas_call_name(tmp_path):
    """Two calls of a kernel named by `pl.pallas_call(name=)` (the entry
    before ``pallas_call`` on its ``tf_op`` path), whatever the vocabulary
    lists, beside a fusion; on a host with four chips one plane is read."""
    kernel = [f"%flash_fwd.{i} = bf16[8]{{0}} custom-call(%p.0), custom_call_target=\"tpu_custom_call\""
              for i in (1, 2)]
    fusion = "%fusion.3 = bf16[8]{0} fusion(%p.1), kind=kLoop"
    path_of = "jit(train_step)/jvp(block/attn)/jit(flash_attention)/flash_fwd/pallas_call"
    device = _plane("/device:TPU:0", {
        "XLA Modules": [("jit_train_step(7)", 1000, 100), ("jit_train_step(7)", 1200, 60)],
        "XLA Ops": [(kernel[0], 1000, 40), (fusion, 1040, 60), (kernel[1], 1200, 60)],
    }, tf_ops={kernel[0]: path_of, kernel[1]: path_of, fusion: "jit(train_step)/jvp(block/mlp)/mul"})
    other = _plane("/device:TPU:1", {"XLA Ops": [(fusion, 1000, 500)]})
    path = tmp_path / "kernels.xplane.pb"
    path.write_bytes(_msg((1, other), (1, device)))
    assert [name for name, _ in scopes.raw_planes(str(path))] == ["/device:TPU:1", "/device:TPU:0"]
    t = scopes.table(str(path))
    assert t["chip"] == "/device:TPU:0"
    assert t["kernels"] == [["flash_fwd", pytest.approx(100e-6), 2]]
    assert t["program_runs"] == {"train_step": [pytest.approx(100e-6), pytest.approx(60e-6)]}
    assert {(p, sc): s for p, sc, _, s in t["by_scope"]} == {
        ("train_step", "block/attn"): pytest.approx(100e-6),
        ("train_step", "block/mlp"): pytest.approx(60e-6)}
    assert "named kernels" in scopes.render(t) and "flash_fwd" in scopes.render(t)
    assert scopes.kernel_of(scopes.Meta("%copy.1 = bf16[8] copy(%p)", {"tf_op": "a/b"})) is None


def test_the_wire_format_reader_agrees_with_profile_data():
    from jax.profiler import ProfileData

    planes = {p.name: p for p in scopes.read_xspace(FIXTURE)}
    for plane in ProfileData.from_file(FIXTURE).planes:
        mine = planes[plane.name]
        for line, got in zip(plane.lines, mine.lines):
            assert line.name == got.name
            want = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            assert [(mine.metas[m].name, s, d) for m, s, d in got.events] == want
    chip = planes["/device:TPU:0"]
    tf_ops = {m.stats.get("tf_op") for m in chip.metas.values()} - {None}
    assert any("block/attn" in op for op in tf_ops)


def test_the_entry_prints_the_table(capsys):
    assert scopes.main([FIXTURE]) == 0
    out = capsys.readouterr().out
    for word in ("fixture_train / block/attn / bwd", "fixture_serve / attn/scores / fwd",
                 "device seconds by program", "idle gaps over", "a fusion counts with its root"):
        assert word in out
    assert scopes.program_name("jit_serve_prefill(123)") == "serve_prefill"
