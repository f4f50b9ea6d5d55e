"""The benchmark's own arithmetic, manifest and traffic generator (CPU)."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import arithmetic, device_reads, harness, schedule, scopes, xplane
from chipbench.families import gpt2
from chipbench.harness import KINDS
from chipbench.manifest import NAME, UNIT, Manifest
from chipbench.peaks import PEAKS, peaks_for

REPO = Path(__file__).resolve().parents[2]
DOC = json.loads((REPO / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((REPO / c["file"]).read_text()) for c in DOC["configs"]}


# ---------------------------------------------------------------- manifest


def test_manifest_is_clean_and_small():
    assert Manifest(REPO).problems() == []
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert set(DOC) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_units_and_keys_are_of_the_allowed_characters(group):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves", "workloads"},
    }[group]
    for entry in DOC[group]:
        assert NAME.match(entry["name"]), entry["name"]
        assert set(entry) <= allowed, entry
        if "unit" in entry:
            assert UNIT.match(entry["unit"]) and " " not in entry["unit"]
            assert entry["source"] in {"device_trace", "program_span", "program_counter",
                                       "host_clock"}
        for key in ("why", "layer", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]


def test_bounds_follow_the_contract():
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.1
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}


def test_four_chip_cells_stay_inside_their_quota_and_the_check_fits():
    cells = DOC["workloads"]
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    rs = DOC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_every_cell_finds_its_files_and_reports_what_it_must(cell):
    m = Manifest(REPO)
    c = m.cell(cell)
    assert c.traffic["kind"] in KINDS
    e2e = {x["name"] for x in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "a cell reports at least one per-layer metric"
    for metric in c.per_layer:
        assert metric["moves"] in e2e
        assert callable(m.reader(metric["name"]))
    for path in DOC["paths"]:
        assert (REPO / path).is_dir()
    assert c.config["reduced"] == next(
        x["reduced"] for x in DOC["configs"] if x["name"] == c.config_name)


@pytest.mark.parametrize("name,millions", [("gpt2-medium", 354.8), ("gpt2-xl", 1557.6)])
def test_configs_hold_the_published_sizes(name, millions):
    cfg = CONFIGS[name]
    assert cfg["n_embd"] % cfg["n_head"] == 0 and cfg["n_embd"] // cfg["n_head"] == 64
    assert cfg["vocab_size"] == 50257 and cfg["n_positions"] == 1024
    assert cfg["family"] == "gpt2"
    assert gpt2.param_count(cfg) == cfg["parameters"] and gpt2.vocab_size(cfg) == 50257
    assert round(gpt2.param_count(cfg) / 1e6, 1) == millions
    assert cfg["train"]["batch_tokens"] % (1024 * cfg["train"]["micro_batch_rows"]) == 0
    sv = cfg["serve"]
    assert sv["num_blocks"] * sv["block_size"] == sv["max_batch"] * sv["max_seq"]


# -------------------------------------------------------------- arithmetic


@pytest.mark.parametrize("q", [0, 10, 50, 90, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 100])
def test_percentile_is_numpys(q, n):
    xs = np.random.default_rng(n).normal(size=n)
    assert arithmetic.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        arithmetic.percentile([], 50)


def test_peaks_table_is_exact_and_unknown_kind_is_an_error():
    assert peaks_for("TPU v5 lite").bf16_flops_per_s == 197e12
    assert peaks_for("TPU v5 lite").hbm_bytes_per_s == 819e9
    assert all(p.source for p in PEAKS.values())
    for kind in ("TPU v5", "TPU v5 lite ", "cpu", ""):
        with pytest.raises(KeyError):
            peaks_for(kind)


def test_training_flops_and_mfu():
    cfg = CONFIGS["gpt2-medium"]
    D, L, V, S = 1024, 24, 50257, 1024
    fwd = L * (24 * D * D + 4 * D * (S + 1) / 2) + 2 * V * D
    assert gpt2.forward_flops_per_token(cfg, S) == pytest.approx(fwd)
    assert arithmetic.train_flops_per_token(fwd) == pytest.approx(3 * fwd)
    # the 6N rule of thumb, to within the attention and embedding terms
    assert 3 * fwd == pytest.approx(6 * gpt2.param_count(cfg), rel=0.08)
    at_peak = 197e12 / (3 * fwd)
    assert arithmetic.mfu_pct(at_peak, 3 * fwd, 1, 197e12) == pytest.approx(100.0)
    assert arithmetic.mfu_pct(at_peak, 3 * fwd, 4, 197e12) == pytest.approx(25.0)


def test_decode_bytes_and_roofline():
    cfg = CONFIGS["gpt2-xl"]
    assert gpt2.kv_bytes_per_token(cfg, 2) == 2 * 48 * 1600 * 2 == 307200
    b = arithmetic.decode_step_bytes(2 * gpt2.param_count(cfg), 10_000, 307200)
    assert b == 2 * cfg["parameters"] + 10_000 * 307200
    assert arithmetic.hbm_roofline_pct(b, b / 819e9, 819e9) == pytest.approx(100.0)
    assert arithmetic.hbm_roofline_pct(b, 0.24, 819e9) < 5.0


@pytest.mark.parametrize("busy,state,more", [
    (0, 0, 0),                       # an architecture with no per-slot state: as before
    (32, 0, 0), (0, 37_748_736, 0),  # neither alone adds a byte
    (64, 37_748_736, 2 * 64 * 37_748_736),   # read once and written once a step
])
def test_decode_bytes_know_a_per_slot_state(busy, state, more):
    plain = arithmetic.decode_step_bytes(1e9, 10_000, 4096)
    assert plain == 1e9 + 10_000 * 4096
    assert arithmetic.decode_step_bytes(1e9, 10_000, 4096, busy, state) == plain + more


def test_attention_flops_are_a_part_of_the_forward_count():
    cfg = CONFIGS["gpt2-medium"]
    attn = gpt2.attention_flops_per_token(cfg, 1024)
    assert attn == 24 * 4 * 1024 * 1025 / 2
    assert gpt2.forward_flops_per_token(cfg, 1024) - attn == 24 * 24 * 1024**2 + 2 * 50257 * 1024


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_engine_is_sized_by_the_serve_sections_own_keys(name):
    """Every key of ``serve`` that is a field of `ServeConfig` is passed;
    for the committed configurations those are the six that were named."""
    import jax.numpy as jnp

    from chipbench.kinds import serve
    from tpu_dist.serve import ServeConfig

    sc = CONFIGS[name]["serve"]
    assert serve.engine_config(sc) == ServeConfig(
        max_batch=sc["max_batch"], block_size=sc["block_size"], num_blocks=sc["num_blocks"],
        max_seq=sc["max_seq"], prefill_chunk=sc["prefill_chunk"],
        prefill_batch=sc["prefill_batch"], cache_dtype=jnp.dtype(sc["dtype"]))
    more = serve.engine_config(dict(sc, decode_event_every=2, deployment="ignored: no field"))
    assert more.decode_event_every == 2 and more.max_batch == sc["max_batch"]


@pytest.mark.parametrize("family", Manifest(REPO).families(), ids=lambda f: Path(f.__file__).stem)
def test_every_family_file_offers_a_rehearsal_size(family):
    for cfg in CONFIGS.values():
        if Path(family.__file__).stem == cfg["family"]:
            small = dict(cfg, **{k: v for k, v in family.tiny(cfg).items()
                                 if k not in ("serve", "train", "limits")})
            assert family.param_count(small) < 5_000_000 < family.param_count(cfg)
    assert isinstance(scopes.vocabulary(family)[0], tuple)


# ------------------------------------------------ the device by the program's names


def _view(table):
    return harness.RunView(cell=None, facts={}, trace=None, scopes=table, rec=None, peaks=None)


def test_a_reader_says_nothing_off_the_chip_and_zero_for_a_scope_that_is_gone():
    table = {
        "program_runs": {"serve_decode_greedy": [0.07, 0.09], "serve_decode_sampled": [0.08]},
        "by_scope": [["serve_decode_greedy", "attn/kv_gather", "fwd", 0.09],
                     ["serve_decode_sampled", "attn/kv_gather", "fwd", 0.03],
                     ["serve_decode_greedy", "mlp", "fwd", 0.12]],
        "kernels": [["flash_fwd", 0.2, 8], ["flash_bwd_dq", 0.1, 8], ["matmul_fused", 0.4, 2]],
    }
    run = _view(table)
    assert device_reads.runs_ms(run, "serve_decode") == pytest.approx([70.0, 90.0, 80.0])
    assert device_reads.median_run_ms(run, "serve_decode") == pytest.approx(80.0)
    assert device_reads.median_run_ms(run, "serve_prefill") is None
    assert device_reads.scope_ms_per_run(run, "serve_decode", "attn/kv_gather") == pytest.approx(40.0)
    assert device_reads.scope_share_pct(run, "serve_decode", "mlp") == pytest.approx(50.0)
    assert device_reads.kernel_seconds(run, "flash_") == pytest.approx(0.3)
    # the program ran, the scope holds no time: 0.0, so the line is still printed
    assert device_reads.scope_ms_per_run(run, "serve_decode", "attn/scores") == 0.0
    assert device_reads.scope_share_pct(run, "serve_decode", "attn/scores") == 0.0
    # the program did not run in the slice, or there is no trace: nothing
    for nothing in (run, _view(None)):
        program = "serve_prefill" if nothing is run else "serve_decode"
        assert device_reads.runs_ms(nothing, program) is None
        assert device_reads.scope_ms_per_run(nothing, program, "attn/kv_gather") is None
        assert device_reads.scope_share_pct(nothing, program, "mlp") is None
    assert device_reads.kernel_seconds(_view(None), "flash_") is None


def test_the_traced_slice_hands_back_both_reductions_and_deletes_the_trace(tmp_path):
    """`TraceSlice.reduce` on a trace recorded on the chip: the summary by
    XLA's names and the table by the program's, made before the file goes."""
    import shutil

    where = tmp_path / "trace" / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    shutil.copy(REPO / "chipbench/fixtures/scoped_tpu.xplane.pb", where / "host.xplane.pb")
    tracer = harness.TraceSlice(tmp_path / "trace", harness.Recorder(), seconds=1.0)
    assert tracer.reduce() == (None, None)           # no slice was taken
    tracer.started, tracer.stopped = 0.0, 1.0
    family = type("family", (), {"SCOPES": ("attn/scores/div",)})
    reduced, table = tracer.reduce(families=[family])
    assert reduced["busy_s"] > 0 and reduced["chips"] == 1
    assert table["program_runs"].keys() == {"fixture_train", "fixture_serve"}
    assert any(scope == "attn/scores/div" for _, scope, *_ in table["by_scope"])
    assert any(name.startswith("tpu_dist/engine.") for name, _ in table["idle_gaps"])
    assert not (tmp_path / "trace").exists()


# ---------------------------------------------------------------- schedule


TRAFFIC = {p.stem: json.loads(p.read_text()) for p in (REPO / "chipbench/traffic").glob("*.json")}


def _open(seed, span=43.0, **over):
    return schedule.open_loop(dict(TRAFFIC["short-answer-open"], **over), seed, span, 50257)


def _scored(plan):
    return [r for r in plan if r.scored]


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2**31 + 12345), (3, 4), (2**31 + 1, 11)])
def test_open_loop_offers_every_seed_the_same_load_in_another_order(seeds):
    a, b = _open(seeds[0]), _open(seeds[1])
    tr = TRAFFIC["short-answer-open"]
    assert len(a) == len(b) == round(tr["rate_per_s"] * 43.0)
    n = round(tr["rate_per_s"] * 43.0 / (1 + tr["warm_share"]))
    assert len(_scored(a)) == len(_scored(b)) == n
    lens = lambda plan: [r.prompt.size for r in _scored(plan)]  # noqa: E731
    outs = lambda plan: [r.max_new for r in _scored(plan)]  # noqa: E731
    # every seed scores the same multiset of requests, in another order
    assert sorted(lens(a)) == sorted(lens(b)) and sorted(outs(a)) == sorted(outs(b))
    assert lens(a) != lens(b) and outs(a) != outs(b)
    for plan in (a, b):
        dues = [r.due_s for r in plan]
        assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] <= 43.0
        assert all(64 <= r.prompt.size <= 896 and 8 <= r.max_new <= 48 for r in plan)
        assert all(r.prompt.size + r.max_new <= 1024 for r in plan)
        # the warm-up goes first and is the end of the same cycle
        assert [r.scored for r in plan] == [False] * (len(plan) - n) + [True] * n
        assert [r.prompt.size for r in plan[: len(plan) - n]] == lens(plan)[-(len(plan) - n):]
    # one fixed cycle from another starting point: neighbours stay neighbours
    k = lens(b).index(lens(a)[0])
    while lens(b)[k:] + lens(b)[:k] != lens(a):
        k = lens(b).index(lens(a)[0], k + 1)
    assert outs(b)[k:] + outs(b)[:k] == outs(a)


def test_the_seed_only_rotates_one_fixed_cycle():
    vals = list(range(40))
    a, b = schedule.ordered(vals, 1, 3), schedule.ordered(vals, 2**31 + 77, 3)
    assert sorted(a) == vals and a != vals and a != b
    k = b.index(a[0])
    assert b[k:] + b[:k] == a
    # a closed loop's clients move whole: the rotation is a multiple of the unit
    c = schedule.ordered(vals, 5, 3, unit=8)
    assert (c.index(schedule.ordered(vals, 0, 3, unit=8)[0])) % 8 == 0
    # no key of a traffic file chooses the order any more
    assert not any("seed_order" in t or "order_seed" in t for t in TRAFFIC.values())


def test_open_loop_is_the_same_for_the_same_seed():
    a, b = _open(5), _open(5)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due_s == y.due_s
               for x, y in zip(a, b))


def test_stratified_gaps_sum_to_the_span_for_both_patterns():
    for arrivals in ({"gaps": "exponential"}, {"gaps": "bursty", "burst": 8, "burst_gap_s": 0.01}):
        gaps = schedule.stratified_gaps(arrivals, 80, 40.0)
        assert len(gaps) == 80 and sum(gaps) == pytest.approx(40.0)
        assert min(gaps) >= 0
    bursty = schedule.stratified_gaps({"gaps": "bursty", "burst": 8, "burst_gap_s": 0.01}, 80, 40.0)
    assert sum(g == 0.01 for g in bursty) == 70


def test_lengths_are_the_mid_stratum_quantiles():
    dist = {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 64, "max": 896}
    xs = schedule.stratified_lengths(dist, 101)
    assert xs == sorted(xs) and xs[50] == 256 and xs[0] == 64 and xs[-1] == 896
    assert schedule.stratified_lengths({"dist": "uniform", "min": 32, "max": 128}, 3) == [48, 80, 112]
    assert schedule.stratified_lengths({"dist": "fixed", "value": 9}, 2) == [9, 9]
    with pytest.raises(ValueError):
        schedule.stratified_lengths({"dist": "pareto"}, 2)


def test_sessions_share_a_prefix():
    tr = dict(TRAFFIC["short-answer-open"], sessions={"turns": 4, "shared_prefix_tokens": 32},
              prompt_tokens={"dist": "fixed", "value": 16})
    plan = schedule.open_loop(tr, 3, 10.0, 1000)
    assert all(r.prompt.size == 48 for r in plan)
    assert np.array_equal(plan[0].prompt[:32], plan[3].prompt[:32])
    assert not np.array_equal(plan[0].prompt[:32], plan[4].prompt[:32])
    assert not np.array_equal(plan[0].prompt[32:], plan[1].prompt[32:])


@pytest.mark.parametrize("seeds", [(1, 5), (2**31 + 9, 2)])
def test_closed_loop_catches_first_requests_in_flight(seeds):
    tr = TRAFFIC["long-answer-closed"]
    a, b = (schedule.closed_loop(tr, s, 50257) for s in seeds)
    assert len(a) == tr["clients"] and all(len(c) == tr["requests_per_client"] for c in a)
    total = lambda plan: sum(r.prompt.size + r.max_new for c in plan for r in c)  # noqa: E731
    shape = lambda c: [(r.prompt.size, r.max_new) for r in c]  # noqa: E731
    assert total(a) == total(b)  # a first request keeps its length, part of it as prompt
    assert [shape(c) for c in a] != [shape(c) for c in b]
    # the same clients' streams, the same phases, dealt to other clients
    assert sorted(shape(c) for c in a) == sorted(shape(c) for c in b)
    firsts = sorted(c[0].max_new for c in a)
    assert firsts[0] < 64 and firsts[-1] > 256  # phases spread over the answer
    assert all(r.prompt.size + r.max_new <= 1024 and r.max_new >= 1 for c in a for r in c)
    assert all(r.client == i for i, c in enumerate(a) for r in c)


def test_token_rows_differ_and_repeat_with_the_seed():
    tr = dict(TRAFFIC["pretrain-1024"], seq_len=64)
    a = schedule.token_rows(tr, 2**31 + 5, 16, 512)
    assert a.shape == (16, 64) and a.dtype == np.int32 and 0 <= a.min() and a.max() < 512
    assert len({r.tobytes() for r in a}) == 16
    assert np.array_equal(a, schedule.token_rows(tr, 2**31 + 5, 16, 512))
    assert not np.array_equal(a, schedule.token_rows(tr, 6, 16, 512))
    counts = np.bincount(a.ravel(), minlength=512)
    assert counts.max() > 8 * np.median(counts[counts > 0])  # Zipf, not uniform


# ------------------------------------------------------------------ xplane


def _ev(name, start, dur):
    return xplane.Event(name, float(start), float(dur))


def test_busy_is_the_union_and_self_time_leaves_out_children():
    evs = [_ev("while.1", 0, 100), _ev("fusion.2", 10, 30), _ev("copy.3", 50, 20),
           _ev("fusion.7", 200, 50), _ev("all-gather-start.1", 300, 10)]
    assert xplane.busy_intervals(evs) == [(0, 100), (200, 250), (300, 310)]
    st = xplane.self_times(evs)
    assert st["while"] == pytest.approx(50e-9)
    assert st["fusion"] == pytest.approx(80e-9)
    assert st["copy"] == pytest.approx(20e-9)
    assert xplane.is_collective("all-gather-start") and not xplane.is_collective("fusion")


def test_reduce_attributes_idle_gaps_to_the_harness_span():
    dev = {"/device:TPU:0": [_ev("fusion.1", 100, 100), _ev("all-reduce.1", 300, 100)],
           "/device:TPU:1": [_ev("fusion.1", 100, 300)]}
    spans = [_ev("train_step", 0, 250), _ev("data_wait", 250, 100), _ev("train_step", 350, 150)]
    r = xplane.reduce(dev, spans)
    assert r["window_s"] == pytest.approx(500e-9) and r["chips"] == 2
    assert r["busy_s"] == pytest.approx((200 + 300) / 2 * 1e-9)
    assert r["collective_share_pct"] == pytest.approx(50.0)
    gaps = dict(r["idle_gaps"])
    assert gaps["train_step"] == pytest.approx(200e-9)   # 0-100 and 400-500
    assert gaps["data_wait"] == pytest.approx(100e-9)    # 200-300, middle at 250
    assert xplane.reduce({"/device:TPU:0": []}, spans) is None


def test_reducer_on_a_trace_recorded_on_the_chip():
    """`chipbench/fixtures/small_tpu.xplane.pb`: three calls of one jitted
    matmul on a v5e, each inside a `chipbench/step` span, 2 ms of
    `chipbench/idle_wait` after each (recorded in PR 24)."""
    devices, spans = xplane.load(str(REPO / "chipbench/fixtures/small_tpu.xplane.pb"))
    assert list(devices) == ["/device:TPU:0"] and len(devices["/device:TPU:0"]) == 9
    assert [s.name for s in spans] == ["step", "idle_wait"] * 3
    ops = xplane.self_times(devices["/device:TPU:0"])
    assert set(ops) == {"fusion", "copy-start", "copy-done"}
    assert ops["fusion"] == pytest.approx(3 * 90.197e-6, rel=1e-4)
    r = xplane.reduce(devices, spans)
    assert r["chips"] == 1 and r["collective_share_pct"] == 0.0
    assert r["window_s"] == pytest.approx(10.49e-3, rel=1e-3)
    # the device's clock runs about a millisecond ahead of the host's in this
    # file, so the first call falls before the first span: two of three count
    assert r["busy_s"] == pytest.approx(2 * 90.2e-6, rel=1e-2)
    assert r["device_ops"][0][0] == "fusion"
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {"step", "idle_wait"}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


def test_op_names_are_cut_from_the_hlo_text():
    assert xplane.op_name("%copy-start.125 = (bf16[1,8]{1,0}) copy-start(...)") == "copy-start"
    assert xplane.op_name("%convolution_add_fusion.149.remat = f32[8] fusion(...)") == (
        "convolution_add_fusion")
    assert xplane.op_name("fusion.7") == "fusion" and xplane.op_name("while") == "while"
