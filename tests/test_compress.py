"""Bucketed error-feedback compressed gradient sync (`comm.compress`).

Covers the ISSUE-6 acceptance surface: parity-vs-psum for every wire
dtype (including bucket-boundary and sub-block payloads), the compressed
reduce-scatter against the exact ``psum_scatter``, config parsing
(unknown wire dtypes rejected at config-parse time), error-feedback
convergence (fast quadratic here; the MNIST/LM parity runs are
slow-marked), residual checkpoint round-trips, the NaN-guard contract
(a skipped step must not absorb a poisoned residual), wire-byte
accounting, telemetry, and the HLO structure of the compiled compressed
steps (1-byte collective operands, one collective per bucket).

Since the legacy strategy builders retired, every compiled-step test
here runs through the partition ENGINE (`make_partitioned_train_step
(compress=...)`) — the only compressed gradient wire in the repo.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpu_dist import comm, data, models, nn, parallel, train
from tpu_dist.comm import compress

N = 8


def _mesh():
    return comm.make_mesh(N, ("data",), platform="cpu")


def _tree():
    # leaf sizes chosen so leaves SPLIT across buckets under the small
    # test bucket (1009*5 spans several 1024-element chunks) and one
    # leaf ("tiny") is smaller than a single scale block
    return {
        "big": jax.random.normal(jax.random.key(0), (1009, 5)),
        "tiny": jax.random.normal(jax.random.key(1), (3,)) * 1e-3,
        "mid": jax.random.normal(jax.random.key(2), (7, 11)) * 10.0,
    }


def _spmd(fn, *args):
    mesh = _mesh()
    mapped = jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(P() for _ in args), out_specs=P(),
        check_vma=False,
    )
    return jax.jit(mapped)(*args)


# ------------------------------------------------------------ wire parity


WIRES = ("int8", "float8_e4m3", "float8_e5m2", "bfloat16")
# two quantization rounds of tensor-scale error; fp8 e5m2 is coarsest
TOL = {"int8": 0.02, "float8_e4m3": 0.08, "float8_e5m2": 0.15,
       "bfloat16": 0.02}


@pytest.mark.parametrize("wire", WIRES)
def test_all_reduce_rows_parity_vs_psum(wire):
    """Bucketed compressed allreduce agrees with exact psum to wire
    tolerance, with leaves splitting across buckets (small buckets) and
    a payload smaller than one scale block.  Quantization error is
    ABSOLUTE at block scale (a near-zero leaf co-bucketed with O(1)
    values carries the block's absolute error), so parity is measured
    against the payload's global scale, not per-tiny-leaf."""
    cfg = compress.CompressConfig(wire=wire, bucket_bytes=4096, block=64)
    tree = _tree()

    def fn(t):
        t = jax.tree.map(lambda x: x * (lax.axis_index("data") + 1.0), t)
        plan = compress.FlatPlan(t, N, cfg)
        assert plan.n_buckets > 1, "test payload must span several buckets"
        total, _, stats = compress.all_reduce_rows(
            plan.to_rows(t), None, plan, "data"
        )
        approx = plan.from_rows(total)
        exact = jax.tree.map(lambda x: lax.psum(x, "data"), t)
        scale = jnp.max(
            jnp.stack([jnp.max(jnp.abs(e)) for e in jax.tree.leaves(exact)])
        )
        rel = [
            jnp.max(jnp.abs(a - e)) / (scale + 1e-12)
            for a, e in zip(jax.tree.leaves(approx), jax.tree.leaves(exact))
        ]
        return jnp.stack(rel), stats["err"]

    rel, err = _spmd(fn, tree)
    assert float(np.max(np.asarray(rel))) < TOL[wire], (wire, np.asarray(rel))
    assert float(err) < TOL[wire]


@pytest.mark.parametrize("wire", ("int8", "bfloat16"))
def test_reduce_scatter_rows_parity_vs_psum_scatter(wire):
    """The compressed reduce-scatter produces each rank's exact shard
    rows (vs a plain flat-padded ``psum_scatter``) to wire tolerance —
    the flat-row reduce-scatter hop contract."""
    from tpu_dist.utils.tree import pad_to_multiple

    def exact_rs(grads):
        return jax.tree.map(
            lambda g: lax.psum_scatter(
                pad_to_multiple(jnp.ravel(g), N).reshape(N, -1), "data",
                scatter_dimension=0, tiled=True,
            )
            / N,
            grads,
        )

    cfg = compress.CompressConfig(wire=wire, bucket_bytes=4096, block=64)
    tree = _tree()

    def fn(t):
        t = jax.tree.map(lambda x: x * (lax.axis_index("data") + 1.0), t)
        plan = compress.FlatPlan(t, N, cfg)
        local, _, _ = compress.reduce_scatter_rows(
            plan.to_rows(t), None, plan, "data"
        )
        shards = plan.shard_rows(local / N)
        exact = exact_rs(t)
        scale = jnp.max(
            jnp.stack([jnp.max(jnp.abs(e)) for e in jax.tree.leaves(exact)])
        )
        rel = [
            jnp.max(jnp.abs(a - e)) / (scale + 1e-12)
            for a, e in zip(jax.tree.leaves(shards), jax.tree.leaves(exact))
        ]
        return lax.pmax(jnp.stack(rel), "data")

    rel = _spmd(fn, tree)
    assert float(np.max(np.asarray(rel))) < TOL[wire]


def test_sub_block_payload_roundtrips():
    """A payload smaller than one scale block (and than one bucket) must
    still sync correctly — the boundary where padding dominates."""
    cfg = compress.CompressConfig(wire="int8", block=256)

    def fn(x):
        x = x * (lax.axis_index("data") + 1.0)
        approx = compress.compressed_all_reduce(x, cfg, "data")
        exact = lax.psum(x, "data")
        return jnp.max(jnp.abs(approx - exact)) / jnp.max(jnp.abs(exact))

    rel = _spmd(fn, jnp.array([1.0, -2.0, 3.0]))
    assert float(rel) < 0.02


def test_bf16_wire_in_collectives_table():
    """ROADMAP names bf16 explicitly: `all_reduce_quantized` accepts the
    bfloat16 wire (and its 'bf16' alias) and agrees with exact psum to
    bf16 mantissa tolerance."""
    from tests.conftest import spmd_run as run  # the shared spmd harness

    def fn():
        x = jax.random.normal(jax.random.key(3), (512,)) * (comm.rank() + 1.0)
        exact = comm.all_reduce(x)
        approx = comm.all_reduce_quantized(x, dtype="bf16")
        return jnp.max(jnp.abs(approx - exact)) / jnp.max(jnp.abs(exact))

    rel = run(fn, world=8)
    assert float(np.asarray(rel).max()) < 0.02


def test_unknown_wire_dtype_rejected_at_parse_time():
    with pytest.raises(ValueError, match="unknown wire dtype"):
        comm.all_reduce_quantized(jnp.ones(4), dtype="int4")
    with pytest.raises(ValueError, match="unknown compress wire"):
        compress.parse("q4_0")
    with pytest.raises(ValueError, match="unknown compress wire"):
        compress.CompressConfig(wire="fp16")


# ------------------------------------------------------------- config


def test_parse_forms():
    assert compress.parse(None) is None
    assert compress.parse("off") is None
    assert compress.parse("none") is None
    assert compress.parse("") is None
    cfg = compress.parse("fp8")
    assert cfg.wire == "float8_e4m3" and cfg.error_feedback
    cfg = compress.parse("int8,bucket_mb=1,block=512,ef=0")
    assert cfg.bucket_bytes == 1 << 20
    assert cfg.block == 512 and not cfg.error_feedback
    assert compress.parse(cfg) is cfg
    with pytest.raises(ValueError, match="unknown compress option"):
        compress.parse("int8,buckets=3")
    with pytest.raises(ValueError, match="malformed compress option"):
        compress.parse("int8,4mb")
    with pytest.raises(ValueError, match="bad compress option"):
        compress.parse("int8,ef=flase")  # a typo must not silently enable


def test_resized_residual_is_zeroed_on_restore():
    """A checkpoint from a different world size must not flat-copy the
    dense per-rank residual into a misdirected layout — it is zeroed
    (one step of re-paid quantization error, not garbage feedback)."""
    mesh = _mesh()
    cfg = compress.parse("int8")
    params = {"w": jnp.zeros((64,))}
    opt = compress.wrap_opt_state({}, params, N, cfg, mesh, "data")
    live = opt["ef"]["residual"]
    poisoned = {
        "opt": {},
        "ef": {"residual": live + 1.0, "err": opt["ef"]["err"]},
    }
    key = "['opt_state']['ef']['residual']"
    same = {"leaves": [{"path": key, "shape": list(live.shape)}]}
    resized = {"leaves": [{"path": key, "shape": [4, 4, 99]}]}
    kept = compress.reset_resized_residual(poisoned, same)
    assert float(np.abs(np.asarray(kept["ef"]["residual"])).max()) == 1.0
    reset = compress.reset_resized_residual(poisoned, resized)
    assert float(np.abs(np.asarray(reset["ef"]["residual"])).max()) == 0.0
    assert reset["ef"]["residual"].shape == live.shape  # live layout wins


def test_trainer_wire_is_the_config_field_alone(monkeypatch):
    """``grad_compress`` is the one way: None is the exact sync whatever
    the environment holds, a spec is its config."""
    monkeypatch.setenv("TPU_DIST_COMPRESS", "bf16")
    t, _ = _mnist_trainer()
    assert t._compress is None
    t2, _ = _mnist_trainer(grad_compress="int8")
    assert t2._compress.wire == "int8"


def test_trainer_rejects_bad_wire_at_construction():
    mesh = _mesh()
    with pytest.raises(ValueError, match="unknown compress wire"):
        train.Trainer(
            models.mnist_net(), models.IN_SHAPE, mesh,
            train.TrainConfig(grad_compress="int3"),
        )


def test_trainer_rejects_compress_plus_other_backend():
    mesh = _mesh()
    with pytest.raises(ValueError, match="grad_compress"):
        train.Trainer(
            models.mnist_net(), models.IN_SHAPE, mesh,
            train.TrainConfig(grad_compress="int8", grad_reduce="ring"),
        )


def test_lm_trainer_rejects_compress_plus_model_sharding():
    mesh = comm.make_mesh((4, 2), ("data", "model"), platform="cpu")
    lm = models.TransformerLM(vocab=32, dim=16, depth=1, heads=2, max_seq=8)
    with pytest.raises(ValueError, match="grad_compress"):
        train.LMTrainer(
            lm, mesh,
            train.LMTrainConfig(grad_compress="int8", tensor_parallel="psum"),
        )


def test_compress_refusal_hint_points_at_engine_mode():
    """After the legacy builders' retirement, compress refusals name the
    offending axis AND point the fix at mesh_axes engine mode — not at
    deleted builders."""
    mesh = comm.make_mesh((4, 2), ("data", "model"), platform="cpu")
    lm = models.TransformerLM(vocab=32, dim=16, depth=1, heads=2, max_seq=8)
    with pytest.raises(ValueError) as ei:
        train.LMTrainer(
            lm, mesh,
            train.LMTrainConfig(grad_compress="int8", tensor_parallel="psum"),
        )
    msg = str(ei.value)
    assert "'model'" in msg  # the offending axis, by name
    assert "mesh_axes" in msg  # the fix: engine mode
    assert "fsdp/zero1 strategy flags" not in msg  # no deleted-builder hints

    # sequence/pipeline/moe genuinely lack support; the refusal says so
    mesh_sp = comm.make_mesh((4, 2), ("data", "seq"), platform="cpu")
    with pytest.raises(ValueError) as ei:
        train.LMTrainer(
            lm, mesh_sp,
            train.LMTrainConfig(
                grad_compress="int8", sequence_parallel="ring"
            ),
        )
    msg = str(ei.value)
    assert "'seq'" in msg
    assert "rule vocabulary" in msg


# ------------------------------------------------- wire-byte accounting


def test_bytes_on_wire_ratios():
    params = {"w": jnp.zeros((512, 512)), "b": jnp.zeros((512,))}
    p_int8 = compress.FlatPlan(params, N, compress.parse("int8"))
    p_bf16 = compress.FlatPlan(params, N, compress.parse("bf16"))
    ratio8 = p_int8.bytes_exact() / p_int8.bytes_on_wire()
    ratio16 = p_bf16.bytes_exact() / p_bf16.bytes_on_wire()
    assert 3.8 < ratio8 <= 4.0  # 1 byte + per-block scale overhead
    assert ratio16 == pytest.approx(2.0)
    # reduce-scatter mode is half the allreduce's traffic, same ratio
    assert p_int8.bytes_on_wire("reduce_scatter") * 2 == p_int8.bytes_on_wire()


def test_bucket_count_scales_with_payload():
    cfg = compress.parse("int8,bucket_bytes=65536")
    small = compress.FlatPlan({"w": jnp.zeros((1000,))}, N, cfg)
    big = compress.FlatPlan({"w": jnp.zeros((300_000,))}, N, cfg)
    assert small.n_buckets == 1
    assert big.n_buckets >= 300_000 * 4 // 65536  # O(total_bytes / bucket)
    assert big.n_buckets == big.K_pad // big.chunk
    # tiny payloads must not ship a mostly-padding full-size bucket
    assert small.K_pad * N * 4 < 2 * 1000 * 4 + 8 * cfg.block * 4


# ------------------------------------------------- error feedback


def _quad_problem():
    W = jnp.array([[1.0], [-2.0], [0.5]])
    x = jax.random.normal(jax.random.key(0), (16, 3))
    return x, x @ W


def _quad_loss(params, batch, key):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2), {}


def _dp_rules(mesh):
    from tpu_dist.parallel import partition as part

    return part.resolve_rules(f"dp={N}", mesh, bind={"dp": "data"})


def _run_quad(mesh, grad_compress, steps=25, nan_batch_at=None,
              nan_guard=False):
    """The quadratic problem through the ENGINE's dp rule set — the
    compressed wire lives inside `make_partitioned_train_step` now."""
    from tpu_dist.parallel import partition as part

    opt = train.sgd(0.1, momentum=0.5)
    if nan_guard:
        from tpu_dist.resilience.guards import nan_guard as guard

        opt = guard(opt, max_scale=1.0)
    params = {"w": jnp.zeros((3, 1)), "b": jnp.zeros((1,))}
    built = part.make_partitioned_train_step(
        _quad_loss, opt, mesh, params, _dp_rules(mesh), donate=False,
        compress=grad_compress,
    )
    p, o = built.params, built.opt_state
    x, y = _quad_problem()
    batch = parallel.shard_batch((x, y), mesh)
    bad_x = x.at[0, 0].set(jnp.nan)
    bad_batch = parallel.shard_batch((bad_x, y), mesh)
    losses, snapshots = [], []
    for i in range(steps):
        b = bad_batch if i == nan_batch_at else batch
        p, o, loss, _ = built.step(p, o, b, jax.random.key(1))
        losses.append(float(loss))
        snapshots.append(o)
    return losses, p, o, snapshots


@pytest.mark.parametrize("wire", ("int8", "bf16", "fp8"))
def test_error_feedback_convergence_matches_exact(wire):
    """Compressed training with error feedback reaches the exact-sync
    loss on the quadratic problem (the fast convergence-parity check;
    MNIST/LM runs are slow-marked below)."""
    mesh = _mesh()
    exact, _, _, _ = _run_quad(mesh, None)
    compressed, _, o, _ = _run_quad(mesh, wire)
    assert compressed[-1] < exact[0] * 0.01
    assert compressed[-1] == pytest.approx(exact[-1], rel=0.15, abs=1e-6)
    err = float(o["ef"]["err"])
    assert 0 <= err < TOL[compress.parse(wire).wire]


def test_nan_step_skipped_and_residual_held():
    """A poisoned batch must (a) trip the NaN guard (skip + count) even
    though NaN does not survive an int8 cast, and (b) leave the
    error-feedback residual bit-identical — a skipped step must not
    absorb a poisoned residual."""
    mesh = _mesh()
    losses, p, o, snaps = _run_quad(
        mesh, "int8", steps=6, nan_batch_at=3, nan_guard=True
    )
    from tpu_dist.resilience.guards import bad_steps

    assert bad_steps(o) == 1
    res_before = np.asarray(snaps[2]["ef"]["residual"])
    res_after = np.asarray(snaps[3]["ef"]["residual"])
    np.testing.assert_array_equal(res_before, res_after)
    # training continues and still converges after the skipped step
    assert losses[-1] < losses[0] * 0.1
    assert np.isfinite(np.asarray(p["w"])).all()


def test_residual_is_nonzero_and_bounded():
    mesh = _mesh()
    _, _, o, _ = _run_quad(mesh, "int8", steps=5)
    res = np.asarray(o["ef"]["residual"])
    assert np.abs(res).max() > 0  # EF is actually carrying error
    assert np.isfinite(res).all()


# ------------------------------------------------- trainers + checkpoint


def _mnist_trainer(tmpdir=None, **cfg_kw):
    mesh = _mesh()
    cfg = train.TrainConfig(
        epochs=1, global_batch=128, log=lambda s: None, **cfg_kw
    )
    return train.Trainer(models.mnist_net(), models.IN_SHAPE, mesh, cfg), mesh


def test_trainer_compressed_end_to_end(tmp_path, monkeypatch):
    """One compressed MNIST dp fit carries the whole trainer contract:
    epoch loss matches exact sync, the residual rides the checkpoint and
    `latest_intact` resume, and the compress telemetry (event + wire
    counters + error gauge) is emitted.  Folded into one fit/compile so
    the tier-1 wall cost stays small."""
    from tpu_dist.observe import events as ev_mod
    from tpu_dist.observe.registry import REGISTRY
    from tpu_dist.train.checkpoint import latest_intact

    monkeypatch.setenv(ev_mod.ENV_DIR, str(tmp_path / "tele"))
    ds = data.load_mnist("train", synthetic_size=512)
    before = REGISTRY.counter("tpu_dist_bytes_on_wire_total").value()
    t, _ = _mnist_trainer(grad_compress="int8")
    h = t.fit(ds, checkpoint_dir=str(tmp_path))
    monkeypatch.delenv(ev_mod.ENV_DIR)
    # loss-vs-exact parity is covered by the quadratic EF tests (fast)
    # and the slow-marked MNIST parity run; here the fit must be sane
    assert np.isfinite(h[0].mean_loss) and h[0].mean_loss < 2.4
    # residual checkpoint round-trip through latest_intact resume; the
    # per-rank residual forces the sharded DIRECTORY format (a npz
    # would materialize it on process 0, impossible on a multi-host
    # mesh)
    assert (tmp_path / "ckpt_0").is_dir()
    best = latest_intact(tmp_path)
    assert best is not None
    t2, _ = _mnist_trainer(grad_compress="int8")
    assert t2.restore(best) == 1
    np.testing.assert_array_equal(
        np.asarray(t.opt_state["ef"]["residual"]),
        np.asarray(t2.opt_state["ef"]["residual"]),
    )
    assert np.abs(np.asarray(t2.opt_state["ef"]["residual"])).max() > 0
    # telemetry: schema-valid compress event + registry counters/gauge
    tele = str(tmp_path / "tele")
    count, errors = ev_mod.validate_dir(tele)
    assert not errors, errors
    recs = [
        r for r in ev_mod.read_events(tele) if r["event"] == "compress"
    ]
    assert recs, "no compress event emitted"
    rec = recs[-1]
    assert rec["wire"] == "int8"
    assert rec["bytes_on_wire"] * 3.8 < (
        rec["bytes_on_wire"] + rec["bytes_saved"]
    ) * 1.0001
    assert rec["compression_error"] is None or rec["compression_error"] >= 0
    assert REGISTRY.counter("tpu_dist_bytes_on_wire_total").value() > before
    assert REGISTRY.gauge("tpu_dist_compression_error").value() >= 0


def test_lm_trainer_fsdp_compressed_sharded_checkpoint(tmp_path):
    from tpu_dist.models.transformer_lm import synthetic_tokens

    mesh = _mesh()
    lm = models.TransformerLM(vocab=64, dim=32, depth=1, heads=2, max_seq=16)
    toks = synthetic_tokens(64, 16, vocab=64, seed=0)
    cfg = train.LMTrainConfig(
        epochs=1, global_batch=32, fsdp=True, grad_compress="int8",
        log=lambda s: None,
    )
    t = train.LMTrainer(lm, mesh, cfg)
    t.fit(toks, checkpoint_dir=str(tmp_path))
    ckpt = tmp_path / "lm_ckpt_0"
    assert ckpt.is_dir()  # sharded directory format
    t2 = train.LMTrainer(lm, mesh, cfg)
    epoch = t2.restore(ckpt)
    assert epoch == 1
    np.testing.assert_array_equal(
        np.asarray(t.opt_state["ef"]["residual"]),
        np.asarray(t2.opt_state["ef"]["residual"]),
    )


@pytest.mark.parametrize("spec,bind", [
    (f"zero1:dp={N}", {"dp": "data"}),
    (f"fsdp={N}", {"fsdp": "data"}),
])
def test_engine_sharded_compressed_matches_exact(spec, bind):
    """Compressed zero1/fsdp ENGINE training matches its own exact-sync
    trajectory on the quadratic problem — the rule sets the legacy
    builders used to own, now on the engine wire."""
    from tpu_dist.parallel import partition as part

    mesh = _mesh()
    opt = train.sgd(0.1, momentum=0.5)
    params = {"w": jnp.zeros((8, 1)), "b": jnp.zeros((1,))}
    W = jnp.concatenate(
        [jnp.array([[1.0], [-2.0], [0.5]]), jnp.zeros((5, 1))]
    )
    x = jax.random.normal(jax.random.key(0), (16, 8))
    batch = parallel.shard_batch((x, x @ W), mesh)
    rules = part.resolve_rules(spec, mesh, bind=bind)

    def run(gc):
        built = part.make_partitioned_train_step(
            _quad_loss, opt, mesh, dict(params), rules, donate=False,
            compress=gc,
        )
        p, o = built.params, built.opt_state
        for _ in range(20):
            p, o, loss, _ = built.step(p, o, batch, jax.random.key(1))
        return float(loss)

    exact, compressed = run(None), run("int8")
    assert compressed == pytest.approx(exact, rel=0.15, abs=1e-6)


# ------------------------------------------------------- HLO structure


_HLO_CACHE: dict = {}


def _compiled_compressed_dp(ccfg):
    cached = _HLO_CACHE.get(ccfg)
    if cached is not None:  # both HLO tests probe the same compiles
        return cached
    from tpu_dist.parallel import partition as part

    mesh = _mesh()
    model = models.mnist_net()
    params, state = model.init(jax.random.key(0), models.IN_SHAPE)

    def loss_fn(p, batch, key):
        x, y = batch
        scores, _ = model.apply(p, state, x, train=False)
        return nn.nll_loss(scores, y), {}

    opt = train.sgd(0.05, momentum=0.5)
    built = part.make_partitioned_train_step(
        loss_fn, opt, mesh, params, _dp_rules(mesh), donate=False,
        compress=ccfg,
    )
    x = jnp.zeros((2 * N,) + models.IN_SHAPE, jnp.float32)
    y = jnp.zeros((2 * N,), jnp.int32)
    sb = parallel.shard_batch((x, y), mesh)
    txt = (
        built.step
        .lower(built.params, built.opt_state, sb, jax.random.key(0))
        .compile()
        .as_text()
    )
    result = (txt, built.flat_plan)
    _HLO_CACHE[ccfg] = result
    return result


def _op_lines(txt, op):
    """HLO lines whose INSTRUCTION is ``op`` (the bare mnemonic followed
    by its operand paren) — excludes get-tuple-element lines that merely
    reference ``%op.N`` results."""
    return [
        line for line in txt.splitlines()
        if f" {op}(" in line or f" {op}-start(" in line
    ]


def test_hlo_compressed_step_payload_is_one_byte_per_bucket():
    """The compiled compressed DP step's gradient payload rides s8
    collective operands, one all-to-all + one all-gather per bucket, and
    NO large f32 collective remains (scales and loss scalars only)."""
    ccfg = compress.parse("int8,bucket_bytes=65536,block=64")
    txt, plan = _compiled_compressed_dp(ccfg)
    assert plan.n_buckets >= 2
    a2a_ops = [l for l in _op_lines(txt, "all-to-all") if "s8[" in l]
    ag_ops = [l for l in _op_lines(txt, "all-gather") if "s8[" in l]
    assert len(a2a_ops) == plan.n_buckets, (len(a2a_ops), plan.n_buckets)
    assert len(ag_ops) == plan.n_buckets, (len(ag_ops), plan.n_buckets)
    # every f32 collective payload is small: per-bucket scales
    # (chunk/block elements) or scalar loss/predicate reductions
    scale_elems = plan.chunk // plan.block
    for op in ("all-reduce", "all-gather", "all-to-all"):
        for line in _op_lines(txt, op):
            for m in re.finditer(r"f32\[([\d,]*)\]", line):
                dims = [int(d) for d in m.group(1).split(",") if d]
                elems = int(np.prod(dims)) if dims else 1
                assert elems <= max(scale_elems * N, 16), (
                    f"large f32 collective in compressed step: {line[:160]}"
                )


def test_hlo_collective_count_scales_with_bucket_size():
    """Smaller buckets mean more collectives, one s8 all-to-all per
    bucket either way — the O(total_bytes / bucket_bytes) contract
    realized in the compiled artifact."""
    txt_small, plan_small = _compiled_compressed_dp(
        compress.parse("int8,bucket_bytes=32768,block=64")
    )
    txt_big, plan_big = _compiled_compressed_dp(
        compress.parse("int8,bucket_bytes=65536,block=64")
    )
    assert plan_small.n_buckets > plan_big.n_buckets

    def count(txt):
        return len([l for l in _op_lines(txt, "all-to-all") if "s8[" in l])

    assert count(txt_small) == plan_small.n_buckets
    assert count(txt_big) == plan_big.n_buckets


def test_hlo_engine_fsdp_compressed_gradient_is_one_byte():
    """The compressed ENGINE fsdp step ships its gradient sync as s8
    all-to-all + all-gather chunks; the only wide f32 collectives left
    are the PARAM gathers fsdp inherently pays — no f32 gradient
    reduce remains."""
    from tpu_dist.parallel import partition as part

    mesh = _mesh()
    model = models.mnist_net()
    params, state = model.init(jax.random.key(0), models.IN_SHAPE)

    def loss_fn(p, batch, key):
        x, y = batch
        scores, _ = model.apply(p, state, x, train=False)
        return nn.nll_loss(scores, y), {}

    opt = train.sgd(0.05, momentum=0.5)
    ccfg = compress.parse("int8,bucket_bytes=65536,block=64")
    rules = part.resolve_rules(f"fsdp={N}", mesh, bind={"fsdp": "data"})
    built = part.make_partitioned_train_step(
        loss_fn, opt, mesh, params, rules, donate=False, compress=ccfg
    )
    x = jnp.zeros((2 * N,) + models.IN_SHAPE, jnp.float32)
    y = jnp.zeros((2 * N,), jnp.int32)
    sb = parallel.shard_batch((x, y), mesh)
    txt = (
        built.step
        .lower(built.params, built.opt_state, sb, jax.random.key(0))
        .compile()
        .as_text()
    )
    a2a_ops = [l for l in _op_lines(txt, "all-to-all") if "s8[" in l]
    assert a2a_ops, "no s8 all-to-all in the compressed engine fsdp step"
    # no wide f32 gradient REDUCE survives (scales + scalar predicates
    # only); param all-gathers are exempt — they are fsdp's own cost
    plan = built.flat_plan
    scale_elems = plan.chunk // plan.block
    for op in ("all-reduce", "reduce-scatter", "all-to-all"):
        for line in _op_lines(txt, op):
            for m in re.finditer(r"f32\[([\d,]*)\]", line):
                dims = [int(d) for d in m.group(1).split(",") if d]
                elems = int(np.prod(dims)) if dims else 1
                assert elems <= max(scale_elems * N, 16), (
                    f"wide f32 gradient collective survived: {line[:160]}"
                )


# ----------------------------------------------- slow convergence parity


@pytest.mark.slow
def test_mnist_dp_compressed_convergence_parity():
    """Compressed MNIST dp reaches the exact-sync loss on the same seed
    (multi-epoch, slow-marked — the fast quadratic parity runs in
    tier-1)."""
    ds = data.load_mnist("train", synthetic_size=2048)
    mesh = _mesh()
    cfg_c = train.TrainConfig(
        epochs=3, global_batch=128, grad_compress="int8", log=lambda s: None
    )
    cfg_e = train.TrainConfig(epochs=3, global_batch=128, log=lambda s: None)
    hc = train.Trainer(models.mnist_net(), models.IN_SHAPE, mesh, cfg_c).fit(ds)
    he = train.Trainer(models.mnist_net(), models.IN_SHAPE, mesh, cfg_e).fit(ds)
    assert hc[-1].mean_loss == pytest.approx(he[-1].mean_loss, rel=0.02)


@pytest.mark.slow
def test_lm_fsdp_compressed_convergence_parity():
    from tpu_dist.models.transformer_lm import synthetic_tokens

    mesh = _mesh()
    lm = models.TransformerLM(vocab=64, dim=32, depth=2, heads=2, max_seq=16)
    toks = synthetic_tokens(512, 16, vocab=64, seed=0)
    cfg_c = train.LMTrainConfig(
        epochs=3, global_batch=64, fsdp=True, grad_compress="int8",
        log=lambda s: None,
    )
    cfg_e = train.LMTrainConfig(
        epochs=3, global_batch=64, fsdp=True, log=lambda s: None
    )
    hc = train.LMTrainer(lm, mesh, cfg_c).fit(toks)
    he = train.LMTrainer(lm, mesh, cfg_e).fit(toks)
    assert hc[-1].mean_loss == pytest.approx(he[-1].mean_loss, rel=0.02)
