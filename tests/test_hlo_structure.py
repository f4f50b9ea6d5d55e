"""Compiled-program structure assertions (VERDICT r4 #2): the
performance claims that do not need hardware to verify.

docs/perf.md claims the fused DP step issues ONE fused gradient
all-reduce (the didactic gap vs the reference's per-parameter blocking
calls, /root/reference/train_dist.py:97-99 + tuto.md:319-320), that the
FSDP step reduce-scatters instead of all-reducing, that the collective
matmuls decompose their gathers into ppermute rings, and that nothing in
a train step stages through the host.  These are properties of the
compiled artifact itself, so the CPU-sim mesh can check them — asserted
through `tpu_dist.analysis` (`CollectivePlan` extraction + lints) over
the canonical analyzer programs, instead of the raw HLO-text regexes
this file used to carry (the same programs now also feed the golden-
plan CI gate, `make analyze`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import analysis, comm, models, nn, parallel, train
from tpu_dist.analysis.lints import lint_host_transfer
from tpu_dist.analysis.programs import AnalysisProgram, canonical_program

N = 8


def _prog(name):
    return canonical_program(name)


class TestDPStepHLO:
    def test_gradient_allreduce_count_is_bounded_by_leaves(self):
        """The compiled step issues at most one all-reduce PER GRADIENT
        TENSOR plus the scalar loss reduction — i.e. the collective
        count is a program-structure property, bounded by the pytree,
        never by batch/microbatch/element counts.  Whether XLA's
        combiner then merges them into one variadic op is a
        VERSION-DEPENDENT fusion decision (some CPU lowerings keep them
        per-leaf), so the count is asserted against the collective
        structure, not a fused total."""
        prog = _prog("engine_dp")
        plan = prog.plan
        n_leaves = len(jax.tree.leaves(prog.params))
        n_ar = plan.count("all-reduce")
        assert n_ar >= 1, "no all-reduce in the DP step at all"
        assert n_ar <= n_leaves + 1, (
            f"{n_ar} all-reduces in the compiled DP step with only "
            f"{n_leaves} grad leaves — collectives are multiplying "
            f"beyond the per-tensor program structure"
        )
        # every one of them rides the dp axis (axis names recovered
        # from replica groups — the GSPMD-era version of reading the
        # ring in the reference source)
        assert all(
            c.axes == ("dp",) for c in plan if c.kind == "all-reduce"
        )

    def test_no_reduce_scatter_in_replicated_dp(self):
        assert _prog("engine_dp").plan.count("reduce-scatter") == 0

    def test_no_host_transfers_in_train_step(self):
        """Collectives ride the device mesh; nothing stages through the
        host inside the compiled step."""
        assert lint_host_transfer(_prog("engine_dp")) == []


class TestFSDPStepHLO:
    def test_fsdp_gathers_params_and_reduces_over_fsdp(self):
        """ZeRO-3's wire structure under the engine rule set: the
        parameters return via AllGather over the fsdp axis and the
        gradient payload is reduced over fsdp.  Whether the reduce
        lowers as a true ReduceScatter or as AllReduce + slice is an
        XLA-backend decision (the CPU lowering picks the latter), so
        the assert is reduce-CLASS presence over the right axis — the
        per-chip residency claim lives in TestPartitionedUpdateHLO."""
        prog = _prog("engine_fsdp")
        plan = prog.plan
        gathers = [c for c in plan if c.kind == "all-gather"]
        assert gathers, "no all-gather in FSDP step"
        assert any(c.axes == ("fsdp",) for c in gathers)
        reduces = [
            c for c in plan
            if c.kind in ("all-reduce", "reduce-scatter")
            and c.max_elems > 16
        ]
        assert reduces, "no gradient reduce in FSDP step"
        assert all(c.axes == ("fsdp",) for c in reduces)
        assert lint_host_transfer(prog) == []


class TestCollectiveMatmulHLO:
    def test_tp_mlp_overlapped_is_permutes_plus_dots(self):
        """The collective-matmul claim: `tp_mlp_overlapped` lowers to
        ppermute ring hops interleaved with per-chunk dots — NO
        standalone all-gather or reduce-scatter barrier ops remain, and
        both rings' hops are present (2 x (n-1) collective-permutes)."""
        mesh = comm.make_mesh(N, ("model",), platform="cpu")
        from jax.sharding import NamedSharding, PartitionSpec as P

        d, hidden, rows_l = 16, 32, 4
        mlp_params = {
            "fc1": {
                "w": jnp.ones((d, hidden), jnp.float32),
                "b": jnp.zeros((hidden,), jnp.float32),
            },
            "fc2": {
                "w": jnp.ones((hidden, d), jnp.float32),
                "b": jnp.zeros((d,), jnp.float32),
            },
        }
        mapped = jax.jit(
            jax.shard_map(
                lambda x, p: parallel.tp_mlp_overlapped(x, p, "model"),
                mesh=mesh,
                in_specs=(P("model"), P()),
                out_specs=P("model"),
                check_vma=False,
            )
        )
        x = jnp.ones((N * rows_l, d), jnp.float32)
        prog = AnalysisProgram(
            name="tp_mlp_overlapped",
            fn=mapped,
            args=(
                jax.device_put(x, NamedSharding(mesh, P("model"))),
                jax.device_put(mlp_params, NamedSharding(mesh, P())),
            ),
            mesh=mesh,
        )
        plan = prog.plan
        n_perm = plan.count("collective-permute")
        assert n_perm >= 2 * (N - 1), (
            f"expected >= {2 * (N - 1)} ring hops, found {n_perm}"
        )
        # every hop is a ring over the model axis
        assert all(
            c.axes == ("model",)
            for c in plan
            if c.kind == "collective-permute"
        )
        assert plan.count("all-gather") == 0, (
            "standalone all-gather barrier in the collective matmul"
        )
        assert plan.count("reduce-scatter") == 0, (
            "standalone reduce-scatter barrier in the collective matmul"
        )
        txt = prog.hlo_text
        assert txt.count("dot(") >= 2 * N - 1 or "fusion" in txt


class TestZero1StepHLO:
    def test_zero1_reduces_grads_and_gathers_updated_params(self):
        """ZeRO-1's wire structure under the engine rule set: gradients
        reduce over dp (reduce class — the RS-vs-AR+slice split is an
        XLA-backend lowering choice), the sharded update runs on 1/|dp|
        rows, and the updated params return via AllGather."""
        prog = _prog("engine_zero1")
        plan = prog.plan
        assert any(
            c.kind in ("all-reduce", "reduce-scatter") and c.max_elems > 16
            for c in plan
        ), "no gradient reduce in ZeRO-1 step"
        assert plan.count("all-gather"), "no all-gather in ZeRO-1 step"
        assert lint_host_transfer(prog) == []


class TestAccumStepHLO:
    def test_accumulated_step_does_not_multiply_collectives(self):
        """Gradient accumulation must NOT multiply collectives: the
        microbatch scan reduces on-device and the all-reduce fires once
        per step, not once per microbatch.  Asserted as collective-op
        COUNT PARITY between accum_steps=4 and accum_steps=1 of the
        identical step — a per-microbatch structure would show ~4x —
        rather than against a fused total, which is an XLA-version-
        dependent combiner decision."""
        mesh = comm.make_mesh(N, ("data",), platform="cpu")
        model = models.mnist_net()
        params, state = model.init(jax.random.key(0), models.IN_SHAPE)

        def loss_fn(p, s, batch, key):
            x, y = batch
            scores, _ = model.apply(p, s, x, train=False)
            return nn.nll_loss(scores, y), (s, {})

        opt = train.sgd(0.05, momentum=0.5)
        x = jnp.zeros((4 * N,) + models.IN_SHAPE, jnp.float32)
        y = jnp.zeros((4 * N,), jnp.int32)
        sb = parallel.shard_batch((x, y), mesh)
        p = parallel.replicate(params, mesh)
        # the REAL model state: Sequential.apply zips layers with the
        # state list, so a bare {} would silently apply zero layers
        ms = parallel.replicate(state, mesh)
        o = parallel.replicate(opt.init(params), mesh)
        counts = {}
        for accum in (1, 4):
            step = parallel.make_spmd_train_step(
                loss_fn, opt, mesh, accum_steps=accum, donate=False
            )
            plan = analysis.extract_plan(
                step, (p, ms, o, sb, jax.random.key(0)),
                mesh=mesh, name=f"accum{accum}",
            )
            counts[accum] = plan.count("all-reduce")
        assert counts[4] >= 1, "no all-reduce in the accumulated step"
        assert counts[4] <= counts[1], (
            f"accum_steps=4 compiled to {counts[4]} all-reduces vs "
            f"{counts[1]} unaccumulated — collectives are scaling with "
            "the microbatch count"
        )


class TestPartitionedUpdateHLO:
    """The partition engine's headline claim at the compiled-program
    level: under a zero1/fsdp rule set the WEIGHT UPDATE runs
    dp-sharded — the live momentum stores 1/|dp| per device and the
    plan carries the all-gather wire structure a sharded update needs —
    while the pure-dp rule set keeps the replicated baseline (no
    all-gather at all)."""

    def test_zero1_rule_set_shards_the_weight_update(self):
        built_dp = _prog("engine_dp").built
        prog_z = _prog("engine_zero1")
        built_z = prog_z.built
        # Live-state truth: every sizable momentum leaf stores 1/|dp|
        # per device under zero1 (params stay replicated).
        w_buf = built_z.opt_state["buf"][1]["w"]
        assert w_buf.addressable_shards[0].data.shape == (784 // N, 48)
        p_w = built_z.params[1]["w"]
        assert p_w.addressable_shards[0].data.shape == (784, 48)
        # Plan truth: the partitioner turned the sharded update into
        # gather wire structure — new params must all-gather back; the
        # pure-dp step needs no all-gather at all.
        plan_dp = _prog("engine_dp").plan
        plan_z = prog_z.plan
        assert plan_z.count("all-gather") >= 1
        assert plan_dp.count("all-gather") == 0
        # and the gathers ride the dp axis with roughly the params'
        # payload (each device contributes its 1/|dp| update shard)
        ag_bytes = sum(
            c.bytes for c in plan_z if c.kind == "all-gather"
        )
        param_bytes = sum(
            np.prod(l.shape) * 4
            for l in jax.tree.leaves(built_dp.params)
        )
        assert 0 < ag_bytes <= param_bytes

    def test_fsdp_rule_set_has_no_fullsize_param_residency(self):
        prog = _prog("engine_fsdp")
        built_f = prog.built
        w = built_f.params[1]["w"]
        buf = built_f.opt_state["buf"][1]["w"]
        for leaf in (w, buf):
            assert leaf.addressable_shards[0].data.shape == (784 // N, 48)
        # the replicated-residency lint agrees: nothing big lives
        # replicated under the fsdp rules
        from tpu_dist.analysis.lints import lint_replicated_residency

        assert lint_replicated_residency(prog) == []


class TestGoldenGate:
    """`make analyze`'s CI role, exercised in-process: every canonical
    program's plan matches its blessed golden under tests/goldens/."""

    @pytest.mark.parametrize(
        "name",
        ["engine_dp", "engine_zero1", "engine_fsdp", "engine_dp_int8"]
    )
    def test_plan_matches_golden(self, name):
        import os

        goldens = os.path.join(os.path.dirname(__file__), "goldens")
        golden = analysis.load_golden(goldens, name)
        assert golden is not None, (
            f"missing golden for {name} — run `make analyze-bless`"
        )
        diffs = analysis.compare_to_golden(_prog(name).plan, golden)
        assert diffs == [], "\n".join(diffs)


class TestProgramNamesAndScopes:
    """The names the device trace carries: every hot program's module
    name (the trace's `XLA Modules` line) and the `jax.named_scope`
    vocabulary of PERF.md §3, read from the lowering's debug text (the
    `op_name` XLA keeps for each instruction).  Metadata only: the
    token-identity and determinism tests hold the values."""

    SERVE_SCOPES = ["embed", "ln", "attn/qkv", "attn/kv_scatter",
                    "attn/kv_gather", "attn/scores", "attn/out", "mlp",
                    "lm_head", "sample"]
    TRAIN_SCOPES = ["cast", "embed", "block/attn", "block/mlp", "lm_head",
                    "loss", "grad_accum", "optimizer"]

    @staticmethod
    def _text(fn, args):
        return fn.lower(*args).as_text(debug_info=True)

    @staticmethod
    def _scoped(text, scope):
        # a scope is one or more whole components of an op_name path
        import re

        return re.search(rf'[/("]{re.escape(scope)}[/)]', text) is not None

    @pytest.fixture(scope="class")
    def engine(self):
        from tpu_dist import serve

        lm = models.TransformerLM(vocab=64, dim=32, depth=2, heads=4, max_seq=48)
        params, _ = lm.init(jax.random.key(7))
        return serve.ServeEngine(lm, params, serve.ServeConfig(
            max_batch=4, block_size=8, num_blocks=16, max_seq=32, prefill_chunk=8))

    # lowered for the CPU here, where decode too takes the gathered view:
    # what the v5e's decode holds, TestDecodeAttendsInThePoolOnTheV5e says
    @pytest.mark.parametrize("key,module,extra", [
        ("serve_decode", "jit_serve_decode_sampled", ["state_update"]),
        ("serve_prefill", "jit_serve_prefill", []),
    ])
    def test_serving_programs_are_named_and_scoped(self, engine, key, module, extra):
        programs = engine.analysis_programs()
        assert set(programs) == {"serve_decode", "serve_prefill"}  # the keys stay
        text = self._text(*programs[key])
        assert f"module @{module} " in text and "jit_fn" not in text
        for scope in self.SERVE_SCOPES + extra:
            assert self._scoped(text, scope), scope

    def test_the_greedy_decode_has_a_name_of_its_own(self, engine):
        _, args = engine.analysis_programs()["serve_decode"]
        text = self._text(engine._decode_fn_greedy, args)
        assert "module @jit_serve_decode_greedy " in text
        assert self._scoped(text, "sample") and self._scoped(text, "state_update")

    @pytest.mark.parametrize("mesh_axes", [None, "fsdp=2"])
    def test_the_trainers_step_is_named_and_scoped(self, mesh_axes):
        lm = models.TransformerLM(vocab=64, dim=32, depth=2, heads=4,
                                  max_seq=16, remat=True)
        mesh = (parallel.build_mesh(mesh_axes, mesh_devices=jax.devices()[:2])
                if mesh_axes else
                comm.make_mesh(1, ("data",), mesh_devices=jax.devices()[:1]))
        tr = train.LMTrainer(lm, mesh, train.LMTrainConfig(
            global_batch=4, accum_steps=2, mesh_axes=mesh_axes,
            compute_dtype="bfloat16", log=lambda m: None))
        batch = (jnp.zeros((4, 16), jnp.int32),)
        text = self._text(tr._partition.step,
                          (tr.params, tr.opt_state, batch, jax.random.key(0)))
        assert "module @jit_train_step " in text
        for scope in self.TRAIN_SCOPES:
            assert self._scoped(text, scope), scope
        # backward and rematerialised work carry the forward's scope inside
        # JAX's own wrappers: what `chipbench.scopes` splits the passes on
        assert "transpose(jvp(" in text and "rematted_computation/block/attn" in text

    @pytest.mark.parametrize("builder", ["spmd", "auto"])
    def test_the_hand_written_steps_are_named_and_scoped(self, builder):
        mesh = comm.make_mesh(2, ("data",), mesh_devices=jax.devices()[:2])
        from tpu_dist.train import optim

        opt = optim.sgd(0.1)
        params = {"w": jnp.ones((4, 4))}

        def loss_fn(p, state, batch, key):
            (x,) = batch
            return jnp.mean((x @ p["w"]) ** 2), (state, {})

        if builder == "spmd":
            step = parallel.make_spmd_train_step(loss_fn, opt, mesh, accum_steps=2)
            want = ["grad_accum", "grad_sync", "optimizer"]
        else:
            step = parallel.make_train_step_auto(loss_fn, opt, mesh)
            want = ["optimizer"]
        text = self._text(step, (params, {}, opt.init(params),
                                 (jnp.ones((8, 4)),), jax.random.key(0)))
        assert "module @jit_train_step " in text
        for scope in want:
            assert self._scoped(text, scope), scope

    @pytest.mark.parametrize("causal,window", [
        (True, None), (False, None), (True, 24)])
    def test_the_flash_kernels_feed_the_mxu_the_inputs_dtype(self, causal, window):
        """Traced with bfloat16 inputs, every product of the three flash
        kernels takes bfloat16 operands and gives float32, and nothing
        bfloat16 is converted to float32 inside a kernel (q, k, v and dO
        reach the MXU as they arrive; everything else is born float32):
        an edit that brings the upcast back fails here, on the CPU."""
        from tpu_dist.ops.flash_attention import flash_attention

        q = jnp.ones((1, 2, 64, 16), jnp.bfloat16)
        closed = jax.make_jaxpr(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, window=window, bq=16, bk=16,
                interpret=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))(q, q, q)

        def eqns(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from eqns(sub)

        kernels = {e.params["name"]: e.params["jaxpr"]
                   for e in eqns(closed.jaxpr) if e.primitive.name == "pallas_call"}
        products = {"flash_fwd": 2, "flash_bwd_dkv": 4, "flash_bwd_dq": 3}
        assert set(kernels) == set(products)
        for name, kernel in kernels.items():
            dots = [e for e in eqns(kernel) if e.primitive.name == "dot_general"]
            assert len(dots) == products[name], (name, len(dots))
            for e in dots:
                assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2, name
                assert e.outvars[0].aval.dtype == jnp.float32, name
            upcasts = [e for e in eqns(kernel)
                       if e.primitive.name == "convert_element_type"
                       and e.invars[0].aval.dtype == jnp.bfloat16
                       and e.params["new_dtype"] == jnp.float32]
            assert not upcasts, (name, [str(e.invars[0].aval) for e in upcasts])

    def test_the_kernels_have_names(self):
        """`name=` on every `pl.pallas_call`: the trace's one
        `flash_attention` row becomes forward, dK/dV and dQ."""
        from tpu_dist.ops.flash_attention import flash_attention
        from tpu_dist.ops.matmul import matmul

        q = jnp.ones((1, 2, 128, 64), jnp.float32)
        flash = str(jax.make_jaxpr(jax.grad(lambda q: flash_attention(
            q, q, q, causal=True, interpret=True).sum()))(q))
        for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
            assert f"name={name}\n" in flash
        x = jnp.ones((128, 128), jnp.float32)
        assert "name=matmul_fused\n" in str(jax.make_jaxpr(
            lambda x: matmul(x, x, interpret=True))(x))
        from tpu_dist.ops import paged_attention_decode

        pool = jnp.ones((3, 8, 128), jnp.float32)
        assert "name=paged_attn_decode\n" in str(jax.make_jaxpr(
            lambda pool: paged_attention_decode(
                pool[:2, :2, :64], pool, pool, jnp.zeros((2, 2), jnp.int32),
                jnp.ones((2,), jnp.int32), interpret=True))(pool))


@pytest.fixture(scope="module")
def v5e_devices():
    """The four described (not attached) chips of a v5e 2x2: libtpu
    compiles for them here.  Made inside a fixture, never at import: only
    the worker that runs this file may load the TPU's library."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds its lock
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def v5e_chip(v5e_devices):
    """One of them."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_devices[0])


# head_dim 64, block_size 16: the two minor dimensions decide.  Where
# the row (kv_heads * head_dim) is no multiple of 128 the device
# still picks the dimension whose padding to a 128-lane tile wastes
# least: 192 -> 256 against 257 -> 384 blocks keeps the row minor
# (docs/serving.md has the rule and its limits).
V5E_SERVING_SHAPES = {
    "mha_row128": dict(dim=128, heads=2, num_blocks=255),
    "gqa_row192": dict(dim=384, heads=6, kv_heads=3, num_blocks=256),
}


@pytest.fixture(scope="module", params=list(V5E_SERVING_SHAPES))
def v5e_engine(request):
    from tpu_dist import serve

    shape = dict(V5E_SERVING_SHAPES[request.param])
    num_blocks = shape.pop("num_blocks")
    lm = models.TransformerLM(vocab=128, depth=2, max_seq=64, **shape)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                          lm.init(jax.random.key(0))[0])
    return serve.ServeEngine(lm, params, serve.ServeConfig(
        max_batch=4, block_size=16, num_blocks=num_blocks, max_seq=64,
        prefill_chunk=16, prefill_batch=2))


def _compiled_for_the_v5e(engine, v5e_chip, program, rows):
    """-> (the program's compiled text, the cache's shapes); each program
    of each engine is compiled once for both classes below."""
    programs = engine.analysis_programs()
    if rows:  # the prefill program is retraced for each row count
        fn, (params, cache, ints, flt) = programs["serve_prefill"]
        ints, flt = (jax.ShapeDtypeStruct((rows,) + a.shape[1:], a.dtype)
                     for a in (ints, flt))
    else:
        _, (params, cache, ints, flt) = programs["serve_decode"]
        fn = (engine._decode_fn_greedy if program.endswith("greedy")
              else engine._decode_fn)
    texts = engine.__dict__.setdefault("_v5e_texts", {})
    if (program, rows) not in texts:
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
            (params, cache, ints, flt))
        texts[program, rows] = fn.lower(*args).compile().as_text()
    return texts[program, rows], params, cache


class TestServingPoolIsNotRelayoutOnTheV5e:
    """The serving programs, compiled for the v5e, update the donated KV
    pool in place.  With a 64-wide last dimension the device's own layout
    of a pool array puts another dimension minor-most, and every program
    then copied every layer's whole pool on entry and again on exit (two
    thirds of the serving cells' device time, ledger PR 25); with heads
    and head_dim folded into one minor dimension (serve/paged_kv.py) the
    argument, the scatter and the read side (decode's kernel, prefill's
    gather) agree."""

    @pytest.mark.parametrize("program,rows", [
        ("serve_decode_greedy", None), ("serve_decode_sampled", None),
        ("serve_prefill", 1), ("serve_prefill", 2)])
    def test_no_pool_sized_copy_and_the_pool_is_aliased(
            self, v5e_engine, v5e_chip, program, rows):
        import math
        import re

        from tpu_dist.analysis.lints import donated_buffer_count

        text, params, cache = _compiled_for_the_v5e(
            v5e_engine, v5e_chip, program, rows)
        pool = math.prod(cache["kv"][0]["k"].shape)
        # "pool-sized" is unambiguous: nothing else in the program is as large
        assert pool > max(math.prod(a.shape) for a in jax.tree.leaves(params))
        copy_of = re.compile(r"=\s*\w+\[([\d,]*)\]\S*\s+copy\(")
        copies = [
            line.strip() for line in text.splitlines()
            if (m := copy_of.search(line))
            and math.prod(int(d) for d in m.group(1).split(",") if d) == pool]
        assert not copies, (
            f"{program} copies a whole pool array "
            f"{cache['kv'][0]['k'].shape}:\n" + "\n".join(copies[:4]))
        # every pool array is donated AND aliased to its output
        assert donated_buffer_count(text) >= len(jax.tree.leaves(cache))


class TestDecodeAttendsInThePoolOnTheV5e:
    """How often the mechanism engages, statically: every decode step.
    Compiled for the v5e, both decode programs hold ONE
    `paged_attn_decode` kernel call an attention layer and nothing of
    the gathered view — no array of ``slots x (max_blocks * block_size)``
    places of K/V rows, whole or split into heads — while `serve_prefill`
    (s > 1) still gathers its view.  The parent's decode programs fail
    both halves."""

    @staticmethod
    def _view_arrays(text, engine, rows):
        """Instructions that make an array of the gathered view's size
        whose leading dimension is its rows."""
        import math
        import re

        attn = engine.lm.blocks[0].attn
        places = engine.blocks_per_seq * engine.cfg.block_size
        sizes = {rows * places * heads * attn.head_dim
                 for heads in (attn.kv_heads, attn.heads)}
        shaped = re.compile(r"=\s*\w+\[([\d,]+)\]")
        found = []
        for line in text.splitlines():
            if (m := shaped.search(line)):
                dims = [int(d) for d in m.group(1).split(",")]
                if dims[0] == rows and math.prod(dims) in sizes:
                    found.append(line.strip()[:160])
        return found

    @pytest.mark.parametrize("program", ["serve_decode_greedy",
                                         "serve_decode_sampled"])
    def test_decode_holds_the_kernel_and_no_gathered_view(
            self, v5e_engine, v5e_chip, program):
        import re

        text, _, cache = _compiled_for_the_v5e(
            v5e_engine, v5e_chip, program, None)
        calls = re.findall(
            r"^\s*%paged_attn_decode\S* = .*custom-call\(.*"
            r'custom_call_target="tpu_custom_call"', text, re.M)
        assert len(calls) == len(cache["kv"]) == 2
        view = self._view_arrays(text, v5e_engine, v5e_engine.cfg.max_batch)
        assert not view, "\n".join(view[:4])

    def test_prefill_still_gathers_its_view(self, v5e_engine, v5e_chip):
        text, _, _ = _compiled_for_the_v5e(
            v5e_engine, v5e_chip, "serve_prefill", 2)
        assert "paged_attn_decode" not in text
        assert self._view_arrays(text, v5e_engine, 2)


class TestTheTrainerPicksFlashItself:
    """`LMTrainer` with nothing in the environment: the step of one chip
    holds the three flash kernels where it is lowered for a TPU; so does
    the same trainer under ``mesh_axes="fsdp=4"``, which XLA partitions
    over four devices: attention is one device's share there, inside one
    `shard_map` over the axes the engine shards batch and heads by.

    The trainer places its state as it is built, so it is built on the
    CPU's devices and its step lowered FOR the TPU from here
    (``lowering_platforms``): the TPU's lowering rules, Mosaic's refusal
    of a partitioned program among them, with no chip.  What XLA then
    makes of the dead dense branch is `test_flash_grads_compiled_for_
    the_v5e_hold_no_scores`'s, below."""

    KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")

    @pytest.mark.parametrize("mesh_axes,grad_compress,kernels", [
        (None, None, KERNELS),
        ("fsdp=4", None, KERNELS),
        ("dp=2,fsdp=2", None, KERNELS),
        # 2 heads over tp=2 divide; the 8 rows of a micro-batch of 4 do
        # too.  3 heads would not: `test_ops.py`'s rule cases
        ("dp=2,tp=2", None, KERNELS),
        # the compressed wire is a `shard_map` over the data axes: every
        # axis of an fsdp mesh, so its body is one device's and keeps the
        # kernels; beside a ``tp`` axis the compiler still partitions the
        # body, be that axis of size 1 (Mosaic refuses both).  No error
        # feedback there: its `lax.axis_index` beside an automatic axis
        # does not lower on any platform (ROADMAP C8's standing failure)
        ("fsdp=4", "int8", KERNELS),
        ("dp=2,tp=2", "int8,ef=off", ()),
        ("dp=4,tp=1", "int8,ef=off", ()),
    ])
    def test_step_lowered_for_a_tpu(self, mesh_axes, grad_compress, kernels,
                                    monkeypatch):
        monkeypatch.delenv("TPU_DIST_FLASH", raising=False)
        lm = models.TransformerLM(vocab=64, dim=32, depth=2, heads=2,
                                  max_seq=1024)
        mesh = (parallel.build_mesh(mesh_axes, mesh_devices=jax.devices()[:4])
                if mesh_axes else
                comm.make_mesh(1, ("data",), mesh_devices=jax.devices()[:1]))
        tr = train.LMTrainer(lm, mesh, train.LMTrainConfig(
            global_batch=8, accum_steps=2, mesh_axes=mesh_axes,
            grad_compress=grad_compress,
            compute_dtype="bfloat16", log=lambda m: None))
        batch = (jnp.zeros((8, 1024), jnp.int32),)
        text = tr._partition.step.trace(
            tr.params, tr.opt_state, batch, jax.random.key(0),
        ).lower(lowering_platforms=("tpu",)).as_text()
        assert "module @jit_train_step " in text
        for name in self.KERNELS:
            assert (f'kernel_name = "{name}"' in text) == (name in kernels), name
        assert ("@tpu_custom_call" in text) == bool(kernels)

    @pytest.mark.parametrize("devices,mesh_axes,kernel", [
        (1, None, True), (4, None, True), (4, "fsdp=4", True),
        (4, "dp=2,tp=2", False)])
    def test_evaluation_lowered_for_a_tpu(self, devices, mesh_axes, kernel):
        """`Trainer.evaluate` shards its batches over the mesh's leading
        axis, under the `shard_map` step and under the engine alike: over
        four devices a program XLA partitions, which must lower, with the
        kernel on each device's rows where that axis is the whole mesh and
        dense beside a ``tp`` axis the batches are not split by; on one
        device the kernel.  1024 tokens: a length flash takes."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        model = nn.Sequential([
            nn.MultiHeadAttention(32, 2),
            nn.Lambda(lambda h: h.mean(axis=1), lambda shape: shape[1:]),
            nn.Dense(10),
            nn.log_softmax(),
        ])
        mesh = (parallel.build_mesh(mesh_axes, mesh_devices=jax.devices()[:4])
                if mesh_axes else
                comm.make_mesh(devices, ("data",),
                               mesh_devices=jax.devices()[:devices]))
        tr = train.Trainer(model, (1024, 32), mesh, train.TrainConfig(
            global_batch=4, mesh_axes=mesh_axes, log=lambda m: None))
        xs = jax.ShapeDtypeStruct(
            (4, 1024, 32), jnp.float32,
            sharding=NamedSharding(mesh, P(mesh.axis_names[0])))
        text = tr._eval_apply.trace(tr.params, tr.model_state, xs).lower(
            lowering_platforms=("tpu",)).as_text()
        assert ('kernel_name = "flash_fwd"' in text) == kernel
        assert ("@tpu_custom_call" in text) == kernel

    def test_partitioned_attention_is_traced_once_a_step(self):
        """Four layers, each its own `jax.checkpoint`: the per-device
        function's Python body runs ONCE while the ``fsdp=4`` step is
        traced, not once a layer (a `shard_map` traced afresh in each of
        gpt2-xl's 48 layers cost `train-xl-fsdp4` 24 s of set-up, PR 33),
        and the text lowered for a TPU holds each kernel's body as often
        as a model of two layers does.  The builder says what it found."""
        texts, logs = {}, []
        for depth in (4, 2):
            # a trace an earlier step of this process left would be reused
            # whole, and the count read 0
            nn.attention._per_device_attention.cache_clear()
            lm = models.TransformerLM(vocab=64, dim=32, depth=depth, heads=2,
                                      max_seq=1024, remat=True)
            mesh = parallel.build_mesh("fsdp=4", mesh_devices=jax.devices()[:4])
            tr = train.LMTrainer(lm, mesh, train.LMTrainConfig(
                global_batch=8, mesh_axes="fsdp=4", compute_dtype="bfloat16",
                log=logs.append))
            batch = (jnp.zeros((8, 1024), jnp.int32),)
            assert tr._partition.attention_form() is None  # not traced yet
            texts[depth] = tr._partition.step.trace(
                tr.params, tr.opt_state, batch, jax.random.key(0),
            ).lower(lowering_platforms=("tpu",)).as_text()
            assert tr._partition.attention_form() == {
                "form": "flash", "axes": ["fsdp", None],
                "per_device_shape": [2, 2, 1024, 16], "calls": depth,
                "per_device_traces": 1}
        bodies = {d: {k: t.count(f'kernel_name = "{k}"') for k in self.KERNELS}
                  for d, t in texts.items()}
        assert bodies[4] == bodies[2], bodies
        assert all(0 < n <= 2 for n in bodies[4].values()), bodies
        tr._partition.report_attention(logs.append)
        tr._partition.report_attention(logs.append)  # once
        assert [m for m in logs if "attention under the partition engine" in m] == [
            "attention under the partition engine: flash, batch and heads over "
            "['fsdp', None], [2, 2, 1024, 16] a device, 2 calls, per-device "
            "body traced 1 time(s)"]

    def test_one_device_step_is_the_direct_selection(self, monkeypatch):
        """On one device nothing is wrapped: the step lowers to the text
        it has with `nn.dot_product_attention` the bare selection between
        the kernel and the dense form (what it was before the partitioned
        form existed), but for each kernel's serialised body, which names
        the Python frames that called it."""
        import functools
        import re

        from tpu_dist import ops

        def lowered():
            lm = models.TransformerLM(vocab=64, dim=32, depth=2, heads=2,
                                      max_seq=1024, remat=True)
            mesh = comm.make_mesh(1, ("data",), mesh_devices=jax.devices()[:1])
            tr = train.LMTrainer(lm, mesh, train.LMTrainConfig(
                global_batch=8, accum_steps=2, compute_dtype="bfloat16",
                log=lambda m: None))
            text = tr._partition.step.trace(
                tr.params, tr.opt_state, (jnp.zeros((8, 1024), jnp.int32),),
                jax.random.key(0),
            ).lower(lowering_platforms=("tpu",)).as_text()
            return (re.sub(r'backend_config = "[^"]*"', "backend_config = ...", text),
                    tr._partition.attention_form())

        def bare_selection(q, k, v, *, causal=False, mask=None, window=None,
                           scale=None):
            assert ops.flash_attention_takes(q, k, v, mask=mask, scale=scale)
            return ops.kernel_for_platform(
                functools.partial(ops.flash_attention, causal=causal, window=window),
                functools.partial(nn.attention.dense_attention, causal=causal,
                                  mask=mask, window=window, scale=scale),
                q, k, v)

        ours, found = lowered()
        assert found == {
            "form": "flash", "axes": [], "per_device_shape": [4, 2, 1024, 16],
            "calls": 2, "per_device_traces": 0}
        monkeypatch.setattr(nn.attention, "dot_product_attention", bare_selection)
        assert (ours, None) == lowered()
        assert ours.count("@tpu_custom_call") >= 3

    def test_step_compiled_for_four_v5e_chips(self, v5e_devices):
        """Two layers at gpt2-xl's widths under the engine's ``fsdp=4``
        rules (its specs on the parameters and the gradients, the batch on
        ``fsdp``, the bfloat16 loss traced as `make_partitioned_train_step`
        traces it), compiled for the described 2x2: the three kernels on
        each chip's four rows, no ``(rows, heads, S, S)`` array, and no
        `all-to-all` under a block.  Without the residual stream pinned to
        the batch's axes (`TransformerLM.apply`) the partitioner moves it
        to the feature-sharded layout of the fsdp rule's weights and back
        around every norm and projection: 46 exchanges of
        ``bf16[4,4,1024,400]`` and ``[4,4,1024,1200]`` in these two
        layers.  The two that stay are the embedding's (its table is
        sharded by features: the looked-up rows change layout once
        forward and once backward a step, 13 MB)."""
        import re

        from jax.sharding import NamedSharding, PartitionSpec as P

        from tpu_dist.models.transformer_lm import lm_loss

        mesh = parallel.build_mesh("fsdp=4", mesh_devices=v5e_devices)
        rules = parallel.resolve_rules("fsdp=4", mesh)
        lm = models.TransformerLM(vocab=50257, dim=1600, depth=2, heads=25,
                                  max_seq=1024, remat=True)
        shapes = jax.eval_shape(lambda k: lm.init(k)[0], jax.random.key(0))
        specs = parallel.match_partition_rules(rules.param_rules, shapes, mesh)
        p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))
        said = parallel.partitioned_over(
            mesh, batch_axes=rules.data_axes, head_axes=rules.model_axes)

        def step(params, tokens):
            def loss(p):
                with said:
                    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
                    logits, _ = lm.apply(p, {}, tokens)
                    return lm_loss(logits.astype(jnp.float32), tokens)
            value, grads = jax.value_and_grad(loss)(params)
            return value, jax.lax.with_sharding_constraint(grads, p_sh)

        text = jax.jit(step).lower(
            jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                         shapes, p_sh),
            jax.ShapeDtypeStruct((16, 1024), jnp.int32,
                                 sharding=NamedSharding(mesh, rules.batch_spec())),
        ).compile().as_text()
        assert said.attention == [("flash", P("fsdp", None), (4, 25, 1024, 64))] * 2
        assert said.per_device_traces == 1
        for name in self.KERNELS:
            assert re.search(rf"^\s*%{name}\S* = .*custom-call\(.*"
                             r'custom_call_target="tpu_custom_call"', text, re.M), name
        assert not re.findall(r"\[[\d,]*1024,1024\]", text)
        exchanges = [line.strip()[:200] for line in text.splitlines()
                     if re.search(r"= \S+ all-to-all(-start)?\(", line)]
        assert len(exchanges) <= 2 and all("(embed)" in e for e in exchanges), exchanges

    @pytest.mark.parametrize("S", [1024, 2048])
    def test_flash_grads_compiled_for_the_v5e_hold_no_scores(self, v5e_chip, S):
        """The selection's other branch is the dense form, whose residuals
        are ``(b, heads, S, S)``; differentiated, the kernel's branch hands
        zeros of that shape to a backward that never reads them.  Compiled
        for the v5e they are gone: three kernels and no S x S array, at
        the least length the rule takes and at twice that."""
        import re

        q = jax.ShapeDtypeStruct((2, 2, S, 64), jnp.bfloat16, sharding=v5e_chip)
        grads = jax.jit(jax.grad(
            lambda q, k, v: nn.dot_product_attention(q, k, v, causal=True)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2)))
        lowered = grads.lower(q, q, q)
        assert f"2x2x{S}x{S}" in lowered.as_text()  # still there as lowered
        text = lowered.compile().as_text()
        for name in self.KERNELS:
            assert len(re.findall(
                rf"^\s*%{name}\S* = .*custom-call\(.*"
                r'custom_call_target="tpu_custom_call"', text, re.M)) == 1, name
        assert not re.findall(rf"\[[\d,]*{S},{S}\]", text)


@pytest.fixture(scope="module")
def v5e_latent_engine():
    """Latent rows of 96 + 8 = 104 and 160 + 8 = 168 values, neither a
    whole number of 128-lane tiles, as the published 576 and 1088 are not;
    257 blocks and rings of 24 rows, which pad less than those rows."""
    from tpu_dist import serve

    sizes = dict(heads=4, q_rank=64, nope_dim=32, rope_dim=8, v_dim=32, rope_base=1e4)
    lm = models.HybridLM(
        vocab=128, dim=128, layer_types=["full_attention", "sliding_attention"],
        mixers={"full_attention": dict(sizes, kv_rank=96, index_heads=4, index_dim=128,
                                       index_topk=16),
                "sliding_attention": dict(sizes, kv_rank=160, window=9, chunk=16)},
        n_experts=8, experts_per_token=2, expert_width=32, shared_width=32,
        held_experts=(0, 4), expert_scoring="sigmoid_normalised", dense_layers=1,
        dense_width=64, tied_head=False, max_seq=64)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), lm.init(jax.random.key(0))[0])
    return serve.ServeEngine(lm, params, serve.ServeConfig(
        max_batch=4, block_size=16, num_blocks=256, max_seq=64, prefill_chunk=16,
        prefill_batch=2))


class TestLatentCachesAreNotRelayoutOnTheV5e:
    """The latent pool and the per-slot ring keep their row as whole
    128-lane tiles (`serve.paged_kv.init_latent_cache`), so the row stays
    the minor dimension on the device and the donated arrays are updated
    in place.  Left at 576 / 1088 values, the published sizes compiled
    with 8 copies of a 264-MB pool and 12 of a 27-MB ring in every decode
    step (PERF.md section 6, PR 32)."""

    @pytest.mark.parametrize("program,rows", [("serve_decode_greedy", None),
                                              ("serve_prefill", 2)])
    def test_no_cache_sized_copy(self, v5e_latent_engine, v5e_chip, program, rows):
        import math
        import re

        text, _, cache = _compiled_for_the_v5e(v5e_latent_engine, v5e_chip, program, rows)
        kept = {"ckv": cache["kv"][0]["ckv"].shape, "ring": cache["state"]["layers"][1]["ring"].shape}
        assert kept == {"ckv": (257, 16, 128), "ring": (4, 24, 256)}
        copy_of = re.compile(r"=\s*\w+\[([\d,]*)\]\S*\s+copy\(")
        sizes = {math.prod(shape) for shape in kept.values()}
        copies = [line.strip()[:160] for line in text.splitlines()
                  if (m := copy_of.search(line))
                  and math.prod(int(d) for d in m.group(1).split(",") if d) in sizes]
        assert not copies, "\n".join(copies[:4])


@pytest.fixture(scope="module")
def v5e_gated_engine():
    """Gated grouped-query layers with the published head layout (six query
    heads to a K/V head of 128, a head size that is not ``dim / heads``): a
    windowed one, whose ring of 33 - 1 + 16 = 48 rows is three blocks a
    slot, and a full one in the paged pool."""
    from tpu_dist import serve

    sizes = dict(heads=12, kv_heads=2, head_dim=128)
    lm = models.HybridLM(
        vocab=128, dim=128, layer_types=["gated_sliding_attention", "gated_attention"],
        mixers={"gated_attention": sizes,
                "gated_sliding_attention": dict(sizes, window=33, chunk=16)},
        n_experts=8, experts_per_token=2, expert_width=32, shared_width=32,
        held_experts=(0, 4), expert_scoring="sigmoid_normalised", route_scale=2.448,
        dense_layers=1, dense_width=64, tied_head=False, embedding_multiplier=128 ** 0.5,
        sandwich_norms=True, max_seq=64)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), lm.init(jax.random.key(0))[0])
    return serve.ServeEngine(lm, params, serve.ServeConfig(
        max_batch=4, block_size=16, num_blocks=256, max_seq=64, prefill_chunk=16,
        prefill_batch=2))


class TestTheRingsAreReadThroughTheKernelOnTheV5e:
    """A windowed grouped-query layer's rings lie in one pair of arrays of
    the pool's layout (`serve.paged_kv.init_ring_cache`), so compiled for
    the v5e the decode program reads them through the same
    `paged_attn_decode` kernel as the full layer's pool, under a table that
    wraps, and neither kind of cache is copied or relayouted."""

    @pytest.mark.parametrize("program,rows,kernels", [("serve_decode_greedy", None, 2),
                                                      ("serve_prefill", 2, 0)])
    def test_a_kernel_a_layer_and_no_cache_sized_copy(self, v5e_gated_engine, v5e_chip,
                                                      program, rows, kernels):
        import math
        import re

        text, _, cache = _compiled_for_the_v5e(v5e_gated_engine, v5e_chip, program, rows)
        kept = {"pool": cache["kv"][1]["k"].shape, "ring": cache["state"]["layers"][0]["k"].shape}
        assert kept == {"pool": (257, 16, 256), "ring": (4 * 3 + 1, 16, 256)}
        calls = re.findall(r"^\s*%paged_attn_decode\S* = .*custom-call\(.*"
                           r'custom_call_target="tpu_custom_call"', text, re.M)
        assert len(calls) == kernels
        copy_of = re.compile(r"=\s*\w+\[([\d,]*)\]\S*\s+copy\(")
        sizes = {math.prod(shape) for shape in kept.values()}
        copies = [line.strip()[:160] for line in text.splitlines()
                  if (m := copy_of.search(line))
                  and math.prod(int(d) for d in m.group(1).split(",") if d) in sizes]
        assert not copies, "\n".join(copies[:4])


@pytest.fixture(scope="module")
def v5e_shortcut_engine():
    """LongCat-Flash's layer at a small size: two sublayers of ungated
    latent attention over every row (a latent row of 128 + 8 values, kept as
    256 lanes), a dense feed-forward each, one routed branch with zero
    experts beside them."""
    from tpu_dist import serve

    sizes = dict(heads=4, q_rank=64, kv_rank=128, nope_dim=32, rope_dim=8, v_dim=32,
                 rope_base=1e7, gated=False)
    lm = models.HybridLM(
        vocab=128, dim=128, layer_types=["latent_attention"] * 2,
        mixers={"latent_attention": sizes}, shortcut=2, n_experts=12, zero_experts=4,
        experts_per_token=3, expert_width=32, held_experts=(0, 4), expert_scoring="softmax",
        route_scale=6.0, dense_width=64, tied_head=False, max_seq=64)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), lm.init(jax.random.key(0))[0])
    return serve.ServeEngine(lm, params, serve.ServeConfig(
        max_batch=4, block_size=16, num_blocks=256, max_seq=64, prefill_chunk=16,
        prefill_batch=2))


class TestTheLatentPoolIsReadWhereItLiesOnTheV5e:
    """A latent layer with neither selection nor window keeps ONE pool, and
    compiled for the v5e the decode program reads it through the kernel
    `paged_latent_decode`, one call a sublayer, with no gathered view of the
    pool and no pool-sized copy; prefill still gathers its view."""

    @pytest.mark.parametrize("program,rows,kernels", [("serve_decode_greedy", None, 2),
                                                      ("serve_prefill", 2, 0)])
    def test_a_kernel_a_sublayer_and_no_pool_sized_copy(self, v5e_shortcut_engine, v5e_chip,
                                                        program, rows, kernels):
        import math
        import re

        text, _, cache = _compiled_for_the_v5e(v5e_shortcut_engine, v5e_chip, program, rows)
        assert [{k: v.shape for k, v in kv.items()} for kv in cache["kv"]] == [
            {"ckv": (257, 16, 256)}] * 2
        calls = re.findall(r"^\s*%paged_latent_decode\S* = .*custom-call\(.*"
                           r'custom_call_target="tpu_custom_call"', text, re.M)
        assert len(calls) == kernels
        pool, view = 257 * 16 * 256, 4 * 64 * 256   # the view: every slot's whole table
        moved = re.compile(r"=\s*\w+\[([\d,]*)\]\S*\s+(copy|gather)\(")
        least = {"copy": pool // 4, "gather": view if rows is None else pool}
        big = [line.strip()[:160] for line in text.splitlines()
               if (m := moved.search(line))
               and math.prod(int(d) for d in m.group(1).split(",") if d) >= least[m.group(2)]]
        assert not big, "\n".join(big[:4])


def _with_callees(text):
    """-> a function that gives the lines of a computation of a compiled
    module's text, by name, with those of everything it calls."""
    import re

    bodies, name = {}, None
    for line in text.splitlines():
        start = re.match(r"^(?:ENTRY )?(%\S+) \(.*\) -> .*\{$", line)
        if start:
            name = start.group(1)
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)

    def with_callees(name, seen=None):
        seen = set() if seen is None else seen
        if name in seen or name not in bodies:
            return []
        seen.add(name)
        lines = list(bodies[name])
        for line in bodies[name]:
            for group in re.findall(r"(?:calls|to_apply|body|condition|branch_computations)="
                                    r"\{?((?:%[\w.\-]+(?:, )?)+)\}?", line):
                for callee in group.split(", "):
                    lines += with_callees(callee, seen)
        return lines

    return with_callees


class TestASelectingLayerReadsInPlaceOrFetchesOnTheV5e:
    """A selecting layer's decode step reaches its picks one of two ways,
    chosen on the device by what the call holds: compiled for the v5e the
    decode program keeps ONE conditional a selecting layer, one arm with the
    kernel `paged_latent_decode` under the picks' mask (one more operand
    than the kernel of a layer that attends everything, whose call is as it
    was) and no row gather, the other with the gather of the picked rows
    through the block table and no kernel."""

    KERNEL = (r"^\s*%paged_latent_decode\S* = .*custom-call\((.*?)\), "
              r'custom_call_target="tpu_custom_call"')

    def test_one_conditional_a_layer_with_the_kernel_in_one_arm_and_the_gather_in_the_other(
            self, v5e_latent_engine, v5e_chip):
        import re

        text, _, _ = _compiled_for_the_v5e(v5e_latent_engine, v5e_chip,
                                               "serve_decode_greedy", None)
        assert len(re.findall(self.KERNEL, text, re.M)) == 1
        with_callees = _with_callees(text)
        conds = [line for line in text.splitlines() if re.search(r" conditional\(", line)]
        assert len(conds) == 1, conds
        arms = re.search(r"branch_computations=\{(%\S+), (%\S+)\}", conds[0]).groups()
        fetched, in_place = ("\n".join(with_callees(arm)) for arm in arms)
        # S x index_topk rows of the pool, one by one
        rows = re.compile(r"= bf16\[4,16,128\]\S* gather\(.*slice_sizes=\{1,1,128\}")
        assert re.search(self.KERNEL, in_place, re.M) and not rows.search(in_place)
        assert rows.search(fetched) and "tpu_custom_call" not in fetched
        assert "dsa/gather" in fetched and "dsa/gather" not in in_place
        assert "dsa/topk" in in_place and "mla/attend" in in_place and "mla/attend" in fetched

    def test_only_the_arm_that_fetches_sorts_the_index_scores(self, v5e_latent_engine, v5e_chip):
        """The picks' mask needs two numbers a row, which the arm that
        reads in place finds by two searches (`ops.kth_score`: two loops of
        compare-and-count passes under ``dsa/topk``); the
        ``sort`` that `lax.top_k` of the ``(S, L)`` scores compiles to is
        left where the picks are needed as indices, in the arm that fetches
        their rows."""
        import re

        text, _, _ = _compiled_for_the_v5e(v5e_latent_engine, v5e_chip,
                                           "serve_decode_greedy", None)
        with_callees = _with_callees(text)
        cond, = [line for line in text.splitlines() if re.search(r" conditional\(", line)]
        arms = re.search(r"branch_computations=\{(%\S+), (%\S+)\}", cond).groups()
        fetched, in_place = ("\n".join(with_callees(arm)) for arm in arms)
        sort = re.compile(r"= \(f32\[4,64\]\S*, s32\[4,64\]\S*\) sort\(.*dsa/topk")
        search = re.compile(r" while\(.*dsa/topk.*kth_and_last")
        assert len(search.findall(in_place)) == 2 and not sort.search(in_place)
        assert sort.search(fetched) and "kth_and_last" not in fetched
        assert len(sort.findall(text)) == 1 and len(search.findall(text)) == 2

    def test_a_prefill_chunk_selects_without_a_sort(self, v5e_latent_engine, v5e_chip):
        """A chunk's rows go through the same two searches: no ``sort`` and
        no running count of the ties under ``dsa/topk``."""
        import re

        text, _, _ = _compiled_for_the_v5e(v5e_latent_engine, v5e_chip, "serve_prefill", 2)
        under = [line for line in text.splitlines() if "dsa/topk" in line]
        assert len([line for line in under if re.search(r" while\(.*kth_and_last", line)]) == 2
        assert not [line[:160] for line in under if re.search(r" (sort|reduce-window)\(", line)]

    def test_the_mask_is_an_operand_only_where_a_layer_selects(
            self, v5e_latent_engine, v5e_shortcut_engine, v5e_chip):
        """The grid's bound, six tables, the queries and the pool once a
        block of a chunk (four here): LongCat's call, as it was; a selecting
        layer's has the mask besides."""
        import re

        operands = {}
        for name, engine in (("selects", v5e_latent_engine), ("whole", v5e_shortcut_engine)):
            text, _, _ = _compiled_for_the_v5e(engine, v5e_chip, "serve_decode_greedy", None)
            operands[name] = {call.count("%") for call in re.findall(self.KERNEL, text, re.M)}
        assert operands == {"whole": {1 + 6 + 1 + 4}, "selects": {1 + 6 + 1 + 1 + 4}}


def test_the_environment_decides_nothing_that_is_compiled():
    """The ``TPU_DIST_*`` names `tpu_dist/` knows are a deployment's: where
    telemetry, metrics and dumps go, where the data is, how processes find
    each other and how long they retry, what chaos to inject.  None picks a
    kernel, a wire or a layout: the program does (`ops.kernel_for_platform`)
    or the config says (``grad_compress``, ``partition_rules``).  A new
    name fails here first."""
    import pathlib
    import re

    import tpu_dist

    deployment = {
        "TELEMETRY", "TELEMETRY_RANK", "TELEMETRY_EVERY", "METRICS_PORT",
        "RUN_ID", "FLIGHTREC", "FLIGHTREC_DIR", "DATA_DIR", "PLATFORM",
        "INIT_METHOD", "PROBE_WORLD", "CHAOS", "CHAOS_ATTEMPT",
        "RDZV_RETRIES", "RDZV_BASE_DELAY", "RDZV_MAX_DELAY",
        "STARTUP_DEADLINE",
    }
    root = pathlib.Path(tpu_dist.__file__).parent
    named = set()
    for path in root.rglob("*.py"):
        # "TPU_DIST_RDZV_*" in a comment names the family, not a variable
        named |= {n for n in re.findall(r"TPU_DIST_([A-Z0-9_]*[A-Z0-9])\b(?!_)",
                                        path.read_text())}
    assert named == deployment, sorted(named ^ deployment)
