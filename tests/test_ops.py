"""Pallas kernel tests (interpret mode on CPU; real-TPU compile paths are
gated behind the `tpu` marker)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import ops


class TestPallasMatmul:
    @pytest.mark.parametrize(
        "shape", [(256, 512, 256), (128, 384, 512), (8, 16, 32), (100, 60, 40)]
    )
    def test_matches_xla_dot(self, shape):
        m, k, n = shape
        x = jax.random.normal(jax.random.key(0), (m, k))
        w = jax.random.normal(jax.random.key(1), (k, n))
        b = jax.random.normal(jax.random.key(2), (n,))
        y = ops.matmul(x, w, b, interpret=True)
        # blocked accumulation order differs from XLA's -> pure fp noise
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(x @ w + b), rtol=1e-4, atol=5e-5
        )

    @pytest.mark.parametrize("epilogue", ["relu", "gelu"])
    def test_fused_epilogue(self, epilogue):
        x = jax.random.normal(jax.random.key(0), (64, 128))
        w = jax.random.normal(jax.random.key(1), (128, 32))
        b = jax.random.normal(jax.random.key(2), (32,))
        y = ops.matmul(x, w, b, epilogue=epilogue, interpret=True)
        act = {"relu": jax.nn.relu, "gelu": jax.nn.gelu}[epilogue]
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(act(x @ w + b)), rtol=2e-5, atol=2e-5
        )

    def test_no_bias(self):
        x = jnp.ones((16, 16))
        w = jnp.eye(16)
        y = ops.matmul(x, w, interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.ones((16, 16)))

    def test_bad_shapes_raise(self):
        with pytest.raises(ValueError, match="inner dims"):
            ops.matmul(jnp.ones((4, 5)), jnp.ones((6, 7)), interpret=True)

    def test_bad_epilogue_raises(self):
        with pytest.raises(ValueError, match="epilogue"):
            ops.matmul(
                jnp.ones((4, 4)), jnp.ones((4, 4)), epilogue="tanh", interpret=True
            )

    @pytest.mark.parametrize("epilogue", ["none", "relu"])
    def test_grad_matches_xla(self, epilogue):
        """The kernel must be differentiable (custom VJP) — training goes
        through it when the Dense flag is on."""
        x = jax.random.normal(jax.random.key(0), (32, 64))
        w = jax.random.normal(jax.random.key(1), (64, 16))
        b = jax.random.normal(jax.random.key(2), (16,))
        act = _EPILOGUES = {"none": lambda v: v, "relu": jax.nn.relu}[epilogue]

        def loss_kernel(x, w, b):
            return ops.matmul(x, w, b, epilogue=epilogue, interpret=True).sum()

        def loss_ref(x, w, b):
            return act(x @ w + b).sum()

        gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(x, w, b)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
        for a, b_ in zip(gk, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=2e-5, atol=2e-5
            )


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("shape", [(1, 2, 64, 16), (2, 3, 128, 8)])
    def test_matches_reference(self, causal, shape):
        from tpu_dist.nn import dot_product_attention

        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (jax.random.normal(kk, shape) for kk in ks)
        out = ops.flash_attention(
            q, k, v, causal=causal, bq=32, bk=32, interpret=True
        )
        ref = dot_product_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_grad_matches_reference(self, causal):
        """The blockwise custom VJP must match autodiff through dense
        attention."""
        from tpu_dist.nn import dot_product_attention

        ks = jax.random.split(jax.random.key(5), 3)
        shape = (1, 2, 64, 8)
        q, k, v = (jax.random.normal(kk, shape) for kk in ks)

        def loss_flash(q, k, v):
            return jnp.sum(
                ops.flash_attention(
                    q, k, v, causal=causal, bq=16, bk=16, interpret=True
                )
                ** 2
            )

        def loss_ref(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
            )

    def test_block_clamping_small_seq(self):
        from tpu_dist.nn import dot_product_attention

        q = jax.random.normal(jax.random.key(1), (1, 1, 8, 4))
        out = ops.flash_attention(q, q, q, interpret=True)  # blocks clamp to 8
        ref = dot_product_attention(q, q, q)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_indivisible_raises(self):
        q = jnp.ones((1, 1, 48, 4))
        with pytest.raises(ValueError, match="not divisible"):
            ops.flash_attention(q, q, q, bq=32, bk=32, interpret=True)

    def test_shape_mismatch_raises(self):
        q = jnp.ones((1, 1, 32, 4))
        k = jnp.ones((1, 1, 16, 4))
        with pytest.raises(ValueError, match="shapes differ"):
            ops.flash_attention(q, k, k, interpret=True)


    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("window", [16, 64])
    def test_sliding_window_matches_dense_band_mask(self, causal, window):
        """window=w must equal dense attention under the band mask
        k > q - w (optionally intersected with causal) — values AND all
        three grads, through the windowed forward + backward kernels."""
        from tpu_dist.nn import dot_product_attention

        ks = jax.random.split(jax.random.key(11), 3)
        shape = (1, 2, 128, 8)
        q, k, v = (jax.random.normal(kk, shape) for kk in ks)
        S = shape[-2]
        pos = jnp.arange(S)
        band = pos[None, :] > pos[:, None] - window  # k > q - w
        if causal:
            band = band & (pos[:, None] >= pos[None, :])

        def loss_flash(q, k, v):
            return jnp.sum(
                ops.flash_attention(
                    q, k, v, causal=causal, window=window,
                    bq=32, bk=32, interpret=True,
                )
                ** 2
            )

        def loss_ref(q, k, v):
            return jnp.sum(
                dot_product_attention(q, k, v, mask=band) ** 2
            )

        np.testing.assert_allclose(
            np.asarray(
                ops.flash_attention(
                    q, k, v, causal=causal, window=window,
                    bq=32, bk=32, interpret=True,
                )
            ),
            np.asarray(dot_product_attention(q, k, v, mask=band)),
            rtol=2e-5, atol=2e-5,
        )
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
            )

    def test_sliding_window_validates(self):
        q = jnp.ones((1, 1, 128, 8))
        with pytest.raises(ValueError, match="window"):
            ops.flash_attention(q, q, q, window=0, interpret=True)

    @pytest.mark.parametrize("blocks", [(16, 16), (32, 16), (16, 32)])
    @pytest.mark.parametrize("causal,window", [
        (False, None), (True, None), (True, 24), (False, 24)])
    def test_bfloat16_inputs_match_dense_on_float32_copies(
            self, causal, window, blocks):
        """The kernels hand the MXU the input's dtype: bfloat16 q, k, v
        and dO, float32 accumulation, ``p`` and ``ds`` rounded to bfloat16
        for their products.  Forward and the three gradients against the
        dense form on the float32 copies of the same values, at a length
        where a row of tiles lies under, on and past the diagonal and the
        window's edge (S = 64, blocks of 16 and 32, a window of 24), in
        the relative Frobenius norm (`chip_smoke.py` bounds the max norm
        by 2e-2 / 4e-2).  What bfloat16 gives here: the output's own
        rounding (2^-9 a value) reads 1.8e-3 to 2.1e-3, the gradients
        2.6e-3 to 3.2e-3; the float32 upcast of the operands read 1.7e-3
        and up to 2.6e-3 (PR 30's kernel, same cases)."""
        from tpu_dist.nn.attention import dense_attention

        ks = jax.random.split(jax.random.key(0), 4)
        shape = (1, 2, 64, 16)
        q, k, v = (jax.random.normal(kk, shape).astype(jnp.bfloat16)
                   for kk in ks[:3])
        wgt = jax.random.normal(ks[3], shape)  # non-trivial cotangent
        bq, bk = blocks

        def loss_flash(q, k, v):
            out = ops.flash_attention(q, k, v, causal=causal, window=window,
                                      bq=bq, bk=bk, interpret=True)
            return jnp.sum(out.astype(jnp.float32) * wgt), out

        def loss_dense(q, k, v):
            out = dense_attention(q, k, v, causal=causal, window=window)
            return jnp.sum(out * wgt), out

        (_, out), grads = jax.value_and_grad(
            loss_flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        assert out.dtype == jnp.bfloat16
        assert all(g.dtype == jnp.bfloat16 for g in grads)
        (_, want), want_g = jax.value_and_grad(
            loss_dense, argnums=(0, 1, 2), has_aux=True)(
                *(x.astype(jnp.float32) for x in (q, k, v)))

        def rel(a, b):
            a, b = (np.asarray(x, np.float32) for x in (a, b))
            return float(np.linalg.norm(a - b) / np.linalg.norm(b))

        assert rel(out, want) < 4e-3
        for g, wg in zip(grads, want_g):
            assert rel(g, wg) < 7e-3

    @pytest.mark.parametrize("d", [16, 128, 160])
    def test_row_sums_with_and_without_spare_lanes(self, d):
        """Where the head size leaves lanes of a 128-wide MXU tile to
        spare (16: 112 of them, 160: 96), the forward takes p's row sums
        from a block of ones beside V; at 128 it reduces over lanes.  Both
        give the dense form's values and gradients."""
        from tpu_dist.nn import dot_product_attention

        ks = jax.random.split(jax.random.key(3), 3)
        q, k, v = (jax.random.normal(kk, (1, 1, 32, d)) for kk in ks)

        def loss(attn):
            return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)

        flash = functools.partial(ops.flash_attention, causal=True, bq=16,
                                  bk=16, interpret=True)
        dense = functools.partial(dot_product_attention, causal=True)
        np.testing.assert_allclose(
            np.asarray(flash(q, k, v)), np.asarray(dense(q, k, v)),
            rtol=2e-5, atol=2e-5)
        for a, b in zip(jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v),
                        jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)

    def test_default_blocks_follow_the_length(self):
        """512 where that divides the sequence, 256 where it does not,
        the whole sequence below that; the caller's blocks win."""
        from tpu_dist.ops.flash_attention import _blocks

        assert _blocks(1024, None, None) == (512, 512)
        assert _blocks(1280, None, None) == (256, 256)
        assert _blocks(512, None, None) == (512, 512)
        assert _blocks(64, None, None) == (64, 64)
        assert _blocks(1024, 128, None) == (128, 512)
        with pytest.raises(ValueError, match="not divisible"):
            _blocks(1000, None, None)

    def test_gqa_through_module_grads_match_dense(self, request):
        """VERDICT r4 #5: the Pallas backward kernels must hold for the
        GQA composition too — `nn.MultiHeadAttention(kv_heads < heads)`
        repeats K/V across each query-head group BEFORE the kernel, so
        the flash VJP's dK/dV must sum correctly back through the repeat.
        Compare the whole module's param grads: the dense form (what the
        selection takes here) against the interpreted kernel in its place."""
        from tpu_dist import nn as tnn

        attn = tnn.MultiHeadAttention(dim=32, heads=4, kv_heads=2, causal=True)
        params, _ = attn.init(jax.random.key(0), (1, 1024, 32))
        x = jax.random.normal(jax.random.key(1), (1, 1024, 32))

        def loss(p):
            out, _ = attn.apply(p, {}, x)
            return jnp.sum(out**2)

        g_dense = jax.grad(loss)(params)
        request.getfixturevalue("kernels_interpreted")
        g_flash = jax.grad(loss)(params)
        for a, b in zip(jax.tree.leaves(g_flash), jax.tree.leaves(g_dense)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
            )


class TestPallasRing:
    def test_off_tpu_without_interpret_raises(self):
        """Off-TPU the RDMA kernel cannot be compiled, and nothing is
        substituted for it: without ``interpret=True`` the entry point
        raises (the ppermute ring is `parallel.ring_all_reduce`, for a
        caller that wants it)."""
        import pytest

        from tests.conftest import spmd_run as run
        from tpu_dist import comm

        def fn():
            x = jnp.arange(8.0 * 128).reshape(8, 128) + comm.rank()
            return ops.ring_all_reduce_pallas(x)

        with pytest.raises(ValueError, match="[Ii]nterpret"):
            run(fn, world=4)

    def test_rdma_kernel_executes_under_interpret_mode(self):
        """VERDICT r4 #4: the RDMA ring kernel itself — neighborhood
        barriers, double-buffered comm slots, `make_async_remote_copy`
        hops — runs under Pallas's TPU interpret simulator on the
        CPU-sim mesh and must equal psum.  The compiled path is
        ``chip_smoke.py``'s kernels phase on a multi-chip host."""
        from tests.conftest import spmd_run as run
        from tpu_dist import comm

        world = 4

        def fn():
            r = comm.rank()
            # distinct per-rank payload: catches dropped/duplicated hops
            x = (jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
                 + 1000.0 * r)
            y = ops.ring_all_reduce_pallas(x, interpret=True)
            z = jax.lax.psum(x, comm.DEFAULT_AXIS)
            return y, z

        ys, zs = run(fn, world=world)
        np.testing.assert_allclose(
            np.asarray(ys), np.asarray(zs), rtol=1e-6
        )


class TestMatmulBlockSelection:
    def test_nondivisible_shapes_are_padded_and_correct(self):
        """ADVICE r3: shapes nothing >=128 divides used to fall back to a
        FULL-dimension block (VMEM-busting for large dims).  They are now
        padded to 128-multiples; results must still match XLA exactly."""
        from tpu_dist.ops.matmul import matmul

        x = jax.random.normal(jax.random.key(0), (520, 384))
        w = jax.random.normal(jax.random.key(1), (384, 520))
        b = jax.random.normal(jax.random.key(2), (520,))
        out = matmul(x, w, b, epilogue="relu", interpret=True)
        ref = jax.nn.relu(x @ w + b)
        assert out.shape == ref.shape
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_auto_blocks_respect_vmem_budget(self):
        """The fallback path applies the same VMEM bound as the main loop
        (ADVICE r3: it used to skip the check entirely)."""
        import importlib

        mm = importlib.import_module("tpu_dist.ops.matmul")

        for shape in [(512, 512, 512), (3072, 3072, 3072), (640, 640, 8192),
                      (128, 4096, 2048)]:
            bm, bn, bk = mm._auto_blocks(*shape)
            assert mm._vmem_bytes(bm, bn, bk) <= mm._VMEM_BUDGET, shape

    def test_grad_through_padded_shapes(self):
        from tpu_dist.ops.matmul import matmul

        x = jax.random.normal(jax.random.key(3), (260, 384))
        w = jax.random.normal(jax.random.key(4), (384, 260))

        def loss(x, w):
            return matmul(x, w, epilogue="gelu", interpret=True).sum()

        def loss_ref(x, w):
            return jax.nn.gelu(x @ w).sum()

        gk = jax.grad(loss, argnums=(0, 1))(x, w)
        gr = jax.grad(loss_ref, argnums=(0, 1))(x, w)
        for a, b_ in zip(gk, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=2e-4, atol=2e-4
            )


def test_explicit_divisible_block_suppresses_padding():
    """An explicit block that divides the dim must be honored — padding
    to a 128-multiple would orphan it (e.g. bm=500 divides m=3000 but
    nothing divides 3072) and degenerate to a full-dim block."""
    import importlib

    mm = importlib.import_module("tpu_dist.ops.matmul")
    # the pad decision is per-dim against the requested block
    x = jax.random.normal(jax.random.key(20), (600, 256))
    w = jax.random.normal(jax.random.key(21), (256, 256))
    out = mm.matmul(x, w, bm=300, interpret=True)  # 300 | 600: no pad
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(x @ w), rtol=2e-5, atol=2e-5
    )
    # and the auto path still pads 600 (no power-of-two >=128 divides it)
    assert mm._pick_block(600, 512) == 600
    out_auto = mm.matmul(x, w, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_auto), np.asarray(x @ w), rtol=2e-5, atol=2e-5
    )


def test_explicit_nondividing_block_skips_useless_padding():
    """Padding is only applied when it buys a dividing block: an explicit
    block that divides neither the dim nor its 128-multiple must not pay
    the pad copy (it would degenerate to a full-dim block either way)."""
    import importlib

    mm = importlib.import_module("tpu_dist.ops.matmul")
    x = jax.random.normal(jax.random.key(22), (600, 256))
    w = jax.random.normal(jax.random.key(23), (256, 128))
    # 500 divides neither 600 nor 640 -> no pad, single 600-row block
    out = mm.matmul(x, w, bm=500, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(x @ w), rtol=2e-5, atol=2e-5
    )
    # auto path: padding 600->640 buys 128-blocks, so it pads
    jaxpr = str(jax.make_jaxpr(lambda a, b: mm.matmul(a, b, interpret=True))(x, w))
    assert "pad" in jaxpr
    jaxpr_explicit = str(
        jax.make_jaxpr(lambda a, b: mm.matmul(a, b, bm=500, interpret=True))(x, w)
    )
    assert "pad" not in jaxpr_explicit


# ------------------------------------------------ the program picks its kernel


def _qkv(sq=1024, sk=None, d=16):
    q = jax.ShapeDtypeStruct((1, 2, sq, d), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 2, sk or sq, d), jnp.float32)
    return q, k, k


def _attend(q, k, v, **kw):
    from tpu_dist import nn

    return nn.dot_product_attention(q, k, v, causal=True, **kw)


def _lowered(fn, *shapes, platform="tpu", **jit_kw):
    """``fn``'s program as lowered FOR ``platform``, from this CPU host:
    the lowering rules are the platform's (a Pallas call becomes a Mosaic
    ``tpu_custom_call``, or is refused), no chip and no libtpu needed."""
    return jax.jit(fn, **jit_kw).trace(*shapes).lower(
        lowering_platforms=(platform,)).as_text()


def _flash_grads(q, k, v):
    return jax.grad(lambda *a: _attend(*a).sum(), argnums=(0, 1, 2))(q, k, v)


def _over_four_devices(partitioned_by, attend=_attend, shape=(4, 2, 1024, 16),
                       grid=(4, 1)):
    """``attend`` over a 4-device mesh ``dp x tp``, the batch sharded over
    ``dp``: either XLA partitions the program (the builder says so with
    `parallel.partitioned_over`, as `make_partitioned_train_step` does,
    naming the axes that split batch and heads, or ``"... of axes it does
    not name"``; ``"nobody says"`` leaves it out), or a `shard_map` body
    holds one device's share: manual over every axis of the mesh, or over
    ``dp`` alone beside a ``tp`` of size 1 that stays the compiler's."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_dist import parallel

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(grid), ("dp", "tp"))
    q = jax.ShapeDtypeStruct(shape, jnp.float32)
    # rows that do not divide arrive whole on every device
    sh = NamedSharding(mesh, P() if shape[0] % grid[0] else P("dp"))
    if partitioned_by in ("shard_map", "shard_map over dp alone"):
        names = {"dp"} if partitioned_by.endswith("alone") else {"dp", "tp"}
        fn = jax.shard_map(attend, mesh=mesh, in_specs=P("dp"),
                           out_specs=P("dp"), axis_names=names,
                           check_vma=False)
    elif partitioned_by.startswith("the compiler"):
        axes = ({} if partitioned_by.endswith("does not name") else
                dict(batch_axes=("dp",), head_axes=("tp",)))
        said = parallel.partitioned_over(mesh, **axes)

        def fn(q, k, v):
            with said:
                return attend(q, k, v)
    else:
        fn = attend
    return _lowered(fn, q, q, q, in_shardings=(sh, sh, sh), out_shardings=sh)


RULE_CASES = {
    # what sends attention to the dense form, lowered for a TPU ...
    "cross-attention lengths": (lambda: _lowered(_attend, *_qkv(1024, 2048)), 0),
    "S=128, one block of the kernel's": (lambda: _lowered(_attend, *_qkv(128)), 0),
    "S=512, under where the kernel stops losing": (
        lambda: _lowered(_attend, *_qkv(512)), 0),
    "S=1152, no multiple of its block": (lambda: _lowered(_attend, *_qkv(1152)), 0),
    "an explicit mask": (lambda: _lowered(
        lambda q, k, v, m: _attend(q, k, v, mask=m), *_qkv(),
        jax.ShapeDtypeStruct((1, 1, 1024, 1024), jnp.bool_)), 0),
    "a model's own scale": (lambda: _lowered(
        lambda q, k, v: _attend(q, k, v, scale=0.5), *_qkv()), 0),
    "partitioned over several devices, of axes it does not name": (
        lambda: _over_four_devices("the compiler, of axes it does not name"), 0),
    "partitioned, 6 rows over dp=4": (
        lambda: _over_four_devices("the compiler", shape=(6, 2, 1024, 16)), 0),
    "partitioned, 3 heads over tp=2": (
        lambda: _over_four_devices("the compiler", shape=(4, 3, 1024, 16),
                                   grid=(2, 2)), 0),
    "a shard_map that leaves an axis, of size 1, to the compiler": (
        lambda: _over_four_devices("shard_map over dp alone"), 0),
    # ... and what keeps the kernel
    "eligible": (lambda: _lowered(_attend, *_qkv()), 1),
    "eligible, S=2048": (lambda: _lowered(_attend, *_qkv(2048)), 1),
    "eligible, forward and backward": (lambda: _lowered(_flash_grads, *_qkv()), 3),
    "a shard_map body": (lambda: _over_four_devices("shard_map"), 1),
    # ... and a partitioned program whose builder names the axes that split
    # batch and heads: one device's share inside a `shard_map` of its own
    "partitioned over several devices": (
        lambda: _over_four_devices("the compiler"), 1),
    "partitioned, heads over tp=2": (
        lambda: _over_four_devices("the compiler", grid=(2, 2)), 1),
    "partitioned, forward and backward": (
        lambda: _over_four_devices("the compiler", _flash_grads), 3),
    # off the TPU the plain form, never the interpreter
    "eligible, lowered for the cpu": (
        lambda: _lowered(_attend, *_qkv(), platform="cpu"), 0),
}


@pytest.mark.parametrize("case", RULE_CASES)
def test_attention_picks_its_kernel_where_it_is_lowered(case):
    """`ops.kernel_for_platform` under `nn.dot_product_attention`: a Mosaic
    call in the text lowered for a TPU exactly where `flash_attention_takes`
    the shapes and XLA does not partition the program; the dense form
    (no Pallas call, compiled or interpreted) everywhere else."""
    text, kernels = RULE_CASES[case]
    text = text()
    assert text.count("@tpu_custom_call") == kernels, case
    names = [n for n in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")
             if f'kernel_name = "{n}"' in text]
    assert len(names) == kernels
    # the interpreter runs a kernel's grid as a loop; the dense form has none
    assert "stablehlo.while" not in text


@pytest.mark.parametrize("mesh_axes,shape,form", [
    ("fsdp=4", (4, 2, 1024, 16), "flash"),
    ("dp=2,fsdp=2", (4, 2, 1024, 16), "flash"),
    ("dp=2,tp=2", (2, 4, 1024, 16), "flash"),
    # what does not divide stays the dense form, and raises nothing
    ("fsdp=4", (6, 2, 1024, 16), "dense"),
    ("dp=2,tp=2", (2, 3, 1024, 16), "dense"),
])
def test_partitioned_attention_is_dense_attention(mesh_axes, shape, form, request):
    """Under the partition engine's rule sets on four devices: value and
    gradients of `nn.dot_product_attention`, one device's share computed
    by the kernels (interpreted here) inside its `shard_map`, are
    `dense_attention`'s; a batch or a head count that an axis does not
    divide keeps the dense form, which XLA partitions."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_dist import nn, parallel

    if form == "flash":
        request.getfixturevalue("kernels_interpreted")
    mesh = parallel.build_mesh(mesh_axes, mesh_devices=jax.devices()[:4])
    rules = parallel.resolve_rules(mesh_axes, mesh)
    said = parallel.partitioned_over(
        mesh, batch_axes=rules.data_axes, head_axes=rules.model_axes)

    def value_and_grads(attend):
        def loss(q, k, v):
            return (attend(q, k, v, causal=True) ** 2).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))

    def partitioned(q, k, v):
        with said:
            return value_and_grads(nn.dot_product_attention)(q, k, v)

    qkv = [jax.random.normal(k, shape) for k in jax.random.split(jax.random.key(3), 3)]
    sh = NamedSharding(mesh, rules.batch_spec() if form == "flash" else P())
    got = jax.jit(partitioned, in_shardings=(sh, sh, sh))(*qkv)
    want = value_and_grads(nn.attention.dense_attention)(*qkv)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)
    assert [a[0] for a in said.attention] == [form]
    assert said.per_device_traces == (form == "flash")


@pytest.mark.parametrize("described_by", ["nobody says",
                                          "shard_map over dp alone"])
def test_mosaic_refuses_a_partitioned_program(described_by):
    """What the rule stands in front of.  The four-device program whose
    builder says nothing reaches Mosaic, which cannot be partitioned; and
    Mosaic wants EVERY axis of a `shard_map`'s mesh manual, so the kernel
    itself is refused where one of size 1 is left over: the rule asks what
    Mosaic asks, not whether more than one device is involved."""
    attend = _attend
    if described_by != "nobody says":
        def attend(q, k, v):
            return ops.flash_attention(q, k, v, causal=True, interpret=False)
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        _over_four_devices(described_by, attend)
