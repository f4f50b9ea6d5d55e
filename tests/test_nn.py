"""Unit tests for the nn layer library (the torch.nn-role components)."""

import jax
import jax.numpy as jnp
import numpy as np

from tpu_dist import nn


def test_dense_shapes_and_linearity():
    layer = nn.Dense(5)
    params, state = layer.init(jax.random.key(0), (3,))
    x = jnp.ones((4, 3))
    y, _ = layer.apply(params, state, x)
    assert y.shape == (4, 5)
    y2, _ = layer.apply(params, state, 2 * x)
    np.testing.assert_allclose(2 * (y - params["b"]), y2 - params["b"], rtol=1e-5)


def test_conv_shape_inference_matches_apply():
    layer = nn.Conv2D(7, 5)
    params, state = layer.init(jax.random.key(0), (28, 28, 1))
    assert layer.out_shape((28, 28, 1)) == (24, 24, 7)
    y, _ = layer.apply(params, state, jnp.ones((2, 28, 28, 1)))
    assert y.shape == (2, 24, 24, 7)


def test_maxpool():
    layer = nn.MaxPool2D(2)
    x = jnp.arange(16.0).reshape(1, 4, 4, 1)
    y, _ = layer.apply({}, {}, x)
    np.testing.assert_allclose(y[0, :, :, 0], [[5.0, 7.0], [13.0, 15.0]])


def test_dropout_train_vs_eval():
    layer = nn.Dropout(0.5)
    x = jnp.ones((100, 100))
    y_eval, _ = layer.apply({}, {}, x, train=False)
    np.testing.assert_allclose(np.asarray(y_eval), np.asarray(x))
    y_train, _ = layer.apply({}, {}, x, train=True, key=jax.random.key(0))
    kept = float((np.asarray(y_train) > 0).mean())
    assert 0.45 < kept < 0.55
    np.testing.assert_allclose(np.asarray(y_train)[np.asarray(y_train) > 0], 2.0)


def test_dropout2d_drops_whole_channels():
    layer = nn.Dropout2D(0.5)
    x = jnp.ones((4, 8, 8, 32))
    y, _ = layer.apply({}, {}, x, train=True, key=jax.random.key(1))
    y = np.asarray(y)
    per_channel = y.reshape(4, 64, 32)
    for b in range(4):
        for c in range(32):
            vals = np.unique(per_channel[b, :, c])
            assert len(vals) == 1, "channel must be uniformly kept or dropped"


def test_batchnorm_normalizes_and_tracks_stats():
    layer = nn.BatchNorm()
    params, state = layer.init(jax.random.key(0), (4,))
    x = jax.random.normal(jax.random.key(2), (256, 4)) * 3.0 + 5.0
    y, new_state = layer.apply(params, state, x, train=True)
    np.testing.assert_allclose(np.asarray(y.mean(0)), np.zeros(4), atol=1e-4)
    np.testing.assert_allclose(np.asarray(y.std(0)), np.ones(4), atol=1e-2)
    assert not np.allclose(np.asarray(new_state["mean"]), 0.0)


def test_layernorm():
    layer = nn.LayerNorm()
    params, state = layer.init(jax.random.key(0), (8,))
    x = jax.random.normal(jax.random.key(3), (5, 8)) * 4 + 2
    y, _ = layer.apply(params, state, x)
    np.testing.assert_allclose(np.asarray(y.mean(-1)), np.zeros(5), atol=1e-5)


def test_mha_shapes_and_causality():
    layer = nn.MultiHeadAttention(16, 4, causal=True)
    params, state = layer.init(jax.random.key(0), (6, 16))
    x = jax.random.normal(jax.random.key(4), (2, 6, 16))
    y, _ = layer.apply(params, state, x)
    assert y.shape == (2, 6, 16)
    # causality: output at position 0 must not change if later tokens change
    x2 = x.at[:, 3:].set(0.0)
    y2, _ = layer.apply(params, state, x2)
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(y2[:, 0]), atol=1e-6)


def test_flash_in_dot_product_attention(request):
    """Where the selection takes the flash kernel (the interpreter stands
    in for it here) the results match the dense form it takes off the TPU."""
    q = jax.random.normal(jax.random.key(0), (1, 2, 1024, 16))
    dense = nn.dot_product_attention(q, q, q, causal=True)
    request.getfixturevalue("kernels_interpreted")
    flash = nn.dot_product_attention(q, q, q, causal=True)
    np.testing.assert_allclose(
        np.asarray(flash), np.asarray(dense), rtol=2e-5, atol=2e-5
    )


def test_losses_known_values():
    logp = jnp.log(jnp.array([[0.7, 0.2, 0.1], [0.1, 0.8, 0.1]]))
    targets = jnp.array([0, 1])
    loss = nn.nll_loss(logp, targets)
    np.testing.assert_allclose(
        float(loss), -(np.log(0.7) + np.log(0.8)) / 2, rtol=1e-6
    )
    assert float(nn.accuracy(logp, targets)) == 1.0


def test_sequential_threads_state():
    net = nn.Sequential([nn.Dense(4), nn.BatchNorm(), nn.relu(), nn.Dense(2)])
    params, state = net.init(jax.random.key(0), (3,))
    x = jax.random.normal(jax.random.key(5), (10, 3))
    y, new_state = net.apply(params, state, x, train=True)
    assert y.shape == (10, 2)
    # BatchNorm state (index 1) must have been updated
    assert not np.allclose(
        np.asarray(new_state[1]["mean"]), np.asarray(state[1]["mean"])
    )


class TestAttentionMask:
    def test_allow_all_mask_is_identity(self):
        import jax.numpy as jnp
        import numpy as np

        from tpu_dist import nn

        q = jax.random.normal(jax.random.key(0), (2, 2, 6, 8))
        k = jax.random.normal(jax.random.key(1), (2, 2, 6, 8))
        v = jax.random.normal(jax.random.key(2), (2, 2, 6, 8))
        base = nn.dot_product_attention(q, k, v, causal=True)
        masked = nn.dot_product_attention(
            q, k, v, causal=True, mask=jnp.ones((6, 6), bool)
        )
        np.testing.assert_allclose(
            np.asarray(base), np.asarray(masked), atol=1e-6
        )

    def test_padding_mask_equals_trimmed_computation(self):
        """Masking out trailing pad keys gives the same outputs on the
        real positions as running the trimmed sequence."""
        import jax.numpy as jnp
        import numpy as np

        from tpu_dist import nn

        s_real, s_pad = 5, 8
        q = jax.random.normal(jax.random.key(0), (1, 2, s_pad, 8))
        k = jax.random.normal(jax.random.key(1), (1, 2, s_pad, 8))
        v = jax.random.normal(jax.random.key(2), (1, 2, s_pad, 8))
        keymask = (jnp.arange(s_pad) < s_real)[None, None, None, :]
        full = nn.dot_product_attention(q, k, v, mask=keymask)
        trimmed = nn.dot_product_attention(
            q[..., :s_real, :], k[..., :s_real, :], v[..., :s_real, :]
        )
        np.testing.assert_allclose(
            np.asarray(full[..., :s_real, :]), np.asarray(trimmed),
            atol=1e-5,
        )

    def test_fully_masked_row_is_zero_not_nan(self):
        import jax.numpy as jnp
        import numpy as np

        from tpu_dist import nn

        q = jax.random.normal(jax.random.key(0), (1, 1, 3, 4))
        k = jax.random.normal(jax.random.key(1), (1, 1, 3, 4))
        v = jax.random.normal(jax.random.key(2), (1, 1, 3, 4))
        out = nn.dot_product_attention(
            q, k, v, mask=jnp.zeros((3, 3), bool)
        )
        np.testing.assert_array_equal(np.asarray(out), 0.0)

    def test_lm_padding_mask_matches_trimmed_prefix(self):
        """LM logits at real positions with a padding mask equal the
        logits of the trimmed batch (learned positions, causal)."""
        import jax.numpy as jnp
        import numpy as np

        from tpu_dist import models

        lm = models.TransformerLM(
            vocab=64, dim=32, depth=2, heads=4, max_seq=16
        )
        params, _ = lm.init(jax.random.key(0))
        tokens = models.synthetic_tokens(2, 8, 64)
        padded = jnp.pad(tokens, ((0, 0), (0, 4)))
        mask = (jnp.arange(12) < 8)[None, :].repeat(2, 0)
        full, _ = lm.apply(params, {}, padded, attn_mask=mask)
        trimmed, _ = lm.apply(params, {}, tokens)
        np.testing.assert_allclose(
            np.asarray(full[:, :8]), np.asarray(trimmed), atol=1e-5
        )

    def test_sliding_window_mask_limits_reach(self):
        import jax.numpy as jnp
        import numpy as np

        from tpu_dist import nn

        m = np.asarray(nn.sliding_window_mask(5, 2))
        # query 3 sees keys 2..4 bidirectionally (window 2: |i-j| < 2)
        np.testing.assert_array_equal(m[3], [False, False, True, True, True])
        # with causal AND: attention where only the last `window` keys count
        q = jax.random.normal(jax.random.key(0), (1, 1, 5, 4))
        k = jax.random.normal(jax.random.key(1), (1, 1, 5, 4))
        v = jax.random.normal(jax.random.key(2), (1, 1, 5, 4))
        out = nn.dot_product_attention(
            q, k, v, causal=True, mask=nn.sliding_window_mask(5, 2)
        )
        # query 4 attends to keys {3,4} only == attention on that slice
        ref = nn.dot_product_attention(
            q[..., 4:, :], k[..., 3:, :], v[..., 3:, :], causal=True
        )
        np.testing.assert_allclose(
            np.asarray(out[..., 4, :]), np.asarray(ref[..., 0, :]),
            atol=1e-5,
        )
        import pytest

        with pytest.raises(ValueError, match="window"):
            nn.sliding_window_mask(5, 0)

    def test_segment_mask_packed_equals_per_document(self):
        """Packed two-document training: causal + segment mask gives the
        same logits as each document alone."""
        import jax.numpy as jnp
        import numpy as np

        from tpu_dist import models, nn

        lm = models.TransformerLM(
            vocab=64, dim=32, depth=1, heads=4, max_seq=16
        )
        params, _ = lm.init(jax.random.key(0))
        a = models.synthetic_tokens(1, 6, 64, seed=1)
        b = models.synthetic_tokens(1, 6, 64, seed=2)
        packed = jnp.concatenate([a, b], axis=1)  # (1, 12)
        segs = jnp.asarray([[0] * 6 + [1] * 6])
        # segment mask blocks cross-document attention; the learned
        # positional table still differs for doc b (positions 6..11), so
        # compare against a trimmed run with matching positions: doc a.
        logits, _ = lm.apply(
            params, {}, packed, attn_mask=nn.segment_mask(segs)
        )
        la, _ = lm.apply(params, {}, a)
        np.testing.assert_allclose(
            np.asarray(logits[:, :6]), np.asarray(la), atol=1e-5
        )


def test_sequential_rejects_mismatched_trees():
    """A bare {} (or truncated tree) must raise, not silently apply
    zero layers and return the input unchanged (the zip-truncation
    footgun found while writing the accum HLO test)."""
    import pytest

    from tpu_dist import models

    model = models.mnist_net()
    params, state = model.init(jax.random.key(0), models.IN_SHAPE)
    x = jnp.zeros((2,) + models.IN_SHAPE, jnp.float32)
    with pytest.raises(ValueError, match="param entries"):
        model.apply(params, {}, x)
    with pytest.raises(ValueError, match="param entries"):
        model.apply((), state, x)
    # the real trees still work
    y, _ = model.apply(params, state, x)
    assert y.shape == (2, 10)


class TestSlidingWindowAttention:
    def test_module_matches_dense_band(self):
        """MHA(sliding_window=w): the parallel forward equals plain
        attention under the band mask, flash on AND off."""
        from tpu_dist import nn as tnn
        from tpu_dist.nn.attention import sliding_window_mask

        w = 4
        attn = tnn.MultiHeadAttention(
            dim=16, heads=2, causal=True, sliding_window=w
        )
        ref = tnn.MultiHeadAttention(dim=16, heads=2, causal=True)
        params, _ = attn.init(jax.random.key(0), (2, 16, 16))
        x = jax.random.normal(jax.random.key(1), (2, 16, 16))
        # full (sq, sk) mask: add broadcast dims (a bare 2-D mask means
        # key padding (b, s) to the module)
        band = sliding_window_mask(16, w)[None, None]
        want, _ = ref.apply(params, {}, x, mask=band)
        got, _ = attn.apply(params, {}, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_flash_path_matches_dense_path(self, request):
        from tpu_dist import nn as tnn

        attn = tnn.MultiHeadAttention(
            dim=32, heads=2, causal=True, sliding_window=320
        )
        params, _ = attn.init(jax.random.key(2), (1, 1024, 32))
        x = jax.random.normal(jax.random.key(3), (1, 1024, 32))
        dense, _ = attn.apply(params, {}, x)
        request.getfixturevalue("kernels_interpreted")
        flash, _ = attn.apply(params, {}, x)
        np.testing.assert_allclose(
            np.asarray(flash), np.asarray(dense), rtol=2e-4, atol=2e-4
        )

    def test_cached_decode_matches_parallel_forward(self):
        """Windowed prefill through the KV cache equals the windowed
        parallel forward — decode and training see the same band."""
        from tpu_dist import nn as tnn

        attn = tnn.MultiHeadAttention(
            dim=16, heads=2, causal=True, sliding_window=3
        )
        params, _ = attn.init(jax.random.key(4), (2, 8, 16))
        x = jax.random.normal(jax.random.key(5), (2, 8, 16))
        want, _ = attn.apply(params, {}, x)
        z = jnp.zeros((2, 2, 12, 8), jnp.float32)
        got, _, _ = attn.apply_cached(params, x, z, z, 0)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_validates(self):
        import pytest

        from tpu_dist import nn as tnn

        with pytest.raises(ValueError, match="sliding_window"):
            tnn.MultiHeadAttention(dim=8, heads=2, sliding_window=0)
