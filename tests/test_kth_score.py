"""`ops.kth_score`: a selecting layer's picks from two numbers a row.

The oracle is the selection as it stood while it sorted: `lax.top_k` for
the k-th value and a running count down the row for the ties
(`_sorted_mask`), and `lax.top_k`'s own picks for the last tied place
(`_sorted_kth_and_last`, decode's rule of PR 44).
`nn.latent_attention.top_visible` must give that mask bit for bit from
the two numbers `kth_and_last` finds without sorting;
tests/test_hlo_structure.py compiles the serving programs for the v5e and
looks for the sort.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from tpu_dist.nn.latent_attention import top_visible
from tpu_dist.ops import kth_score


def _sorted_mask(scores, causal, k):
    if k >= scores.shape[-1]:
        return causal
    s = jnp.where(causal, scores, -jnp.inf)
    kth = lax.top_k(s, k)[0][..., -1:]
    above, tie = s > kth, s == kth
    room = k - above.sum(-1, keepdims=True)
    return causal & (above | (tie & (jnp.cumsum(tie, axis=-1) <= room)))


def _sorted_kth_and_last(visible, k):
    # one zero: `lax.top_k` orders -0.0 under +0.0, a comparison ties them
    best, picks = lax.top_k(jnp.where(visible == 0, 0.0, visible), k)
    kth = best[:, -1:]
    last = jnp.where(best == kth, picks, -1).max(axis=-1, keepdims=True)
    return kth, jnp.where(kth == -jnp.inf, visible.shape[-1] - 1, last)


def _random(rng, R, L):
    return rng.normal(size=(R, L)).astype(np.float32), np.ones((R, L), bool)


def _ties(rng, R, L):
    """A few values a row, so the k-th is shared by many places."""
    return rng.integers(-2, 3, size=(R, L)).astype(np.float32) / 4, np.ones((R, L), bool)


def _zeros(rng, R, L):
    """Both zeros, which compare equal and whose bits do not, between
    negative and positive scores."""
    s = rng.choice(np.float32([-1.5, -0.0, 0.0, 2.0]), size=(R, L), p=[0.3, 0.3, 0.3, 0.1])
    return s, np.ones((R, L), bool)


def _extremes(rng, R, L):
    s = rng.choice(np.float32([np.inf, 3e38, 1e-45, -1e-45, -3e38, 1.0, -1.0]), size=(R, L))
    return s, rng.random((R, L)) < 0.8


def _ragged(rng, R, L):
    """Rows that see a prefix of every length from none to all."""
    seen = np.linspace(0, L, R).astype(int)
    s = np.round(rng.normal(size=(R, L)), 1).astype(np.float32)
    return s, np.arange(L)[None, :] < seen[:, None]


def _nothing(rng, R, L):
    return rng.normal(size=(R, L)).astype(np.float32), np.zeros((R, L), bool)


KINDS = {"random": _random, "ties": _ties, "zeros": _zeros, "extremes": _extremes,
         "ragged": _ragged, "nothing": _nothing}
# (rows, places, k): sizes that are no power of two, a k one less than
# every place, a k of one
SHAPES = [(5, 300, 17), (16, 1024, 128), (3, 130, 129), (24, 256, 1)]


@pytest.fixture(params=[(kind, shape) for kind in KINDS for shape in SHAPES],
                ids=lambda p: f"{p[0]}-{'x'.join(map(str, p[1]))}")
def case(request):
    kind, (R, L, k) = request.param
    scores, causal = KINDS[kind](np.random.default_rng(R * L + k), R, L)
    return jnp.asarray(scores), jnp.asarray(causal), k


def test_the_mask_is_the_sorted_selections(case):
    scores, causal, k = case
    np.testing.assert_array_equal(top_visible(scores, causal, k), _sorted_mask(scores, causal, k))


def test_the_search_finds_what_the_sort_gives(case):
    scores, causal, k = case
    visible = jnp.where(causal, scores, -jnp.inf)
    for got, want in zip(kth_score.kth_and_last(visible, k), _sorted_kth_and_last(visible, k)):
        assert (got.shape, got.dtype) == (want.shape, want.dtype)
        np.testing.assert_array_equal(got, want)
        # bit for bit: +0.0 for a k-th value of either zero
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


@pytest.mark.parametrize("lead", [(), (2,), (2, 3)])
def test_rows_of_any_leading_shape_and_every_place_where_k_covers_them(lead):
    rng = np.random.default_rng(0)
    scores = jnp.asarray(np.round(rng.normal(size=lead + (4, 40)), 1).astype(np.float32))
    causal = jnp.asarray(rng.random(lead + (4, 40)) < 0.7)
    np.testing.assert_array_equal(top_visible(scores, causal, 9), _sorted_mask(scores, causal, 9))
    for k in (40, 64):
        assert top_visible(scores, causal, k) is causal


@pytest.mark.parametrize("k", [0, 8, 9])
def test_a_k_that_picks_nothing_or_everything_is_refused(k):
    with pytest.raises(ValueError, match="places"):
        kth_score.kth_and_last(jnp.zeros((2, 8)), k)


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_no_platform_sorts_for_the_selection(platform):
    fn = jax.jit(lambda s: top_visible(s, jnp.ones(s.shape, bool), 16))
    text = fn.trace(jax.ShapeDtypeStruct((4, 256), jnp.float32)).lower(
        lowering_platforms=(platform,)).as_text()
    assert "top_k" not in text and "sort" not in text and "custom_call" not in text
    assert text.count("stablehlo.while") == 2
