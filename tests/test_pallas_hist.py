"""Pallas fused level-histogram kernel (models/pallas_hist.py).

Here the kernel runs under the Pallas interpreter (``interpret=True``);
on TPU the same code compiles via Mosaic (chip_smoke.py). Reference
result is the matmul-strategy einsum (models/trees._level_histograms),
which these tests reproduce in numpy.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from transmogrifai_tpu.models.pallas_hist import pallas_level_hist


def _reference(bin_oh, slot, stats, C):
    return np.einsum("nc,ns,nb->cbs",
                     np.eye(C, dtype=np.float32)[slot], stats, bin_oh)


@pytest.mark.parametrize(
    "n,TB,C,S",
    [
        (1000, 50, 8, 3),     # generic
        (777, 130, 16, 2),    # n not a multiple of the row block,
                              # TB just past one lane tile
        (64, 10, 1, 4),       # single slot (level 0)
        (2100, 300, 64, 2),   # many slots, multiple row blocks
        (512, 2200, 4, 2),    # TB beyond one tile -> multi-tile grid
    ])
def test_matches_einsum(n, TB, C, S):
    rng = np.random.default_rng(n + TB)
    bin_oh = np.zeros((n, TB), np.float32)
    # multi-hot rows like real packed designs (several ones per row)
    for _ in range(3):
        bin_oh[np.arange(n), rng.integers(0, TB, size=n)] = 1.0
    slot = rng.integers(0, C, size=n)
    stats = rng.normal(size=(n, S)).astype(np.float32)
    ref = _reference(bin_oh, slot, stats, C)
    got = np.asarray(pallas_level_hist(
        jnp.asarray(bin_oh), jnp.asarray(slot), jnp.asarray(stats), C,
        interpret=True))
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-5)


def test_zero_stats_rows_are_inert():
    """Row padding relies on zero stats contributing nothing."""
    rng = np.random.default_rng(0)
    n, TB, C, S = 100, 20, 4, 2
    bin_oh = np.zeros((n, TB), np.float32)
    bin_oh[np.arange(n), rng.integers(0, TB, size=n)] = 1.0
    slot = rng.integers(0, C, size=n)
    stats = rng.normal(size=(n, S)).astype(np.float32)
    stats[50:] = 0.0
    got = np.asarray(pallas_level_hist(
        jnp.asarray(bin_oh), jnp.asarray(slot), jnp.asarray(stats), C,
        interpret=True))
    ref = _reference(bin_oh[:50], slot[:50], stats[:50], C)
    np.testing.assert_allclose(got, ref, atol=1e-5)
