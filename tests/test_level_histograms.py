"""The level histograms (models/trees._level_histograms) under every path
the resolver (``_hist_mode``) can choose: ``scatter`` (fused segment sums),
``matmul`` (one contraction over the whole bin indicator) and
``matmul_chunk`` (the same contraction, the indicator rebuilt per bin
block), each against the contraction written out in NumPy.
``_HIST_CHUNK_ELEMS`` is patched small enough that ``scatter`` runs several
feature blocks and ``matmul_chunk`` several bin blocks.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from transmogrifai_tpu.models import trees

MODES = ("scatter", "matmul", "matmul_chunk")

#: (rows, packed bins, columns, slots, stats columns)
SHAPES = [
    (1000, 50, 5, 8, 3),      # generic
    (777, 130, 9, 16, 2),     # odd row count, uneven column widths
    (64, 10, 2, 1, 4),        # single slot (level 0)
    (2100, 300, 20, 64, 2),   # many slots
    (512, 2200, 40, 4, 2),    # wide packed axis: hundreds of bin blocks
]


def _packed_design(rng, n, total_bins, d):
    """A packed bin matrix as ``_PackedDesign`` lays it out: column f owns
    the bins [offset_f, offset_f + width_f), widths uneven."""
    widths = np.full(d, total_bins // d)
    widths[:total_bins % d] += 1
    offsets = np.concatenate([[0], np.cumsum(widths)[:-1]])
    packed = (offsets[None, :]
              + rng.integers(0, widths[None, :], size=(n, d))).astype(np.int32)
    feat_of = np.repeat(np.arange(d, dtype=np.int32), widths)
    return packed, feat_of


def _reference(packed, slot, stats, num_slots, total_bins):
    bin_oh = np.zeros((packed.shape[0], total_bins))
    bin_oh[np.arange(packed.shape[0])[:, None], packed] = 1.0
    return np.einsum("nc,ns,nb->cbs", np.eye(num_slots)[slot], stats,
                     bin_oh)


def _histograms(mode, packed, feat_of, slot, stats, num_slots, total_bins):
    packed, feat_of, stats = (jnp.asarray(packed), jnp.asarray(feat_of),
                              jnp.asarray(stats))
    bin_oh = (trees._bin_indicator(packed, total_bins, stats.dtype, feat_of)
              if mode == "matmul" else None)
    return np.asarray(trees._level_histograms(
        packed, jnp.asarray(slot), stats, num_slots, total_bins, bin_oh,
        mode=mode, feat_of=feat_of))


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("n,total_bins,d,num_slots,s_dim", SHAPES)
def test_matches_numpy_contraction(monkeypatch, mode, n, total_bins, d,
                                   num_slots, s_dim):
    rng = np.random.default_rng(n + total_bins)
    monkeypatch.setattr(trees, "_HIST_CHUNK_ELEMS", 8 * n)
    packed, feat_of = _packed_design(rng, n, total_bins, d)
    slot = rng.integers(0, num_slots, size=n).astype(np.int32)
    stats = rng.normal(size=(n, s_dim))
    got = _histograms(mode, packed, feat_of, slot, stats, num_slots,
                      total_bins)
    assert got.shape == (num_slots, total_bins, s_dim)
    np.testing.assert_allclose(
        got, _reference(packed, slot, stats, num_slots, total_bins),
        atol=1e-9)


@pytest.mark.parametrize("mode", MODES)
def test_zero_stats_rows_are_inert(monkeypatch, mode):
    """Fold masks and row padding rely on zero stats contributing
    nothing, whatever slot and bins the row holds."""
    rng = np.random.default_rng(0)
    n, total_bins, d, num_slots, s_dim = 100, 20, 4, 4, 2
    monkeypatch.setattr(trees, "_HIST_CHUNK_ELEMS", 8 * n)
    packed, feat_of = _packed_design(rng, n, total_bins, d)
    slot = rng.integers(0, num_slots, size=n).astype(np.int32)
    stats = rng.normal(size=(n, s_dim))
    stats[50:] = 0.0
    got = _histograms(mode, packed, feat_of, slot, stats, num_slots,
                      total_bins)
    np.testing.assert_allclose(
        got, _reference(packed[:50], slot[:50], stats[:50], num_slots,
                        total_bins), atol=1e-10)
