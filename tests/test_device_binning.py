"""Device-side quantile binning (models/trees._PackedDesign._bin_device).

The device path (f32 sorts + quantile gathers + compare-sum digitize)
must reproduce the host f64 loop exactly on data where f32 is exact:
values that are small multiples of 1/8 and a row count whose m-1 is
divisible by every bin width, so np.quantile's interpolation lands on
sample points (frac = 0) and every comparison is representable.
"""
import numpy as np
import pytest

from transmogrifai_tpu.models import trees
from transmogrifai_tpu.models.trees import _PackedDesign


def _data(n=3201, seed=0):
    rng = np.random.default_rng(seed)
    cols = [
        rng.integers(0, 1000, size=n) / 8.0,     # high-card -> 32 bins
        rng.integers(0, 2, size=n).astype(float),  # binary -> 2 bins
        np.full(n, 3.5),                          # constant -> 2 bins
        rng.integers(0, 5, size=n) / 8.0,         # low-card -> 8 bins
    ]
    return np.stack(cols, axis=1)


def _assert_designs_equal(a: _PackedDesign, b: _PackedDesign):
    np.testing.assert_array_equal(np.asarray(a.binned),
                                  np.asarray(b.binned))
    np.testing.assert_array_equal(np.asarray(a.packed),
                                  np.asarray(b.packed))
    np.testing.assert_array_equal(a.widths, b.widths)
    np.testing.assert_array_equal(a.packed_thr, b.packed_thr)
    np.testing.assert_array_equal(a.col_thr, b.col_thr)


def test_device_matches_host(monkeypatch):
    X = _data()
    host = _PackedDesign(X, 32)
    monkeypatch.setattr(trees, "_bin_on_device", lambda elems: True)
    dev = _PackedDesign(X, 32)
    _assert_designs_equal(host, dev)


def test_device_matches_host_edge_rows(monkeypatch):
    """Fold-edge mode: edges from a subset, binning over all rows."""
    X = _data()
    edge_rows = np.arange(0, X.shape[0], 2)[:1601]  # m-1 = 1600
    host = _PackedDesign(X, 32, edge_rows=edge_rows)
    monkeypatch.setattr(trees, "_bin_on_device", lambda elems: True)
    dev = _PackedDesign(X, 32, edge_rows=edge_rows)
    _assert_designs_equal(host, dev)


def test_device_digitize_chunked(monkeypatch):
    """Row-chunk padding path: force tiny chunks and a ragged tail."""
    X = _data(n=777)
    host = _PackedDesign(X, 32)
    monkeypatch.setattr(trees, "_bin_on_device", lambda elems: True)
    monkeypatch.setattr(trees, "_HIST_CHUNK_ELEMS", 10_000)
    dev = _PackedDesign(X, 32)
    np.testing.assert_array_equal(np.asarray(host.binned),
                                  np.asarray(dev.binned))


def test_cpu_fits_stay_on_the_host():
    """The rule must not switch small/CPU fits off the bit-exact path."""
    X = _data(n=64)
    d = _PackedDesign(X, 32)
    assert isinstance(d.binned, np.ndarray)


@pytest.mark.parametrize("backend, elems, on_device", [
    ("cpu", 100_000_000, False),
    ("tpu", trees._DEVICE_BIN_MIN_ELEMS - 1, False),
    ("tpu", trees._DEVICE_BIN_MIN_ELEMS, True),
])
def test_binning_place_follows_backend_and_size(
        monkeypatch, retired_tree_switches, backend, elems, on_device):
    """Where a design is binned is worked out from the backend and the
    edge matrix's size alone; the variables that used to override it
    (``retired_tree_switches``) are not read."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert trees._DEVICE_BIN_MIN_ELEMS == 4_000_000
    assert trees._bin_on_device(elems) is on_device


def test_device_fit_quality(monkeypatch):
    """End-to-end: a GBT fit on device-binned design reaches the same
    training accuracy as the host-binned fit (edges may differ by
    float rounding on arbitrary data, so assert quality, not bits)."""
    rng = np.random.default_rng(1)
    n = 4000
    X = rng.normal(size=(n, 10))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    from transmogrifai_tpu.models.trees import GBTClassifier
    est = GBTClassifier(num_rounds=5, max_depth=3)
    acc_host = float(np.mean(
        est.fit_arrays(X, y).predict_arrays(X).data == y))
    monkeypatch.setattr(trees, "_bin_on_device", lambda elems: True)
    trees._DESIGN_CACHE.clear()
    acc_dev = float(np.mean(
        est.fit_arrays(X, y).predict_arrays(X).data == y))
    trees._DESIGN_CACHE.clear()
    assert acc_host > 0.9 and abs(acc_host - acc_dev) < 0.02
