"""Binning and the packed design of a tree fit, the per-tree feature pools,
and the memo that bins a fold's matrix once for every family.

A fit quantile-bins its matrix once (on the host, or on the device for a
large table: ``forms._bin_on_device``) into a :class:`_PackedDesign`: every
feature's bins packed into one flat axis, which the level grower
(``grower.py``) contracts. A pooled forest tree draws its own narrower
design from the binned columns (``_tree_pool``).
"""
from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import forms as _forms


# ---------------------------------------------------------------------------
# binning — packed variable-width bins
# ---------------------------------------------------------------------------
#
# Transmogrified feature matrices are dominated by one-hot columns with
# only two distinct values; giving every feature a uniform ``max_bins``-
# wide histogram wastes ~max_bins/2 x HBM traffic on them. Instead each
# feature gets its own bin count (pow2-quantized so fold-to-fold
# cardinality jitter doesn't change compiled shapes) and all features'
# bins are PACKED into one flat axis of ``total_bins`` entries. Per-level
# histograms are then (slots, total_bins, S) — one MXU contraction against
# the bin indicator on the chip, one fused scatter-add on a CPU (see
# forms._hist_mode) — and split gains come from a single segmented
# cumulative sum over the packed axis.


@jax.jit
def _device_sort_stats(E: jnp.ndarray):
    """Column-sorted copy + per-column unique count of the edge-row
    matrix — the device half of width/edge estimation."""
    s = jnp.sort(E, axis=0)
    uniq = 1 + jnp.sum(jnp.diff(s, axis=0) != 0, axis=0)
    return s, uniq


@jax.jit
def _device_edge_gather(sT: jnp.ndarray, lo: jnp.ndarray,
                        frac: jnp.ndarray) -> jnp.ndarray:
    """np.quantile's linear interpolation, vectorized: value at sorted
    position ``lo + frac`` per (feature, interior-quantile)."""
    m = sT.shape[1]
    vlo = jnp.take_along_axis(sT, lo, axis=1)
    vhi = jnp.take_along_axis(sT, jnp.minimum(lo + 1, m - 1), axis=1)
    return vlo + frac * (vhi - vlo)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _device_digitize(Xp: jnp.ndarray, edges: jnp.ndarray,
                     chunk: int) -> jnp.ndarray:
    """searchsorted(edges_f, x, side="left") for every feature column:
    the bin index is the count of that feature's edges strictly below
    x (+inf padding never counts). Row-chunked via lax.map so the
    (chunk, d, max_width) compare transient stays bounded."""
    k = Xp.shape[0] // chunk

    def one(xb):
        return jnp.sum(xb[:, :, None] > edges[None], axis=-1,
                       dtype=jnp.int32)
    return jax.lax.map(one, Xp.reshape(k, chunk, -1)).reshape(
        Xp.shape[0], -1)


class _PackedDesign:
    """Host-prepared binning of a feature matrix (one per fit).

    Attributes (n rows, d features, TB = sum of per-feature bin counts):
      packed    (n, d) int32 — packed bin index of row i, feature f
                (feature f's block spans [offset_f, offset_f + B_f))
      feat_of   (TB,) int32 — original feature id per packed bin
      block_start (TB,) int32 — packed index of the owning block's start
      packed_thr (TB,) float — split threshold "x <= thr" when splitting
                at this bin; +inf marks last/padded bins (not a split)
    """

    __slots__ = ("packed", "feat_of", "block_start", "packed_thr",
                 "binned", "col_thr", "widths", "max_width", "n", "d",
                 "total_bins")

    def __init__(self, X: np.ndarray, max_bins: int,
                 edge_rows: Optional[np.ndarray] = None):
        """``edge_rows`` restricts QUANTILE-EDGE estimation to those
        rows (the fold-train rows under ``TX_TREE_EDGES=fold``) while
        still binning every row of ``X`` — out-of-fold rows never
        influence where the splits can fall."""
        n, d = X.shape          # numpy or device array — never download
        e_rows = n if edge_rows is None else len(edge_rows)
        if _forms._bin_on_device(e_rows * d):
            thr_parts, widths, binned = self._bin_device(
                X, max_bins, edge_rows)
        else:
            thr_parts, widths, binned = self._bin_host(
                X, max_bins, edge_rows)
        offsets = np.concatenate([[0], np.cumsum(widths)[:-1]]).astype(np.int32)
        self.n, self.d = n, d
        self.total_bins = int(np.sum(widths))
        #: (n, d) per-feature bin ids (uniform addressing for feature-
        #: pool gathers) and (d, max_width) per-feature thresholds
        #: (+inf padded = not-a-split). Device-binned designs keep the
        #: two (n, d) matrices as DEVICE arrays — their only consumer
        #: (_design_args) re-uploads host copies otherwise, and a
        #: 1M x 100 int32 device->host->device round-trip is pure waste.
        self.binned = binned
        self.widths = np.asarray(widths, dtype=np.int64)
        self.max_width = int(max(widths))
        self.col_thr = np.full((d, self.max_width), np.inf)
        for f in range(d):
            t = thr_parts[f]
            self.col_thr[f, :len(t)] = t
        self.packed = binned + (jnp.asarray(offsets[None, :])
                                if isinstance(binned, jnp.ndarray)
                                else offsets[None, :])
        self.feat_of = np.repeat(np.arange(d, dtype=np.int32), widths)
        self.block_start = np.repeat(offsets, widths)
        self.packed_thr = np.concatenate(thr_parts)

    @staticmethod
    def _bin_host(X: np.ndarray, max_bins: int,
                  edge_rows: Optional[np.ndarray]):
        """Exact f64 per-feature binning (the reference semantics)."""
        X = np.asarray(X, dtype=np.float64)
        E = X if edge_rows is None else X[edge_rows]
        binned_cols, thr_parts, widths = [], [], []
        for f in range(X.shape[1]):
            col = E[:, f]
            uniq = np.unique(col)
            if uniq.size <= 2:
                edges = uniq[:1]                     # one edge, two bins
                width = 2
            else:
                width = int(min(max_bins,
                                1 << int(np.ceil(np.log2(uniq.size)))))
                width = max(width, 4)
                qs = np.linspace(0.0, 1.0, width + 1)[1:-1]
                edges = np.unique(np.quantile(col, qs))
                if edges.size < width - 1:           # dedup left empty bins
                    edges = np.concatenate(
                        [edges, np.full(width - 1 - edges.size, np.inf)])
            binned_cols.append(
                np.searchsorted(edges, X[:, f],
                                side="left").astype(np.int32))
            thr_parts.append(np.concatenate([edges, [np.inf]]))
            widths.append(width)
        return thr_parts, widths, np.stack(binned_cols, axis=1)

    @staticmethod
    def _bin_device(X, max_bins: int, edge_rows: Optional[np.ndarray]):
        """f32 device binning: one column sort + unique count, one
        quantile-interpolation gather, one chunked compare-sum
        digitize — same width/edge/dedup semantics as _bin_host, with
        only small (d,)-shaped metadata crossing to the host."""
        Xd = jnp.asarray(X, jnp.float32)
        n, d = Xd.shape
        Ed = Xd if edge_rows is None else Xd[jnp.asarray(edge_rows)]
        m = int(Ed.shape[0])
        s, uniq_d = _device_sort_stats(Ed)
        uniq = np.asarray(uniq_d)
        widths = np.where(
            uniq <= 2, 2,
            np.clip(np.exp2(np.ceil(np.log2(np.maximum(uniq, 2)))),
                    4, max_bins)).astype(np.int64)
        maxw = int(widths.max())
        # interior quantile positions (host f64 math on (d, maxw-1)
        # metadata; only the value gather runs in f32)
        j = np.arange(max(maxw - 1, 1))
        q = (j[None, :] + 1) / widths[:, None].astype(np.float64)
        h = np.clip(q, 0.0, 1.0) * (m - 1)
        lo = np.floor(h).astype(np.int32)
        edges = np.asarray(_device_edge_gather(
            s.T, jnp.asarray(lo),
            jnp.asarray((h - lo).astype(np.float32))), np.float64)
        colmin = np.asarray(s[0])
        thr_parts: List[np.ndarray] = []
        for f in range(d):
            w = int(widths[f])
            if uniq[f] <= 2:
                e = colmin[f:f + 1]
            else:
                e = np.unique(edges[f, :w - 1])
                if e.size < w - 1:                   # dedup left empty bins
                    e = np.concatenate(
                        [e, np.full(w - 1 - e.size, np.inf)])
            thr_parts.append(np.concatenate([e, [np.inf]]))
        col_edges = np.full((d, maxw), np.inf)
        for f in range(d):
            t = thr_parts[f][:-1]                    # real edges only
            col_edges[f, :len(t)] = t
        chunk = max(256, min(n, _forms._HIST_CHUNK_ELEMS // max(d * maxw, 1)))
        n_pad = -(-n // chunk) * chunk
        Xp = (jnp.pad(Xd, ((0, n_pad - n), (0, 0)))
              if n_pad != n else Xd)
        binned = _device_digitize(
            Xp, jnp.asarray(col_edges, jnp.float32), chunk)[:n]
        return thr_parts, list(widths), binned


# ---------------------------------------------------------------------------
# per-tree feature pools
# ---------------------------------------------------------------------------

#: feature widths <= this form the "narrow" pool class (one-hot-ish
#: columns); wider columns form the other. Stratified per-tree pools
#: then use per-class bin widths instead of the global max, cutting
#: pooled-histogram width ~(global_max / 2) x on one-hot-heavy data
_NARROW_WIDTH = 4


def _pool_classes(widths: np.ndarray, pool_size: int, max_features: int):
    """Host-side stratified pool plan from per-feature bin widths:
    ((narrow_idx, wide_idx) host arrays, (Pn, Pw, Bn, Bw) static ints,
    effective per-node max_features)."""
    narrow = np.nonzero(widths <= _NARROW_WIDTH)[0].astype(np.int32)
    wide = np.nonzero(widths > _NARROW_WIDTH)[0].astype(np.int32)
    d = len(widths)
    # proportional split, but every NON-EMPTY class keeps >= 1 slot so no
    # feature is deterministically unreachable across the whole forest
    p_n = min(len(narrow), int(round(pool_size * len(narrow) / d)))
    if len(narrow):
        p_n = max(p_n, 1)
    p_w = min(len(wide), pool_size - p_n)
    if len(wide):
        p_w = max(p_w, 1)
    p_n = min(len(narrow), max(pool_size - p_w, 1 if len(narrow) else 0))
    b_n = int(widths[narrow].max()) if len(narrow) and p_n else 0
    b_w = int(widths[wide].max()) if len(wide) and p_w else 0
    return ((narrow, wide), (p_n, p_w, b_n, b_w),
            min(max_features, p_n + p_w))


def _tree_pool(pkey, binned, col_thr, narrow_idx, wide_idx, pool_cfg):
    """Per-tree STRATIFIED feature pool: sample narrow and wide columns
    separately (proportional to their population) and pack them with
    per-class bin widths. Histogram work then scales with the pooled
    bins, not feature_count x global_max_bins — per-node max_features
    sampling applies WITHIN the pool (documented deviation from MLlib's
    per-node-over-all-features sampling; across a 50-tree forest the
    pools cover the full feature set many times over)."""
    p_n, p_w, b_n, b_w = pool_cfg
    kn, kw = jax.random.split(pkey)
    parts_pool, parts_packed, parts_thr = [], [], []
    parts_feat, parts_block = [], []
    base_bin = 0
    base_feat = 0
    for key, idx, p, b in ((kn, narrow_idx, p_n, b_n),
                           (kw, wide_idx, p_w, b_w)):
        if p == 0:
            continue
        sel = idx[jax.random.choice(key, idx.shape[0], (p,),
                                    replace=False)]
        offs = base_bin + jnp.arange(p, dtype=jnp.int32) * b
        parts_pool.append(sel)
        parts_packed.append(jnp.take(binned, sel, axis=1) + offs[None, :])
        parts_thr.append(col_thr[sel][:, :b].reshape(p * b))
        parts_feat.append(base_feat
                          + jnp.repeat(jnp.arange(p, dtype=jnp.int32), b))
        parts_block.append(jnp.repeat(offs, b))
        base_bin += p * b
        base_feat += p
    return (jnp.concatenate(parts_pool),
            jnp.concatenate(parts_packed, axis=1),
            jnp.concatenate(parts_feat),
            jnp.concatenate(parts_block),
            jnp.concatenate(parts_thr))


def _pool_size(d: int, mf: Optional[int]) -> Optional[int]:
    """Per-tree feature-pool size: 4x the per-node sample (floored at 8)
    keeps per-node choice diversity while bounding histogram work."""
    if mf is None or mf >= d:
        return None
    return min(d, max(4 * mf, 8))


def _pool_plan(widths: np.ndarray, mf: Optional[int]):
    """((narrow_idx, wide_idx) device arrays, pool_cfg static tuple,
    effective max_features) — or (dummies, None, mf) when no pooling."""
    d = len(widths)
    pool = _pool_size(d, mf)
    empty = jnp.zeros((0,), jnp.int32)
    if pool is None or pool >= d:
        # pool covers everything: the shared pre-packed design is both
        # exact and free of per-tree gather/pad work
        return (empty, empty), None, mf
    (narrow, wide), cfg, mf_eff = _pool_classes(widths, pool, mf)
    return ((jnp.asarray(narrow), jnp.asarray(wide)), cfg, mf_eff)


def _resolve_max_features(strategy: str, d: int, classification: bool
                          ) -> Optional[int]:
    """MLlib featureSubsetStrategy (RandomForestParams)."""
    s = str(strategy).lower()
    if s == "auto":
        s = "sqrt" if classification else "onethird"
    if s == "all":
        return None
    if s == "sqrt":
        return max(1, int(np.sqrt(d)))
    if s == "log2":
        return max(1, int(np.log2(d)))
    if s == "onethird":
        return max(1, d // 3)
    return max(1, min(d, int(float(s) * d) if "." in s else int(s)))


#: binning memo: the validator holds each fold's matrix with stable
#: identity across the whole grid, so one O(d) host binning pass serves
#: every grid point of every tree family on that fold. Strong refs to
#: the keyed arrays keep their id()s valid while cached.
_DESIGN_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_DESIGN_CACHE_SIZE = 8
#: the validator dispatches tree families from separate threads
#: (TX_ASYNC_FAMILIES); one lock makes the memo race-free AND keeps a
#: shared matrix binned once instead of once per family
_DESIGN_LOCK = threading.Lock()


def clear_design_cache() -> None:
    """Drop every memoized binned design (and the device buffers each
    pins). Benchmarks re-measuring binning on fresh uploads of the same
    matrix call this between passes so stale passes' working sets don't
    accumulate in HBM."""
    with _DESIGN_LOCK:
        _DESIGN_CACHE.clear()


def _design_args(X: np.ndarray, max_bins: int,
                 edge_rows: Optional[np.ndarray] = None):
    """Host-bin X and return ((packed, feat_of, block_start, packed_thr,
    binned, col_thr) device arrays, widths host array). ``edge_rows``
    restricts quantile-edge estimation (TX_TREE_EDGES=fold)."""
    # where the design is binned (_bin_on_device) is pure in X's shape
    # and the backend, so it needs no place in the key
    key = (id(X), getattr(X, "shape", None), max_bins,
           None if edge_rows is None else id(edge_rows))
    with _DESIGN_LOCK:
        hit = _DESIGN_CACHE.get(key)
        if hit is not None and hit[0] is X and hit[1] is edge_rows:
            _DESIGN_CACHE.move_to_end(key)
            return hit[2]
        design = _PackedDesign(X, max_bins, edge_rows=edge_rows)
        args = ((jnp.asarray(design.packed), jnp.asarray(design.feat_of),
                 jnp.asarray(design.block_start),
                 jnp.asarray(design.packed_thr),
                 jnp.asarray(design.binned), jnp.asarray(design.col_thr)),
                design.widths)
        _DESIGN_CACHE[key] = (X, edge_rows, args)
        while len(_DESIGN_CACHE) > _DESIGN_CACHE_SIZE:
            _DESIGN_CACHE.popitem(last=False)
        return args
