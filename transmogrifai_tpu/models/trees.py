"""Tree model family: decision tree, random forest, gradient-boosted trees.

TPU-native replacements for the reference's Spark MLlib / XGBoost wrappers:
- OpDecisionTreeClassifier / OpDecisionTreeRegressor
  (core/.../classification/OpDecisionTreeClassifier.scala,
   core/.../regression/OpDecisionTreeRegressor.scala)
- OpRandomForestClassifier / OpRandomForestRegressor
  (core/.../classification/OpRandomForestClassifier.scala)
- OpGBTClassifier / OpGBTRegressor
  (core/.../classification/OpGBTClassifier.scala)
- OpXGBoostClassifier / OpXGBoostRegressor
  (core/.../classification/OpXGBoostClassifier.scala:47 — xgboost4j JNI,
   the reference's only native-C++ compute; see SURVEY.md §2.9)

Design (histogram GBDT, XLA-first — no CUDA/Rabit translation):

- Features are quantile-binned once into <= ``max_bins`` integer bins
  (MLlib ``maxBins``/XGBoost ``tree_method=hist`` equivalent).
- Trees grow **level-wise** with ACTIVE-NODE SLOT COMPRESSION (deep
  levels of a complete tree are mostly empty; histograms cover only
  occupied nodes) over PACKED variable-width bins: every level computes
  per-(slot, packed-bin) statistic histograms via fused ``segment_sum``
  scatters (chunked over feature blocks to bound memory), turns them
  into split gains with one segmented cumulative sum over the packed
  axis, and advances every row one level. No data-dependent shapes
  anywhere, so the whole builder jits into one XLA program; a forest is
  a ``lax.scan`` of that program over bootstrap keys (with per-tree
  feature pools bounding histogram width) and boosting is a
  ``lax.scan`` of it over rounds with margin updates.
- Nodes that fail the gain/min-weight checks emit a +inf threshold
  ("everything goes left"), which makes dead branches self-propagating
  without ragged control flow.
- Split histograms sum 2nd-order grad/hess stats (XGBoost objective)
  or class-count/variance stats (MLlib gini/variance impurity).

Distributed fit: histograms are linear in rows, so data-parallel
multi-chip training is a ``psum`` of per-shard histograms over ICI —
the TPU equivalent of XGBoost's Rabit allreduce (see parallel/cv.py for
the mesh machinery). The builders here take already-materialized
device arrays and are safe to call inside ``shard_map``.
"""
from __future__ import annotations

import functools
import itertools
import logging
import os
import threading
from collections import OrderedDict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np

_log = logging.getLogger(__name__)

from ..features.columns import PredictionColumn
from .base import (ClassifierModel, Predictor, RegressionModel,
                   check_fold_classes, num_classes, subset_grid)
from ..observability import trace as _trace
from ..parallel.mesh import to_host
from ..utils.jax_setup import with_frame_room

__all__ = [
    "DecisionTreeClassifier", "DecisionTreeRegressor",
    "RandomForestClassifier", "RandomForestRegressor",
    "GBTClassifier", "GBTRegressor",
    "XGBoostClassifier", "XGBoostRegressor",
    "TreeEnsembleClassifierModel", "TreeEnsembleRegressorModel",
    "GBTClassifierModel", "GBTRegressorModel",
    "GBTMulticlassClassifierModel",
]

#: every ``jax.named_scope`` of the package's fold-grid and fit programs
#: (docs/observability.md "Device time by scope"): the ``tree.*`` phases of
#: one level of ``_grow_tree``, a forest tree's bootstrap weights and feature
#: pool, a boosting round, the validation metric of the ``*_eval_kernel``s
#: (and of the linear fold-grid programs), one ``fg.<family>`` around each
#: fold-grid program's body (``fg.softmax``: the multinomial logistic lanes,
#: ``fg.bayes``: ``models/bayes.py``; ``fg.glm``: the IRLS lanes of
#: ``models/glm.py``, with ``glm.gram`` and ``glm.solve`` inside an
#: iteration), the linear cores' ``lin.*`` (``models/linear.py``,
#: ``parallel/cv.py``), and ``fg.order``, the row gathers that put the table
#: in every fold's own order (``_by_fold``). The one list of the package:
#: the benchmark's scope readers take it from this attribute. A scope is a
#: path component of the ``op_name`` of the ops traced under it and exists
#: only while JAX traces: it adds no operation and changes no program's name.
#: ``tree.traverse`` is the walk of finished heaps (``_traverse``, either
#: form): the scoring programs' (``jit__predict_leaves``), not a fit's.
#: ``gbt.pick``, inside ``gbt.round``, adds each row's leaf value of a
#: round's finished tree to its margin (``_TreeGrower.add_leaf_values``).
SCOPES = ("tree.indicator", "tree.compress", "tree.hist", "tree.node_sums",
          "tree.split", "tree.route", "tree.bootstrap", "tree.pool",
          "gbt.round", "fg.metric", "fg.gbt", "fg.forest",
          "fg.gbt_softmax", "fg.linear", "lin.standardize", "lin.solve",
          "fg.softmax", "fg.bayes", "fg.glm", "glm.gram", "glm.solve",
          "fg.order", "tree.traverse", "gbt.pick")

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
# histograms are then (slots, total_bins, S) — one fused scatter-add —
# and split gains come from a single segmented cumulative sum over the
# packed axis.


#: edge-matrix element count (edge rows x features) from which quantile
#: binning runs on the accelerator: the per-feature host loop (np.unique
#: + np.quantile + searchsorted, all f64 sorts) dwarfs the device fit it
#: feeds at 1M x 100 (builder-reported; ROADMAP S4 re-measures it)
_DEVICE_BIN_MIN_ELEMS = 4_000_000


def _bin_on_device(edge_elems: int) -> bool:
    """Where quantile bin edges + digitization run: on the device (f32
    column sorts + quantile gathers + compare-sum digitize, one XLA
    program set) when an accelerator backend is active and the edge
    matrix holds >= _DEVICE_BIN_MIN_ELEMS elements, else on the host (the
    exact f64 numpy per-feature loop). The device path deviates from the
    host's only in f32 arithmetic (edges can shift ~1 ulp around ties);
    small fits and CPU runs keep host binning bit-exact. Pure in the
    matrix's shape and the backend."""
    return (edge_elems >= _DEVICE_BIN_MIN_ELEMS
            and jax.default_backend() != "cpu")


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
        if _bin_on_device(e_rows * d):
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
        chunk = max(256, min(n, _HIST_CHUNK_ELEMS // max(d * maxw, 1)))
        n_pad = -(-n // chunk) * chunk
        Xp = (jnp.pad(Xd, ((0, n_pad - n), (0, 0)))
              if n_pad != n else Xd)
        binned = _device_digitize(
            Xp, jnp.asarray(col_edges, jnp.float32), chunk)[:n]
        return thr_parts, list(widths), binned


# ---------------------------------------------------------------------------
# generic level-wise tree builder
# ---------------------------------------------------------------------------

#: how many compressed levels the traced ``_grow_tree`` calls of this process
#: hold (each one a call of ``_carry_slots``)
_COMPRESS_LEVELS = {"carried": 0}


def tree_compress_levels() -> dict:
    """Traced compressed levels of ``_grow_tree`` so far in this process,
    ``{"carried": k}``: the levels whose slots were carried over from the
    level before (see _carry_slots), beside :func:`tree_route_forms` and
    :func:`tree_sum_forms`. An identity level counts nothing."""
    return dict(_COMPRESS_LEVELS)


def _carry_slots(slot: jnp.ndarray, node_of_slot: jnp.ndarray,
                 went_right: jnp.ndarray, next_slots: int, node_sums,
                 route: str):
    """The next level's dense slots from this level's: the rank compression
    of a level wider than the slot cap, without a sort and without a
    per-row scatter or gather.

    Deep levels of a level-wise tree are mostly empty, so histograms and
    gains are computed per *active slot*, not per node: a compressed level's
    slots are the ranks of its occupied node ids in ascending order. This
    level's slots (``slot`` (n,); ``node_of_slot`` (C,) holds their node
    ids, ``_SLOT_SENTINEL`` for an unused one) already ascend with the node
    id, and a row's next node is ``2 * node + went_right``: so ``column =
    2 * slot + went_right`` ascends with the next level's node id too, and a
    row's next slot is the number of occupied columns below its own. The
    ranking is done on the 2 * C columns, not on the n rows:

    - the columns' row counts come from the tree's ``node_sums`` (its
      ``_sums_form``, and the ``psum`` of a row-sharded fit: the occupancy
      is then global and every shard gives a node the same slot). Rows
      count, not weights: a zero-weight bootstrap row occupies its node;
    - a row reads its column's rank in the tree's ``route`` form (see
      _route_form): "gather" indexes the 2 * C ranks; "dense" selects, over
      the slot axis, one C-entry table keyed by the slot the row already
      has (a right column has its left sibling's rank, plus one where the
      sibling is occupied): all integers, rows along the lanes, the slots
      reduced on the major axis;
    - the next ``node_of_slot`` takes 2 * C updates.

    One level on a v5e, 54 vmapped lanes x 49,152 rows, 256 slots
    (builder's chip run, PR 33, PERF.md section 6): 2.6 ms, against 28.5 ms
    for a sort of the rows' node ids and the two n-update scatters that
    put the ranks back, and 29 ms with the gather as the read. The read
    alone 1.3 ms; with the slots on the minor axis, as the routing's
    tables lie, 2.8 ms, and two tables twice that; the row counts 1.6 ms.

    Returns (slot (n,), node_of_slot (next_slots,), active count). The
    budget mask of ``_grow_tree`` keeps the count within ``next_slots``."""
    _COMPRESS_LEVELS["carried"] += 1
    C = node_of_slot.shape[0]
    column = 2 * slot + went_right
    rows = node_sums(jnp.ones((slot.shape[0], 1), jnp.int32), column, 2 * C)
    occ = (rows[:, 0] > 0).astype(jnp.int32)
    below = jnp.cumsum(occ) - occ       # an occupied column's rank
    if route == "dense":
        table = 2 * below[0::2] + occ[0::2]
        of_slot = slot[None, :] == jnp.arange(C, dtype=slot.dtype)[:, None]
        mine = jnp.sum(jnp.where(of_slot, table[:, None], 0), axis=0)
        next_slot = (mine >> 1) + went_right * (mine & 1)
    else:
        next_slot = below[column]
    next_node_of_slot = jnp.full((next_slots,), _SLOT_SENTINEL, jnp.int32).at[
        jnp.where(occ > 0, below, next_slots)].set(
        _child_ids(node_of_slot, _SLOT_SENTINEL), mode="drop")
    return next_slot, next_node_of_slot, jnp.sum(occ)


def _child_ids(node_of_slot: jnp.ndarray, unused: int) -> jnp.ndarray:
    """(2 * C,) int32 node ids one level down of the two children of every
    slot, left then right: column ``2 * c + side`` of slot ``c`` holds
    ``2 * node_of_slot[c] + side``, and ``unused`` under a slot that holds
    ``_SLOT_SENTINEL``."""
    parent = jnp.repeat(node_of_slot, 2)
    side = jnp.arange(parent.shape[0], dtype=jnp.int32) & 1
    return jnp.where(parent == _SLOT_SENTINEL, unused, 2 * parent + side)


_SLOT_SENTINEL = jnp.iinfo(jnp.int32).max

#: default per-level active-node slot cap (see _grow_tree docstring)
_DEFAULT_NODE_CAP = 256


#: cap on the (rows x features x stats) scatter-input materialized per
#: histogram call; larger designs chunk over feature blocks (the memory
#: bound the pre-packed per-feature scan used to provide)
_HIST_CHUNK_ELEMS = 32_000_000


#: largest (n, total_bins) bin indicator, in bytes of the stats dtype,
#: that the ``matmul`` path still holds whole: it is re-read at every
#: level, and past this it crowds a 16 GB chip's HBM (12.8 GB at 1M x
#: 3,200 bins in float32). The largest a benchmark cell builds is the
#: 1M-row fit's, 1,000,000 x 800 x 4 B = 2.98 GiB
_INDICATOR_MAX_BYTES = 4 * 2 ** 30


def _hist_mode(n: int, total_bins: int) -> str:
    """The one owner of the level histograms' path, worked out from what
    the code can observe (no environment variable, no option):

    - "scatter" on a CPU: fused segment_sum, the tests' reference;
    - "matmul" on an accelerator (XLA scatters serialize there): one-hot
      contractions on the MXU over the (n, total_bins) bin indicator built
      once per tree. On a TPU the einsum is a default-precision, i.e.
      bf16-pass, contraction: chip_smoke.py measures it ~2e-3 of max off
      the highest-precision result (PERF.md);
    - "matmul_chunk" on an accelerator where that indicator, in the stats
      dtype, would pass _INDICATOR_MAX_BYTES: the same exact contraction
      with the indicator rebuilt per bin block (gather + compare,
      scatter-free) every level instead of held whole.

    Not returned, but still a value of the static that tests pass:
    "<mode>+sub", LightGBM-style histogram SUBTRACTION inside the level
    loop of _grow_tree: identity levels > 0 build histograms for LEFT
    children only (half the slots) and derive each right child as parent
    - left (the parent histogram is the previous level's, and the per-row
    stats are level-invariant within a tree). Mathematically identical;
    float cancellation can move near-tie splits. Making it the
    accelerator's default is ROADMAP S2 (e), a ``perf_opt`` with a claim;
    no driver record holds a measurement of it.

    ``n`` is the rows one device holds (a row-sharded fit passes its
    shard's). Decided at trace time; every jitted entry pins the result
    as its static ``hist_mode``, so another answer retraces. The routing
    form of a level follows it (see _route_form): ``scatter`` gathers each
    row's bin, the other two select it densely. So does the form of the
    node sums (see _sums_form): ``scatter`` keeps ``segment_sum``, the
    other two reduce a select over the slot axis. Those sums (a level's
    ``total``, the tree's ``leaf_stats``) are float32-exact under every
    mode: the bf16 pass above is the histogram's alone."""
    if jax.default_backend() == "cpu":
        return "scatter"
    itemsize = np.dtype(jax.dtypes.canonicalize_dtype(float)).itemsize
    if n * total_bins * itemsize > _INDICATOR_MAX_BYTES:
        return "matmul_chunk"
    return "matmul"


def _bin_indicator(packed: jnp.ndarray, total_bins: int, dtype,
                   feat_of: jnp.ndarray,
                   lo: int = 0, hi: Optional[int] = None) -> jnp.ndarray:
    """(n, hi-lo) 0/1 bin-membership matrix for packed bins [lo, hi)
    (default: all TB bins): feature bin ranges are DISJOINT in the
    packed axis, so each row has exactly one 1 per feature block.

    The build is a GATHER + COMPARE — ``packed[:, feat_of[b]] == b`` —
    which is scatter-free (XLA serializes scatters on TPU; a column
    gather + VPU compare is not). Built once per tree for the
    whole-matrix modes, or per (level, bin-block) under
    ``matmul_chunk`` where the full (n, TB) matrix would blow HBM (the
    12.8 GB case of the BASELINE roofline)."""
    hi = total_bins if hi is None else hi
    cols = packed[:, feat_of[lo:hi]]                # (n, hi-lo) gather
    return (cols == jnp.arange(lo, hi, dtype=packed.dtype)[None, :]
            ).astype(dtype)


def _fold_indicator(packed: jnp.ndarray, feat_of: jnp.ndarray, dtype,
                    hist_mode: Optional[str], hist_rows: Optional[int]
                    ) -> Optional[jnp.ndarray]:
    """The bin indicator that every tree of one fold's body shares (see
    _forest_body ``hist_rows``), built ONCE a call of the program and not
    once a tree or a round: the (hist_rows, TB) indicator of the rows the
    fold trains on, under the whole-matrix ``matmul`` mode; None elsewhere
    (the growers then see to their own, as a pooled tree must: its design
    is its own). XLA does not move the build out of the trees' ``scan``
    itself, the indicator being some 17 times its inputs' bytes: built
    once a tree it read 0.90 + 0.36 s of the regression pool's train (my
    chip run, PR 37, PERF.md section 6)."""
    if hist_rows is None or (hist_mode or "").partition("+")[0] != "matmul":
        return None
    with jax.named_scope("tree.indicator"):
        return _bin_indicator(packed[:hist_rows], int(feat_of.shape[0]),
                              dtype, feat_of)


def _level_histograms(packed: jnp.ndarray, slot: jnp.ndarray,
                      stats: jnp.ndarray, num_slots: int,
                      total_bins: int,
                      bin_oh: Optional[jnp.ndarray] = None,
                      mode: str = "scatter",
                      axis_name: Optional[str] = None,
                      feat_of: Optional[jnp.ndarray] = None
                      ) -> jnp.ndarray:
    """(num_slots, total_bins, S) histograms. Mathematically identical
    strategies (see _hist_mode):

    - scatter (bin_oh None): fused segment_sum per feature block
      (segment id = slot*TB + packed bin), blocks bounding the
      broadcasted (n x d_block x S) scatter input to _HIST_CHUNK_ELEMS;
    - matmul (bin_oh given): hist[c,b,s] =
      sum_i 1[slot_i=c] * binOH[i,b] * stats[i,s] — S dense
      contractions on the MXU, no per-level scatters. Peak memory is
      the (n, TB) indicator built once per tree;
    - matmul_chunk (bin_oh None, feat_of given): the same MXU
      contraction with the indicator REBUILT per bin block by gather +
      compare, bounding the transient to ~_HIST_CHUNK_ELEMS — the
      big-n mode where the whole (n, TB) indicator would blow HBM
      (12.8 GB at 1M x 3200 in float32).
    """
    n, d = packed.shape
    s_dim = stats.shape[1]
    if mode == "matmul_chunk":
        slot_oh = jax.nn.one_hot(slot, num_slots, dtype=stats.dtype)
        # per-block transient ≈ n * step elements; the floor of 8 bins
        # keeps blocks from degenerating, so the true bound is
        # max(_HIST_CHUNK_ELEMS, 8n) elements — still linear in n, the
        # unavoidable cost of materializing any (n, block) indicator
        step = max(8, min(total_bins,
                          _HIST_CHUNK_ELEMS // max(n, 1)))
        parts = []
        for lo in range(0, total_bins, step):
            hi = min(lo + step, total_bins)
            oh = _bin_indicator(packed, total_bins, stats.dtype,
                                feat_of, lo, hi)
            parts.append(jnp.einsum("nc,ns,nb->cbs", slot_oh, stats, oh))
        hist = jnp.concatenate(parts, axis=1)
        return (jax.lax.psum(hist, axis_name) if axis_name else hist)
    if bin_oh is not None:
        slot_oh = jax.nn.one_hot(slot, num_slots, dtype=stats.dtype)
        hist = jnp.einsum("nc,ns,nb->cbs", slot_oh, stats, bin_oh)
        # histograms are linear in rows: the data-parallel reduction is
        # one psum over ICI — the Rabit-allreduce role (SURVEY §2.9)
        return (jax.lax.psum(hist, axis_name) if axis_name else hist)
    n_chunks = max(1, -(- (n * d * s_dim) // _HIST_CHUNK_ELEMS))
    step = -(-d // n_chunks)
    segs = num_slots * total_bins
    out = None
    for lo in range(0, d, step):
        blk = packed[:, lo:lo + step]
        db = blk.shape[1]
        seg = slot[:, None] * total_bins + blk
        part = jax.ops.segment_sum(
            jnp.broadcast_to(stats[:, None, :], (n, db, s_dim)
                             ).reshape(n * db, s_dim),
            seg.reshape(-1), num_segments=segs)
        out = part if out is None else out + part
    if axis_name:
        out = jax.lax.psum(out, axis_name)
    return out.reshape(num_slots, total_bins, s_dim)


#: widest design (columns) the ``matmul`` family still routes densely
#: (see _route_form). One level on a v5e, 196,608 rows, 32 slots (builder's
#: chip run, PR 27, PERF.md section 6): the gather form takes 5.4-6.2 ms
#: whatever the width (27-31 ns a row), the dense form 0.24 / 0.28 / 1.14 /
#: 2.18 / 4.26 ms at 100 / 200 / 1,000 / 2,000 / 4,000 columns (0.2 ms +
#: 1.0 ms per 1,000 columns; 256 slots add 0.3 ms): they meet near 5,600
#: columns, and at 4,096 the dense form still wins by a quarter
_ROUTE_DENSE_MAX_D = 4096

#: how many traced ``_grow_tree`` calls took each routing form
_ROUTE_FORMS = {"dense": 0, "gather": 0}


def _route_form(base_mode: str, d: int) -> str:
    """How a level of ``_grow_tree`` reads each row's split column:
    "gather" (``packed[rows, bfeat[slot]]``: three per-row gathers, O(n),
    cheap on a CPU) or "dense" (selects over the slot and the column axis,
    O(n * (C + d)) elementwise, no gather). Chosen at trace time from the
    resolved base ``hist_mode`` (``scatter``, the CPU path and the tests'
    reference, gathers; the ``matmul`` family, the accelerator default,
    routes densely) and the design's width ``d``. Both forms give the same
    integers."""
    if base_mode == "scatter" or d > _ROUTE_DENSE_MAX_D:
        return "gather"
    return "dense"


def tree_route_forms() -> dict:
    """Traced ``_grow_tree`` calls so far in this process by routing form,
    ``{"dense": k, "gather": m}`` (see _route_form): the record of which
    path the compiled tree programs hold."""
    return dict(_ROUTE_FORMS)


#: most columns (a level's slots, or the two leaves under each slot of the
#: last one) the ``matmul`` family still sums densely (see _sums_form). One
#: sum on a v5e, float32, median of five (builder's chip run, PR 31,
#: PERF.md section 6), ``segment_sum`` against ``_slot_sums``: 1,000,000
#: rows x 3 statistics, 32 slots, 8.42 ms against 1.02 ms; 54 vmapped lanes
#: x 49,152 rows x 2 statistics, 20.8 ms at 32, 256 and 512 slots (7.8 ns a
#: row whatever the slots; 43 ms from 1,024 up) against 1.47 / 1.78 / 2.47 /
#: 3.82 / 7.90 / 21.7 ms at 32 / 256 / 512 / 1,024 / 2,048 / 4,096 slots;
#: 1,000,000 rows x 2 statistics unbatched, 8.3-8.4 ms against 2.51 ms at
#: 1,024 and 6.63 ms at 4,096 slots: they would meet near 5,200 slots, and
#: at 4,096 the dense form still wins by a fifth (by half under ``vmap``).
#: The other dense candidates of that run: the same selects with the rows
#: on the major axis, 4.8 ms at 256 and 512 slots; ``einsum("nc,ns->cs",
#: precision=HIGHEST)``, 3.0 / 3.7 ms, and further from the float64 sums
#: (2-3e-6 of the largest at 1,000,000 rows, as the scatter's sequential
#: adds, 2-5e-6; the selects' tree reduction 1-3e-7); a 3-D select over
#: (n, slots, S) stores the (lanes, n, slots) one-hot (648 MB at 256 slots)
_SUMS_DENSE_MAX_SLOTS = 4096

#: how many traced ``_grow_tree`` calls summed their nodes in each form
_SUM_FORMS = {"dense": 0, "scatter": 0}


def _sums_form(base_mode: str, num_slots: int) -> str:
    """How ``_grow_tree`` adds the rows' statistics up by node (each level's
    per-slot totals and the tree's per-leaf sums): "scatter"
    (``jax.ops.segment_sum``: O(n), cheap on a CPU, a serialized per-row
    scatter-add on the chip) or "dense" (``_slot_sums``: the slot one-hot
    selected against each statistic and reduced over the rows, O(n * slots)
    elementwise, no scatter and no gather). Chosen at trace time, once a
    tree, from the resolved base ``hist_mode`` (``scatter``, the CPU path
    and the tests' reference, keeps ``segment_sum`` and its bits; the
    ``matmul`` family, the accelerator default, sums densely) and the
    widest reduction the tree makes, ``num_slots`` (its leaf sum's columns:
    twice the last level's slots), because the dense form grows with the
    slots and the scatter does not. Both forms are float32 sums of float32
    statistics; they differ in summation order only.

    Why the sums are not read from the level histogram, which holds them
    (any one feature's bins of ``hist`` add up to ``total``): on the chip the
    histogram is a default-precision einsum, a bf16 pass over ``stats``
    (see _hist_mode), while ``total`` and ``leaf_stats`` (the model's leaf
    values) are float32-exact. Taking them from ``hist`` would state float32
    and deliver bf16-rounded gradients in every leaf."""
    if base_mode == "scatter" or num_slots > _SUMS_DENSE_MAX_SLOTS:
        return "scatter"
    return "dense"


def tree_sum_forms() -> dict:
    """Traced ``_grow_tree`` calls so far in this process by the form of
    their node sums, ``{"dense": k, "scatter": m}`` (see _sums_form): the
    record of which path the compiled tree programs hold."""
    return dict(_SUM_FORMS)


#: how many traced fused fit+metric kernels (``*_eval_kernel``) took each
#: source of the validation rows' leaves (see _eval_form)
_EVAL_FORMS = {"in_fit": 0, "traverse": 0}


def _eval_form(in_fit: bool) -> None:
    """Count one traced fused fit+metric kernel under the form it holds,
    i.e. where it finds each validation row's leaf: "in_fit" (the caller
    named the validation rows' positions in the fitted table,
    ``val_rows``: ``_grow_tree`` routed them with every other row, so the
    kernel reads the leaf, or the boosted margin, the fit already holds
    and no second walk is traced) or "traverse" (the validation rows are
    foreign to the fitted table: ``_traverse`` walks the raw ``X_val``
    down the finished heaps). Both forms give the same leaf index (see
    _candidate_scores)."""
    _EVAL_FORMS["in_fit" if in_fit else "traverse"] += 1


def tree_eval_forms() -> dict:
    """Traced fused fit+metric tree kernels so far in this process by
    form, ``{"in_fit": k, "traverse": m}`` (see _eval_form): the record
    of which path the compiled fold-grid programs hold."""
    return dict(_EVAL_FORMS)


#: how many traced tree growers contracted each set of rows in their level
#: histograms (see tree_hist_rows)
_HIST_ROWS = {"head": 0, "all": 0}


def tree_hist_rows() -> dict:
    """Traced tree growers (:class:`_TreeGrower`) so far in this process by
    the rows their level histograms contract, ``{"head": k, "all": m}``:
    "head" where the lane sees the table in its fold's own order and
    contracts the rows it trains on, the static head of that order (see
    _fold_order: the in-fit form of a fold-grid program without a mesh,
    under the ``matmul`` family); "all" everywhere else (a single fit, the
    traverse form, a search mesh, the ``scatter`` mode), where the held-out
    rows of a lane are contracted at weight zero."""
    return dict(_HIST_ROWS)


#: how many traced boosting rounds read their rows' leaf values in each
#: form (see _TreeGrower.add_leaf_values)
_PICK_FORMS = {"dense": 0, "gather": 0}


def tree_pick_forms() -> dict:
    """Traced leaf-value picks of boosting rounds so far in this process by
    form, ``{"dense": k, "gather": m}`` (see _TreeGrower.add_leaf_values):
    the record of which path the compiled boosted programs hold."""
    return dict(_PICK_FORMS)


def _fetch_span(**group):
    """The ``search.fetch`` span of a fold-grid driver, carrying
    :func:`tree_route_forms`, :func:`tree_sum_forms`,
    :func:`tree_eval_forms`, :func:`tree_compress_levels`,
    :func:`tree_hist_rows` and :func:`tree_pick_forms` as the attributes
    ``route_dense`` / ``route_gather``, ``sums_dense`` / ``sums_scatter``,
    ``eval_in_fit`` / ``eval_traverse``, ``compress_carried``,
    ``hist_head`` / ``hist_all`` and ``pick_dense`` / ``pick_gather`` (see
    trace.counted_span), and ``group``, the attributes of the call's own
    group (``depth_blocks`` / ``depth_lane_levels``, see tree_depth_blocks;
    ``hist_row_share``, the rows a lane's histograms contract over the rows
    it holds)."""
    return _trace.counted_span("search.fetch", (
        ("route_", tree_route_forms), ("sums_", tree_sum_forms),
        ("eval_", tree_eval_forms), ("compress_", tree_compress_levels),
        ("hist_", tree_hist_rows), ("pick_", tree_pick_forms)), **group)


def train_eval_span():
    """The selector's ``search.train_eval`` span (the refitted winner's
    scores on its training rows), carrying :func:`tree_traverse_forms` as
    ``traverse_dense`` / ``traverse_gather`` (see trace.counted_span)."""
    return _trace.counted_span("search.train_eval",
                         (("traverse_", tree_traverse_forms),))


def _route_left_dense(packed: jnp.ndarray, slot: jnp.ndarray,
                      bfeat: jnp.ndarray, best_r: jnp.ndarray
                      ) -> jnp.ndarray:
    """``packed[i, bfeat[slot[i]]] <= best_r[slot[i]]`` for every row
    without a gather: the two per-slot tables (C entries) are selected
    over the slot axis, the row's bin over the column axis of the resident
    (n, d) matrix. All integer compares and sums, so the result is exactly
    the gather's (a float contraction on the chip is a bf16 pass and would
    round bin indices above 256)."""
    idt = packed.dtype
    of_slot = slot[:, None] == jnp.arange(bfeat.shape[0],
                                          dtype=slot.dtype)[None, :]
    f_i = jnp.sum(jnp.where(of_slot, bfeat.astype(idt)[None, :], 0), axis=1)
    b_i = jnp.sum(jnp.where(of_slot, best_r.astype(idt)[None, :], 0), axis=1)
    cols = jnp.arange(packed.shape[1], dtype=idt)[None, :]
    return jnp.any((cols == f_i[:, None]) & (packed <= b_i[:, None]),
                   axis=1)


def _slot_sums(stats: jnp.ndarray, slot: jnp.ndarray,
               num_slots: int) -> jnp.ndarray:
    """``jax.ops.segment_sum(stats, slot, num_segments=num_slots)`` without
    a scatter: (num_slots, S) sums of the rows' statistics by slot. Each
    statistic column is selected against the (num_slots, n) slot one-hot
    and reduced over the rows, one fused compare-select-reduce a column on
    the VPU (rows along the lanes, as ``slot`` and a column of ``stats``
    lie; the one-hot is never stored, which a 3-D select over (n, slots,
    S) would do). A row whose slot is outside [0, num_slots) matches no
    slot and adds nothing, as ``segment_sum`` drops it. Sums in the
    statistics' dtype: no contraction, so no bf16 pass on the chip."""
    of_slot = slot[None, :] == jnp.arange(num_slots,
                                          dtype=slot.dtype)[:, None]
    return jnp.stack(
        [jnp.sum(jnp.where(of_slot, stats[:, s][None, :], 0), axis=1)
         for s in range(stats.shape[1])], axis=1)


class _TreeState(NamedTuple):
    """What one lane of a :class:`_TreeGrower` carries from level to level:
    each row's within-level ``node`` id and ``slot``, the slots' node ids
    and their ``active`` count (compressed levels, see _carry_slots), the
    heaps so far (2^level - 1 entries after ``level`` levels), the last
    level's ``went_right`` and, under histogram subtraction only, the last
    level's histogram."""
    node: jnp.ndarray
    slot: jnp.ndarray
    node_of_slot: jnp.ndarray
    active: jnp.ndarray
    feat_heap: jnp.ndarray
    thr_heap: jnp.ndarray
    went_right: jnp.ndarray
    prev_hist: Optional[jnp.ndarray]


def _leaf_values_dense(vals: jnp.ndarray, state: _TreeState,
                       by_id: bool) -> jnp.ndarray:
    """``vals[state.node]`` for every row of a finished tree without a
    per-row gather. A row ends in the last level's ``slot`` on the side
    ``went_right``, and its leaf is that column's child (see _child_ids):
    the (C, 2) table of leaf values by slot and side is ``vals`` itself
    where the last level's slots are its node ids (``by_id``, an identity
    level) and is read from it where they carry them (a compressed level:
    2C entries, a sentinel slot's reading nothing); then every row selects
    its slot's entry over the slot axis, C compares a row. The selects run
    on the values' bits and sum integers, one non-zero term a row, so the
    result is the gather's to the bit, signed zeros included; rows along
    the lanes, the slots reduced on the major axis, as _carry_slots reads
    its rank table."""
    bits = jax.lax.bitcast_convert_type(
        vals, jnp.dtype(f"int{8 * vals.dtype.itemsize}"))
    if not by_id:
        bits = bits.at[_child_ids(state.node_of_slot, vals.shape[0])].get(
            mode="fill", fill_value=0)
    table = bits.reshape(-1, 2)
    mine = jnp.where(state.went_right[None, :] > 0, table[:, 1:],
                     table[:, :1])
    of_slot = state.slot[None, :] == jnp.arange(
        table.shape[0], dtype=state.slot.dtype)[:, None]
    picked = jnp.sum(jnp.where(of_slot, mine, 0), axis=0, dtype=bits.dtype)
    return jax.lax.bitcast_convert_type(picked, vals.dtype)


class _TreeGrower:
    """The level-wise growth of trees over ONE packed binned design (see
    :class:`_PackedDesign`), a range of levels at a time: what
    :func:`_grow_tree` runs from the root to the leaves for one tree, and
    what :func:`_grow_blocks` runs a segment at a time for the lanes of a
    fold-grid program, each over the lanes still growing.

    Built once a tree (outside any ``vmap`` over lanes) it holds what no
    lane owns: the design, the bin indicator of the ``matmul`` mode, the
    resolved forms (see _hist_mode, _route_form) and the per-level keys of
    the per-node feature draw (the chain ``key, sub = split(key)`` a level,
    to ``max_depth``). ``levels`` and ``leaves`` are functions of one
    lane's statistics and state and are ``vmap``ped by their caller.

    ``hist_rows`` (a static; default: every row) is the count of leading
    rows that carry weight: the indicator is built over them, the level
    histograms contract them and the node sums add them up, while every
    row is routed and counted where rows are counted (an identity level's
    empty slots, the occupancy of _carry_slots). The lanes of one FOLD of
    a fold-grid program share such a design, the table in the fold's own
    order (see _fold_order), and the grower is then built inside the
    fold's body (see _by_fold). ``bin_oh``: the indicator of those rows where
    the caller holds it already (see _fold_indicator)."""

    def __init__(self, packed: jnp.ndarray, feat_of: jnp.ndarray,
                 block_start: jnp.ndarray, packed_thr: jnp.ndarray, dtype,
                 *, max_depth: int,
                 feat_key: Optional[jnp.ndarray] = None,
                 max_features: Optional[int] = None,
                 node_cap: Optional[int] = None,
                 feat_map: Optional[jnp.ndarray] = None,
                 hist_mode: Optional[str] = None,
                 axis_name: Optional[str] = None,
                 row_total: Optional[int] = None,
                 hist_rows: Optional[int] = None,
                 bin_oh: Optional[jnp.ndarray] = None):
        n, d = packed.shape
        TB = feat_of.shape[0]
        # the leading rows that carry weight (see _fold_order): what the
        # level histograms contract and the node sums add up; every row is
        # still routed
        self.hist_rows = n if hist_rows is None else hist_rows
        _HIST_ROWS["head" if self.hist_rows < n else "all"] += 1
        self.packed, self.feat_of = packed, feat_of
        self.block_start, self.packed_thr = block_start, packed_thr
        self.feat_map, self.axis_name = feat_map, axis_name
        self.cap = min(row_total if row_total is not None else n,
                       _DEFAULT_NODE_CAP if node_cap is None else node_cap)
        self.not_a_split = ~jnp.isfinite(packed_thr)  # last + padded bins
        # resolved here only when the caller did not pin it; jitted entry
        # points MUST pin it (static arg) or mode switches won't retrace
        hist_mode = hist_mode or _hist_mode(n, TB)
        # "<mode>+sub": a value of the static that only tests pass (the
        # resolver never returns it): histogram subtraction, see _hist_mode
        self.hist_mode, _, suffix = hist_mode.partition("+")
        self.sub_enabled = suffix == "sub"
        if self.hist_mode == "matmul" and bin_oh is None:
            with jax.named_scope("tree.indicator"):
                bin_oh = _bin_indicator(self.head(packed), TB, dtype,
                                        feat_of)
        self.bin_oh = bin_oh             # None: scatter / matmul_chunk modes
        self.route = _route_form(self.hist_mode, d)
        _ROUTE_FORMS[self.route] += 1
        self.max_features = (max_features if max_features is not None
                             and max_features < d else None)
        self.subkeys = []
        if self.max_features is not None:
            key = feat_key
            for _ in range(max_depth):
                key, sub = jax.random.split(key)
                self.subkeys.append(sub)

    def head(self, rows: jnp.ndarray) -> jnp.ndarray:
        """The leading ``hist_rows`` of a per-row array (itself where that
        is every row: no operation is traced)."""
        return (rows if self.hist_rows == rows.shape[0]
                else rows[:self.hist_rows])

    def is_identity(self, level: int, depth: int) -> bool:
        # identity fast path: while every within-level node id fits the
        # slot cap AND the next level's budget mask cannot bind
        # (2^(level+1) <= cap, or this is the tree's last level), slots ARE
        # node ids and nothing is ranked. Empty nodes produce all-zero
        # histograms -> -inf gains -> they write the already-initialized
        # (0, inf) heap entries, so results are bit-identical to the
        # compressed path. With the default cap (256) this covers every
        # level of trees up to depth 9; only deeper trees carry compressed
        # slots from level to level (_carry_slots), and once a level is
        # compressed every later one is.
        return 2 ** level <= self.cap and (
            level + 1 == depth or 2 ** (level + 1) <= self.cap)

    def sums_form(self, depth: int) -> str:
        # one form of the node sums a tree, by its widest: the leaf sum's
        # columns, two under each slot of the last level
        return _sums_form(self.hist_mode,
                          2 * min(2 ** max(depth - 1, 0), self.cap))

    def level_forms(self, lo: int, hi: int, depth: int) -> tuple:
        """What of levels [lo, hi) depends on the tree's ``depth``: lanes
        of different depths whose forms agree trace the same levels (see
        _grow_blocks). A level: is it an identity level, does the budget
        mask apply, are the slots carried over at its end."""
        return (self.sums_form(depth),) + tuple(
            (self.is_identity(level, depth),
             level + 1 < depth and not self.is_identity(level, depth),
             level + 1 < depth and not self.is_identity(level + 1, depth))
            for level in range(lo, hi))

    def _node_sums(self, sums: str):
        def node_sums(values, slots, num_slots):
            if sums == "dense":
                out = _slot_sums(values, slots, num_slots)
            else:
                out = jax.ops.segment_sum(values, slots,
                                          num_segments=num_slots)
            return (jax.lax.psum(out, self.axis_name) if self.axis_name
                    else out)
        return node_sums

    def levels(self, state: Optional[_TreeState], stats: jnp.ndarray,
               gain_fn, min_info_gain, lo: int, hi: int,
               depth: int) -> _TreeState:
        """Levels [lo, hi) of ONE lane's tree of static ``depth`` from its
        ``state`` after level ``lo`` (None at the root)."""
        packed, feat_of = self.packed, self.feat_of
        n, d = packed.shape
        TB = feat_of.shape[0]
        cap, route, hist_mode = self.cap, self.route, self.hist_mode
        axis_name = self.axis_name
        node_sums = self._node_sums(self.sums_form(depth))
        heap_len = 2 ** hi - 1
        if state is None:
            node = jnp.zeros((n,), jnp.int32)
            feat_heap = jnp.zeros((max(heap_len, 1),), jnp.int32)[:heap_len]
            thr_heap = jnp.full((max(heap_len, 1),), jnp.inf,
                                stats.dtype)[:heap_len]
            # a level 0 that is no identity level (cap == 1) has one slot
            slot, node_of_slot, active = node, jnp.zeros((1,), jnp.int32), 1
            went_right, prev_hist = node, None
        else:
            (node, slot, node_of_slot, active, feat_heap, thr_heap,
             went_right, prev_hist) = state
            grown = heap_len - feat_heap.shape[0]
            feat_heap = jnp.concatenate(
                [feat_heap, jnp.zeros((grown,), feat_heap.dtype)])
            thr_heap = jnp.concatenate(
                [thr_heap, jnp.full((grown,), jnp.inf, thr_heap.dtype)])
        prev_identity = lo > 0 and self.is_identity(lo - 1, depth)
        for level in range(lo, hi):
            identity = self.is_identity(level, depth)
            C = min(2 ** level, cap)               # static slots this level
            if identity:
                slot = node
                node_of_slot = jnp.arange(C, dtype=jnp.int32)
            with jax.named_scope("tree.hist"):
                if (self.sub_enabled and identity and prev_identity
                        and prev_hist is not None):
                    # histogram subtraction (the LightGBM trick): rows
                    # routed left stayed even-numbered (`node = 2*node +
                    # (1-go_left)`), so build ONLY the left-child
                    # histograms — half the contraction — indexed by
                    # parent (slot >> 1); each right child is parent -
                    # left. Stats are level-invariant within a tree and
                    # bins never change, so prev_hist[p] IS the parent's
                    # full histogram. Odd-slot rows park on sentinel slot C
                    # (== 2*C_half): one_hot zeroes it, scatter drops it.
                    C_half = C // 2
                    slot_sub = jnp.where((slot & 1) == 0, slot >> 1, C)
                    hist_even = _level_histograms(
                        self.head(packed), self.head(slot_sub),
                        self.head(stats), C_half, TB, self.bin_oh,
                        mode=hist_mode, axis_name=axis_name,
                        feat_of=feat_of)
                    hist = jnp.stack([hist_even, prev_hist - hist_even],
                                     axis=1).reshape(C, TB, stats.shape[1])
                else:
                    hist = _level_histograms(
                        self.head(packed), self.head(slot),
                        self.head(stats), C, TB, self.bin_oh,
                        mode=hist_mode, axis_name=axis_name,
                        feat_of=feat_of)
            prev_hist, prev_identity = hist, identity
            with jax.named_scope("tree.split"):
                cs = jnp.cumsum(hist, axis=1)      # packed-axis running sum
                # per-feature segmented cumsum: subtract the running sum at
                # the owning block's start; splitting at bin b sends
                # bins<=b left
                base = jnp.where(
                    (self.block_start > 0)[None, :, None],
                    cs[:, jnp.maximum(self.block_start - 1, 0), :], 0.0)
                left = cs - base
                with jax.named_scope("tree.node_sums"):
                    if identity:
                        # unlike compression (which only materializes
                        # non-empty slots), identity slots include empty
                        # nodes; their all-zero histograms yield -inf/zero
                        # gains under every default gain, but a user-set
                        # gamma<0 with min_child_weight<=0 could make an
                        # empty node's XGB gain positive — so count rows
                        # per slot (folded into the total reduction as an
                        # extra ones column) and mask empty slots out of
                        # split_ok below. The rows counted are all the
                        # lane holds: behind ``hist_rows`` they carry no
                        # statistic and get a pass of their own
                        if self.hist_rows < n:
                            total = node_sums(self.head(stats),
                                              self.head(slot), C)[:, None, :]
                            nonempty = node_sums(
                                jnp.ones((n, 1), jnp.int32), slot,
                                C)[:, 0] > 0
                        else:
                            aug = node_sums(
                                jnp.concatenate(
                                    [stats, jnp.ones((n, 1), stats.dtype)],
                                    axis=1),
                                slot, C)
                            total = aug[:, None, :-1]
                            nonempty = aug[:, -1] > 0
                    else:
                        total = node_sums(self.head(stats), self.head(slot),
                                          C)[:, None, :]
                right = total - left
                gain = gain_fn(left, right, total)         # (C, TB)
                gain = jnp.where(self.not_a_split[None, :], -jnp.inf, gain)
                if self.max_features is not None:
                    sub = self.subkeys[level]
                    if identity:
                        # node_of_slot is arange(C) here — the node-keyed
                        # gather below would be a no-op
                        u = jax.random.uniform(sub, (C, d))
                    elif 2 ** level <= cap:
                        # node-keyed draw: invariant to slot numbering, so
                        # the identity and compressed paths pick identical
                        # per-node feature subsets. A sentinel (empty) slot
                        # clamps onto the last node's row — safe not
                        # because that row is unused but because
                        # sentinel-slot outputs never reach the heap
                        # (mode="drop") or routing
                        u = jax.random.uniform(sub, (2 ** level, d))[
                            jnp.clip(node_of_slot, 0, 2 ** level - 1)]
                    else:
                        u = jax.random.uniform(sub, (C, d))
                    kth = jnp.sort(u, axis=1)[
                        :, self.max_features - 1:self.max_features]
                    gain = jnp.where((u <= kth)[:, feat_of], gain, -jnp.inf)
                best = jnp.argmax(gain, axis=1)        # (C,) packed bin
                best_gain = jnp.take_along_axis(gain, best[:, None],
                                                axis=1)[:, 0]
                split_ok = best_gain >= jnp.maximum(min_info_gain, 1e-12)
                if identity:
                    split_ok &= nonempty
                if level + 1 < depth and not identity:
                    # budget mask: next level holds at most
                    # min(2^(level+1), cap) slots; each split adds one net
                    # node, so only the first (budget - active) slots may
                    # split. Binds only near capacity (the identity fast
                    # path above is taken exactly when it cannot bind).
                    budget = min(2 ** (level + 1), cap)
                    split_ok &= jnp.arange(C) < (budget - active)
                bfeat = jnp.where(split_ok, feat_of[best], 0)
                thr = jnp.where(split_ok, self.packed_thr[best], jnp.inf)
                heap_pos = jnp.where(node_of_slot == _SLOT_SENTINEL,
                                     _SLOT_SENTINEL,
                                     2 ** level - 1 + node_of_slot)
                # feat_map translates design-local feature ids (e.g. a
                # per-tree feature pool) back to ORIGINAL column ids for
                # the heap
                heap_feat = (bfeat if self.feat_map is None
                             else jnp.where(split_ok, self.feat_map[bfeat],
                                            0))
                feat_heap = feat_heap.at[heap_pos].set(heap_feat,
                                                       mode="drop")
                thr_heap = thr_heap.at[heap_pos].set(
                    thr.astype(thr_heap.dtype), mode="drop")
            # route rows: packed[i, f*] <= best_packed  <=>  bin <= b; a
            # denied split routes everything left via the TB sentinel
            with jax.named_scope("tree.route"):
                best_r = jnp.where(split_ok, best, TB)
                if route == "dense":
                    go_left = _route_left_dense(packed, slot, bfeat, best_r)
                else:
                    go_left = (packed[jnp.arange(n), bfeat[slot]]
                               <= best_r[slot])
                # within-level index
                went_right = 1 - go_left.astype(jnp.int32)
                node = 2 * node + went_right
            if level + 1 < depth and not self.is_identity(level + 1, depth):
                with jax.named_scope("tree.compress"):
                    slot, node_of_slot, active = _carry_slots(
                        slot, node_of_slot, went_right,
                        min(2 ** (level + 1), cap), node_sums, route)
        return _TreeState(node, slot, node_of_slot,
                          jnp.asarray(active, jnp.int32), feat_heap,
                          thr_heap, went_right,
                          prev_hist if self.sub_enabled else None)

    def leaves(self, state: _TreeState, stats: jnp.ndarray,
               depth: int) -> jnp.ndarray:
        """(2^depth, S) per-leaf sums of ONE lane's finished tree."""
        sums = self.sums_form(depth)
        _SUM_FORMS[sums] += 1
        node_sums = self._node_sums(sums)
        with jax.named_scope("tree.node_sums"):
            if (sums == "scatter" or depth == 0
                    or self.is_identity(depth - 1, depth)):
                # the last level's slots were its node ids (or there is
                # none): a leaf's column is its id
                return node_sums(self.head(stats), self.head(state.node),
                                 2 ** depth)
            # a leaf is (last level's slot, side): summed over 2 * C
            # columns, not 2^depth segments, then placed by the columns'
            # leaf ids, which the slots' node ids give (a column no row
            # reached adds zeros to its leaf, as an empty segment does; an
            # unused slot's two columns land nowhere)
            C = min(2 ** (depth - 1), self.cap)
            by_column = node_sums(
                self.head(stats),
                self.head(2 * state.slot + state.went_right), 2 * C)
            return jnp.zeros((2 ** depth, stats.shape[1]), stats.dtype).at[
                _child_ids(state.node_of_slot, 2 ** depth)].set(
                by_column, mode="drop")

    def add_leaf_values(self, margins: jnp.ndarray, state: _TreeState,
                        vals: jnp.ndarray, depth: int) -> jnp.ndarray:
        """``margins + vals[state.node]`` of ONE lane's finished tree
        (``vals`` (2^depth,), one value a leaf): a boosting round's step,
        scope ``gbt.pick``. The read is the transpose of the leaf sums
        (``leaves``), so it takes their form (see _sums_form): the gather
        under ``scatter``, the CPU path and the tests' reference;
        :func:`_leaf_values_dense` under the ``matmul`` family, where a
        batched per-row gather is slow. Both give the same bits."""
        form = "dense" if self.sums_form(depth) == "dense" else "gather"
        _PICK_FORMS[form] += 1
        with jax.named_scope("gbt.pick"):
            if form == "gather":
                return margins + vals[state.node]
            return margins + _leaf_values_dense(
                vals, state, depth > 0 and self.is_identity(depth - 1,
                                                            depth))


def _grow_tree(packed: jnp.ndarray, feat_of: jnp.ndarray,
               block_start: jnp.ndarray, packed_thr: jnp.ndarray,
               stats: jnp.ndarray, *, depth: int, gain_fn,
               min_info_gain: float,
               feat_key: Optional[jnp.ndarray] = None,
               max_features: Optional[int] = None,
               node_cap: Optional[int] = None,
               feat_map: Optional[jnp.ndarray] = None,
               hist_mode: Optional[str] = None,
               axis_name: Optional[str] = None,
               row_total: Optional[int] = None,
               hist_rows: Optional[int] = None):
    """Grow one complete tree of static ``depth`` over a packed binned
    design (see :class:`_PackedDesign`).

    gain_fn(left, right, total) -> (..., ) gains with -inf where a split
    is invalid; ``left/right`` are (C, TB, S) and ``total`` (C, 1, S).

    ``node_cap`` bounds the per-level active-node slot count (default
    ``_DEFAULT_NODE_CAP``, further clamped by the row count — a node
    with no rows is never split). If a level would overflow the cap,
    the highest-numbered nodes are denied splits (budget mask below) so
    the bound stays sound — the analogue of MLlib's maxMemoryInMB
    node-batch limiting. With default min-instances grids (>= 10) the
    cap never binds; it only limits very deep unregularized trees.

    With ``axis_name`` set (row-sharded fit inside shard_map), every
    cross-row reduction — per-level histograms, node totals, leaf stats
    and the slot-compression occupancy — goes through ``psum`` over that
    mesh axis, so each shard holds only its rows yet every shard makes
    identical split decisions (the TPU equivalent of XGBoost's Rabit
    allreduce, SURVEY §2.9). ``row_total`` must then carry the GLOBAL
    row count (slot caps must not depend on the shard-local count).

    A level wider than the cap (or whose next level is) is a COMPRESSED
    level: its slots are the ranks of the occupied node ids in ascending
    order, carried over from the level before at that level's end (scope
    ``tree.compress``, see _carry_slots: the occupancy of the 2 * C
    (slot, side) columns in the node sums' form, a rank table read in the
    routing's form; no sort, no per-row scatter, the same slots on every
    row shard). Every other level is an identity level: slots are node ids.

    The node sums (scope ``tree.node_sums``: each level's per-slot
    ``total``, with the row count of an identity level as one more
    column, and the per-leaf ``leaf_stats``) are sums of ``stats`` in its
    own dtype, in one form a tree (see _sums_form): ``segment_sum``
    under the ``scatter`` mode, ``_slot_sums`` (a select over the slot
    axis reduced over the rows; no per-row scatter-add) under the
    ``matmul`` family. They are never read from ``hist``, which holds
    them but is a bf16-pass contraction on the chip.

    With ``hist_rows`` only the leading ``hist_rows`` rows carry
    statistics: the level histograms contract them and the node sums add
    them up, and what ``stats`` holds behind them is never read; the rows
    behind them are routed like every other and occupy their nodes (the
    order of a fold-grid lane whose held-out rows come last, see
    _fold_order).

    Returns (feat_heap (2^depth - 1,), thr_heap (2^depth - 1,),
    leaf_stats (2^depth, S), final node assignment (n,)).
    """
    grower = _TreeGrower(
        packed, feat_of, block_start, packed_thr, stats.dtype,
        max_depth=depth, feat_key=feat_key, max_features=max_features,
        node_cap=node_cap, feat_map=feat_map, hist_mode=hist_mode,
        axis_name=axis_name, row_total=row_total, hist_rows=hist_rows)
    state = grower.levels(None, stats, gain_fn, min_info_gain, 0, depth,
                          depth)
    return (state.feat_heap, state.thr_heap,
            grower.leaves(state, stats, depth), state.node)


def _grow_blocks(grower: _TreeGrower, depths: tuple, lanes: tuple,
                 stats: jnp.ndarray, lane_args: tuple, gain_of):
    """One tree a lane of a fold-grid program whose lanes come in DEPTH
    BLOCKS (see _candidate_groups): ``stats`` (L, n, S) and the per-lane
    ``lane_args`` ((L,) each) hold block ``b``'s ``lanes[b]`` lanes, which
    grow to ``depths[b]`` (ascending), one block after another;
    ``gain_of(*lane_args)`` gives a lane's ``(gain_fn, min_info_gain)``.

    The levels are traced ONCE, not once a block: segment ``[depths[k-1],
    depths[k])`` runs as one ``vmap`` over the lanes of every block still
    growing (all 54 lanes of a default grid through levels 0-2, the 36
    deeper ones through 3-5, the 18 deepest through 6-11), then block
    ``k`` takes its leaves and leaves. So a lane still grows to its own
    depth and no deeper, and the program holds the levels of its deepest
    block only: what it takes to trace, lower, compile and load. Blocks
    whose levels differ in form over a segment (``level_forms``: a
    depth-9 lane's last level is an identity level, a depth-12 lane's
    level 8 is compressed) run that segment apart.

    Returns a tuple a block of ``(feat_heap (lanes, 2^depth - 1), thr_heap,
    leaf_stats (lanes, 2^depth, S), state)``: the lanes' final
    :class:`_TreeState`, whose ``node`` (lanes, n) is every row's leaf and
    whose last level's slots are what ``add_leaf_values`` reads."""
    ends = np.cumsum(lanes)
    block = [slice(int(e - k), int(e)) for e, k in zip(ends, lanes)]
    states: List[Optional[_TreeState]] = [None] * len(depths)
    lo = 0
    for first, hi in enumerate(depths):
        alike: Dict[tuple, list] = {}
        for b in range(first, len(depths)):
            alike.setdefault(grower.level_forms(lo, hi, depths[b]),
                             []).append(b)
        for members in alike.values():
            depth = depths[members[0]]

            def stacked(of_block):
                parts = [of_block(b) for b in members]
                return (parts[0] if len(parts) == 1 else
                        jax.tree_util.tree_map(
                            lambda *a: jnp.concatenate(a), *parts))

            def levels(state, lane_stats, *args):
                gain_fn, min_info_gain = gain_of(*args)
                return grower.levels(state, lane_stats, gain_fn,
                                     min_info_gain, lo, hi, depth)
            lane_stats = stacked(lambda b: stats[block[b]])
            args = stacked(lambda b: tuple(a[block[b]] for a in lane_args))
            if lo == 0:
                grown = jax.vmap(functools.partial(levels, None))(
                    lane_stats, *args)
            else:
                grown = jax.vmap(levels)(stacked(lambda b: states[b]),
                                         lane_stats, *args)
            at = 0
            for b in members:
                states[b] = jax.tree_util.tree_map(
                    lambda a: a[at:at + lanes[b]], grown)
                at += lanes[b]
        lo = hi
    return tuple(
        (state.feat_heap, state.thr_heap,
         jax.vmap(lambda st, ls: grower.leaves(st, ls, depth))(
             state, stats[block[b]]),
         state)
        for b, (depth, state) in enumerate(zip(depths, states)))


#: widest design (columns) and deepest tree the dense traversal still
#: takes (see _traverse_form). One walk on a v5e, 20 trees x 49,152 rows,
#: median of five (builder's chip runs, PR 40, PERF.md section 6), gather
#: -> dense: at 200 columns 60.8 -> 1.26 ms at depth 3, 151 -> 1.93 ms at
#: depth 6, 335 -> 8.2 ms at depth 12, 652 -> 24.7 ms at 14, 699 -> 89.8 ms
#: at 16 (the gather adds a level's cost, the dense form doubles a level's
#: nodes: they would meet near depth 18); at depth 6, 208 -> 6.2 / 47.0 ms
#: at 1,000 / 4,000 columns and 175 -> 146 ms at 16,000; at depth 3, 74.5
#: -> 82.9 ms at 16,000, where the gather wins; at depth 12, 376 -> 144 ms
#: at 8,192. At 196,608 rows and depth 6, 949 -> 4.95 ms
_TRAVERSE_DENSE_MAX_D = 8192
_TRAVERSE_DENSE_MAX_DEPTH = 16

#: how many traced ``_traverse`` walks took each form
_TRAVERSE_FORMS = {"dense": 0, "gather": 0}


def _traverse_form(d: int, depth: int) -> str:
    """How ``_traverse`` reads each row's split column and threshold:
    "gather" (``feat_heap[heap]``, ``thr_heap[heap]``, ``X[rows, f]``:
    three per-row gathers a level, O(n); cheap on a CPU, the tests'
    reference; 27-31 ns a row on the chip) or "dense" (selects over a
    level's nodes and over the columns, O(n * (2^level + d)) elementwise,
    no gather). Chosen at trace time from the backend and the walk's
    shape: dense on an accelerator up to ``_TRAVERSE_DENSE_MAX_D``
    columns and ``_TRAVERSE_DENSE_MAX_DEPTH`` levels. Both forms give the
    same integers."""
    if (jax.default_backend() == "cpu" or d > _TRAVERSE_DENSE_MAX_D
            or depth > _TRAVERSE_DENSE_MAX_DEPTH):
        return "gather"
    return "dense"


def tree_traverse_forms() -> dict:
    """Traced ``_traverse`` walks so far in this process by form,
    ``{"dense": k, "gather": m}`` (see _traverse_form): the record of which
    path the compiled scoring programs hold."""
    return dict(_TRAVERSE_FORMS)


def _traverse(X: jnp.ndarray, feat_heap: jnp.ndarray, thr_heap: jnp.ndarray,
              depth: int) -> jnp.ndarray:
    """Leaf index in [0, 2^depth) for every row; static-depth descent in
    the form of _traverse_form. A row goes left where ``x <= t`` in the
    dtype both promote to (a NaN walks right). The dense form moves ``x``
    and ``t`` as integer bits, so the compare sees the gather's operands:
    level ``l``'s nodes are the static heap slice ``[2^l - 1, 2^(l+1) -
    1)``; a row's feature id and threshold are selected over that slice
    (nodes on the major axis, rows along the lanes), its value over the
    columns of ``X.T``."""
    n, d = X.shape
    form = _traverse_form(d, depth)
    _TRAVERSE_FORMS[form] += 1
    node = jnp.zeros((n,), jnp.int32)
    with jax.named_scope("tree.traverse"):
        if form == "gather":
            rows = jnp.arange(n)
            for level in range(depth):
                heap = 2 ** level - 1 + node  # levels concatenate
                f = feat_heap[heap]
                t = thr_heap[heap]
                go_left = X[rows, f] <= t
                node = 2 * node + (1 - go_left.astype(jnp.int32))
            return node
        dtype = jnp.result_type(X.dtype, thr_heap.dtype)      # a float
        bits = jnp.int64 if jnp.dtype(dtype).itemsize == 8 else jnp.int32
        x_bits = jax.lax.bitcast_convert_type(X.astype(dtype), bits).T
        t_bits = jax.lax.bitcast_convert_type(thr_heap.astype(dtype), bits)
        column = jnp.arange(d, dtype=jnp.int32)[:, None]
        for level in range(depth):
            first, m = 2 ** level - 1, 2 ** level
            mine = node[None, :] == jnp.arange(m, dtype=jnp.int32)[:, None]
            f = jnp.sum(jnp.where(mine, feat_heap[first:first + m, None], 0),
                        axis=0, dtype=feat_heap.dtype)
            t = jnp.sum(jnp.where(mine, t_bits[first:first + m, None], 0),
                        axis=0, dtype=bits)
            x = jnp.sum(jnp.where(f[None, :] == column, x_bits, 0), axis=0,
                        dtype=bits)
            go_left = (jax.lax.bitcast_convert_type(x, dtype)
                       <= jax.lax.bitcast_convert_type(t, dtype))
            node = 2 * node + (1 - go_left.astype(jnp.int32))
        return node


# ---------------------------------------------------------------------------
# split criteria
# ---------------------------------------------------------------------------

def _xgb_gain(reg_lambda: float, gamma: float, min_child_weight: float):
    """Second-order gain (stats = [grad, hess]); XGBoost objective."""
    def gain(left, right, total):
        def score(s):
            return s[..., 0] ** 2 / (s[..., 1] + reg_lambda)
        g = 0.5 * (score(left) + score(right) - score(total)) - gamma
        ok = ((left[..., 1] >= min_child_weight)
              & (right[..., 1] >= min_child_weight))
        return jnp.where(ok, g, -jnp.inf)
    return gain


def _gini_gain(min_instances: float):
    """Weighted gini impurity gain (stats = per-class weights); MLlib
    'gini' impurity, tree/impurity/Gini in Spark MLlib."""
    def impurity_weighted(s):               # sum_c s_c - sum_c s_c^2 / w
        w = jnp.sum(s, axis=-1)
        return w - jnp.sum(s * s, axis=-1) / jnp.maximum(w, 1e-12)
    def gain(left, right, total):
        wl = jnp.sum(left, axis=-1)
        wr = jnp.sum(right, axis=-1)
        wp = jnp.maximum(jnp.sum(total, axis=-1), 1e-12)
        g = (impurity_weighted(total) - impurity_weighted(left)
             - impurity_weighted(right)) / wp
        ok = (wl >= min_instances) & (wr >= min_instances)
        return jnp.where(ok, g, -jnp.inf)
    return gain


def _entropy_gain(min_instances: float):
    def impurity_weighted(s):
        w = jnp.maximum(jnp.sum(s, axis=-1, keepdims=True), 1e-12)
        p = s / w
        ent = -jnp.sum(jnp.where(s > 0, p * jnp.log(p), 0.0), axis=-1)
        return w[..., 0] * ent
    def gain(left, right, total):
        wl = jnp.sum(left, axis=-1)
        wr = jnp.sum(right, axis=-1)
        wp = jnp.maximum(jnp.sum(total, axis=-1), 1e-12)
        g = (impurity_weighted(total) - impurity_weighted(left)
             - impurity_weighted(right)) / wp
        ok = (wl >= min_instances) & (wr >= min_instances)
        return jnp.where(ok, g, -jnp.inf)
    return gain


def _variance_stats(w: jnp.ndarray, yc: jnp.ndarray) -> jnp.ndarray:
    """(n, 3) statistics of a regression tree, ``[w, hi, lo]`` with ``hi +
    lo == w * yc`` exactly: the rows' weights and their weighted CENTRED
    labels (``yc``: the label less the lane's masked mean, see
    _forest_body), the real-valued column in two pieces of which each
    survives the chip's level histogram. That histogram is a
    default-precision einsum, one bf16 pass over the statistics (see
    _hist_mode), which keeps 8 significant bits of a value: small integers
    (weights, class indicators) pass through it exactly, a real number does
    not. ``hi`` is ``w * yc`` rounded to those 8 bits (``reduce_precision``,
    an operation of its own: a float32 -> bfloat16 -> float32 round trip is
    one XLA may drop), ``lo`` the remainder, itself rounded by the pass to
    its own 8 bits: together 16 bits, an error of 2^-17 of a row's value
    where the single column's is 2^-9. On a CPU, whose histogram adds in
    the statistics' own dtype, both pieces are exact and their sums differ
    from the one column's in summation order only.

    No column of squares: with ``right = total - left`` the squares'
    sums cancel out of a split's gain (see _variance_gain), and a leaf's
    value needs none."""
    v = w * yc
    hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
    return jnp.stack([w, hi, v - hi], axis=1)


def _variance_gain(min_instances: float):
    """SSE-reduction gain over ``_variance_stats``; MLlib 'variance'. The
    gain of a split, ``(SSE(total) - SSE(left) - SSE(right)) / w(total)``
    with ``SSE(s) = sum(w y^2) - sum(w y)^2 / sum(w)``, is ``(sum_l^2 / w_l
    + sum_r^2 / w_r - sum_t^2 / w_t) / w_t`` in the sums of ``w * y``
    alone, the sums of squares of the two children adding up to the
    parent's; it is invariant under a shift of the label, which is what
    lets the statistics carry the centred one."""
    def score(s):
        return (s[..., 1] + s[..., 2]) ** 2 / jnp.maximum(s[..., 0], 1e-12)
    def gain(left, right, total):
        wp = jnp.maximum(total[..., 0], 1e-12)
        g = (score(left) + score(right) - score(total)) / wp
        ok = ((left[..., 0] >= min_instances)
              & (right[..., 0] >= min_instances))
        return jnp.where(ok, g, -jnp.inf)
    return gain


# ---------------------------------------------------------------------------
# jitted fit programs
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


def _row_draw(draw_fn, wkey, n: int, axis_name: Optional[str],
              row_total: Optional[int]):
    """Per-row random draw that is SHARD-POSITION-STABLE: under row
    sharding the draw is generated over the GLOBAL row count (identical
    on every shard — the key replicates) and each shard slices its own
    contiguous block, so a sharded fit resamples exactly the rows the
    single-device fit would (mesh ≡ local parity). The global vector is
    O(rows) scalars — negligible next to the (rows, features) design."""
    if not axis_name:
        return draw_fn(wkey, n)
    full = draw_fn(wkey, row_total)
    start = jax.lax.axis_index(axis_name) * n
    return jax.lax.dynamic_slice(full, (start,), (n,))


#: transient-memory budget (MB) for batching independent forest trees
#: with vmap on an accelerator. Trees of a bagged forest
#: are embarrassingly parallel — a lax.scan over them serializes
#: hundreds of tiny per-level ops (the dominant cost of small-data
#: selector searches, where dispatch/latency beats FLOPs), so trees are
#: fit in vmapped BLOCKS as large as the budget allows: small data ->
#: the whole forest in one program step; huge data -> block size 1,
#: which is exactly the old scan.
_TREE_BLOCK_BUDGET_MB = 256


def _tree_block_size(n: int, total_bins: int, depth: int, s_dim: int,
                     num_trees: int, hist_mode: str, pooled: bool,
                     outer_batch: int = 1) -> int:
    """How many forest trees one vmapped block fits (decided at trace
    time, like _hist_mode): as many as _TREE_BLOCK_BUDGET_MB holds on an
    accelerator, where a lax.scan of tiny per-level ops is launch-
    latency-bound; 1 (the plain scan) on a CPU, where batching measured
    a ~9% Titanic regression on one core."""
    if jax.default_backend() == "cpu":
        return 1
    budget = _TREE_BLOCK_BUDGET_MB * 1024 * 1024
    cap = min(n, _DEFAULT_NODE_CAP)
    c_max = min(2 ** max(depth - 1, 0), cap)
    per_tree = 2 * n * 8 + 2 * c_max * total_bins * s_dim * 8
    if hist_mode.partition("+")[0] != "scatter":
        # the (n, c_max) slot one-hot is the dominant per-tree transient
        # of the einsum strategy at depth
        per_tree += n * c_max * 8
        if pooled:
            per_tree += n * total_bins * 8  # per-tree pooled bin indicator
    if pooled:
        per_tree += 3 * n * 8               # per-tree gathered design cols
    b = max(1, int(budget // max(per_tree * outer_batch, 1)))
    return min(b, num_trees)


def _forest_body(packed, feat_of, block_start, packed_thr,
                 binned, col_thr, narrow_idx, wide_idx, y, key, mask,
                 min_instances, min_info_gain, subsample, *, kind: str,
                 depth, num_classes: int, num_trees: int,
                 max_features: Optional[int], pool_cfg: Optional[tuple],
                 impurity: str, bootstrap: bool,
                 hist_mode: Optional[str],
                 axis_name: Optional[str] = None,
                 row_total: Optional[int] = None,
                 val_rows=None, lanes: Optional[tuple] = None,
                 hist_rows: Optional[int] = None):
    """Shared forest program: ``mask`` (n,) row weights let one body
    serve the single fit (mask=ones), the fold x grid batched kernel
    (mask = fold membership, traced per-candidate hyperparams), and the
    "models"-axis mesh path — masked rows contribute nothing to
    histograms or leaves, which is exactly fitting on the subset.
    ``axis_name`` row-shards the fit: every cross-row reduction psums
    over that mesh axis (see _TreeGrower) and bootstrap draws slice a
    global-shaped sample (_row_draw). Independent trees are fit in
    vmapped blocks (see _tree_block_size).

    One fit (``lanes`` None: ``mask`` (n,), scalar hyperparameters, an int
    ``depth``), or the lanes of a fold-grid program in depth blocks
    (``lanes[b]`` lanes that grow to ``depth[b]``, ascending; ``mask``
    (L, n), the hyperparameters (L,) and ``val_rows`` (L, nv) hold the
    blocks one after another): every per-lane step then runs under
    ``vmap`` over all the lanes, a tree's feature pool and bin indicator
    are made once for all of them, and the trees grow through
    :func:`_grow_blocks`.

    ``val_rows`` ((nv,) int32 positions in the fitted table; the fused
    fit+metric kernel's in-fit form, see _eval_form) adds a fourth
    output: the leaf the grower routed each of those rows to in every
    tree, (T, nv) int32: ``_traverse``'s leaf, without the walk. Without
    it the body returns (feats, thrs, leaves) and traces what it always
    did. With ``lanes`` it returns a tuple a block of those, lanes
    first.

    ``hist_rows`` (with ``lanes``; instead of ``val_rows``): the body is
    one FOLD's, mapped over the folds of the program (see _by_fold): the
    design, ``binned`` and ``y`` are the table in the fold's own order,
    the rows it trains on first (``hist_rows`` of them, the rows ``mask``
    may weigh) and its held-out rows last (see _fold_order). The trees
    contract the head alone (see _TreeGrower) and the fourth output is the
    tail of every tree's final nodes, a slice. The bootstrap weights are
    drawn in that order: one stream a tree for every fold, keyed by the
    row's position in the lane's order."""
    assert val_rows is None or axis_name is None, \
        "val_rows index the whole table: not under row sharding"
    assert hist_rows is None or (val_rows is None and axis_name is None
                                 and lanes is not None)
    n, d = packed.shape
    dtype = packed_thr.dtype
    over = _over_lanes(lanes)
    depths = (depth,) if lanes is None else depth
    if kind == "cls":
        onehot = jax.nn.one_hot(y.astype(jnp.int32), num_classes,
                                dtype=dtype)
        y_mean = jnp.zeros(() if lanes is None else (sum(lanes),), dtype)

        def gain_of(min_instances, min_info_gain):
            return (_gini_gain(min_instances) if impurity == "gini"
                    else _entropy_gain(min_instances)), min_info_gain
    else:
        # a regression lane's statistics carry the label CENTRED on the
        # lane's masked mean, which re-enters at the leaves: the gain does
        # not see the shift (see _variance_gain), and a label of 1998 +- 11
        # would spend its significant bits on the 1998 (_variance_stats)
        def _gsum(v):
            return jax.lax.psum(v, axis_name) if axis_name else v
        y_mean = over(lambda mask: _gsum(jnp.sum(mask * y)) / jnp.maximum(
            _gsum(jnp.sum(mask)), 1.0))(mask)

        def gain_of(min_instances, min_info_gain):
            return _variance_gain(min_instances), min_info_gain

    held_oh = None if pool_cfg is not None else _fold_indicator(
        packed, feat_of, dtype, hist_mode, hist_rows)

    def one_tree(tkey):
        pkey, wkey, fkey = jax.random.split(tkey, 3)

        def tree_stats(mask, subsample, y_mean):
            if bootstrap:
                w = _row_draw(
                    lambda k, m: jax.random.poisson(k, subsample,
                                                    (m,)).astype(dtype),
                    wkey, n, axis_name, row_total)
            else:
                w = jnp.ones((n,), dtype)
            w = w * mask
            return (onehot * w[:, None] if kind == "cls"
                    else _variance_stats(w, y - y_mean))

        def tree_leaves(leaf_stats, y_mean):
            if kind == "cls":
                lw = jnp.sum(leaf_stats, axis=-1, keepdims=True)
                return jnp.where(lw > 0,
                                 leaf_stats / jnp.maximum(lw, 1e-12),
                                 1.0 / num_classes)
            return y_mean + (leaf_stats[:, 1] + leaf_stats[:, 2]
                             ) / jnp.maximum(leaf_stats[:, 0], 1e-12)
        with jax.named_scope("tree.bootstrap"):
            stats = over(tree_stats)(mask, subsample, y_mean)
        design, pool = (packed, feat_of, block_start, packed_thr), None
        if pool_cfg is not None:
            with jax.named_scope("tree.pool"):
                pool, *design = _tree_pool(
                    pkey, binned, col_thr, narrow_idx, wide_idx, pool_cfg)
        grower = _TreeGrower(
            *design, dtype, max_depth=max(depths), feat_key=fkey,
            max_features=max_features, feat_map=pool, hist_mode=hist_mode,
            axis_name=axis_name, row_total=row_total, hist_rows=hist_rows,
            bin_oh=held_oh)
        if lanes is None:
            state = grower.levels(None, stats, *gain_of(
                min_instances, min_info_gain), 0, depth, depth)
            tree = (state.feat_heap, state.thr_heap, tree_leaves(
                grower.leaves(state, stats, depth), y_mean))
            return tree if val_rows is None else (
                *tree, state.node[val_rows])
        grown = _grow_blocks(grower, depths, lanes, stats,
                             (min_instances, min_info_gain), gain_of)
        at, trees = 0, []
        for k, (feat, thr, leaf_stats, state) in zip(lanes, grown):
            mine = slice(at, at + k)
            tree = (feat, thr, jax.vmap(tree_leaves)(leaf_stats,
                                                     y_mean[mine]))
            if val_rows is not None:
                tree += (jax.vmap(lambda nd, rows: nd[rows])(
                    state.node, val_rows[mine]),)
            elif hist_rows is not None:
                tree += (state.node[:, hist_rows:],)
            trees.append(tree)
            at += k
        return tuple(trees)

    keys = jax.random.split(key, num_trees)
    # full-design TB is a safe upper bound for the pooled design's; the
    # lanes of a block share the budget (a fold's body holds its own lanes
    # and indicator, one fold at a time), and the blocks one block size
    tb = min(_tree_block_size(
        row_total if row_total is not None else n,
        int(feat_of.shape[0]), block_depth,
        num_classes if kind == "cls" else 3, num_trees,
        hist_mode or "scatter", pool_cfg is not None, block_lanes)
        for block_depth, block_lanes in zip(depths, lanes or (1,)))
    if tb >= num_trees:
        outs = jax.vmap(one_tree)(keys)
    elif tb == 1:
        _, outs = jax.lax.scan(lambda c, k: (c, one_tree(k)), None, keys)
    else:
        pad = (-num_trees) % tb
        keys_p = jnp.concatenate([keys, keys[:pad]], axis=0)
        _, outs = jax.lax.scan(
            lambda c, kb: (c, jax.vmap(one_tree)(kb)), None,
            keys_p.reshape(-1, tb, *keys.shape[1:]))
        outs = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:])[:num_trees], outs)
    if lanes is None:
        return outs
    # a block's trees come out trees first: lanes first
    return jax.tree_util.tree_map(lambda a: jnp.moveaxis(a, 0, 1), outs)


@functools.partial(
    jax.jit, static_argnames=("depth", "num_classes", "num_trees",
                              "max_features", "pool_cfg", "impurity",
                              "bootstrap", "hist_mode"))
def _fit_forest_classifier(packed, feat_of, block_start, packed_thr,
                           binned, col_thr, narrow_idx, wide_idx, y, key,
                           *, depth: int, num_classes: int, num_trees: int,
                           max_features: Optional[int],
                           pool_cfg: Optional[tuple], impurity: str,
                           min_instances: float, min_info_gain: float,
                           subsample: float, bootstrap: bool,
                           hist_mode: Optional[str]):
    return _forest_body(
        packed, feat_of, block_start, packed_thr, binned, col_thr,
        narrow_idx, wide_idx, y, key, jnp.ones_like(y), min_instances,
        min_info_gain, subsample, kind="cls", depth=depth,
        num_classes=num_classes, num_trees=num_trees,
        max_features=max_features, pool_cfg=pool_cfg, impurity=impurity,
        bootstrap=bootstrap, hist_mode=hist_mode)


@functools.partial(
    jax.jit, static_argnames=("depth", "num_trees", "max_features",
                              "pool_cfg", "bootstrap", "hist_mode"))
def _fit_forest_regressor(packed, feat_of, block_start, packed_thr,
                          binned, col_thr, narrow_idx, wide_idx, y, key,
                          *, depth: int, num_trees: int,
                          max_features: Optional[int],
                          pool_cfg: Optional[tuple],
                          min_instances: float, min_info_gain: float,
                          subsample: float, bootstrap: bool,
                          hist_mode: Optional[str]):
    return _forest_body(
        packed, feat_of, block_start, packed_thr, binned, col_thr,
        narrow_idx, wide_idx, y, key, jnp.ones_like(y), min_instances,
        min_info_gain, subsample, kind="reg", depth=depth, num_classes=0,
        num_trees=num_trees, max_features=max_features, pool_cfg=pool_cfg,
        impurity="", bootstrap=bootstrap, hist_mode=hist_mode)


def _over_lanes(lanes: Optional[tuple]):
    """How a tree family's body runs a per-lane step (see _forest_body,
    _gbt_body): as it is for one fit (``lanes`` None), under ``jax.vmap``
    over the leading axis of its arguments for the lanes of a fold-grid
    program."""
    return (lambda step: step) if lanes is None else jax.vmap


def _leaf_values(leaf_stats, step_size, reg_lambda):
    """A boosted tree's (2^depth,) leaf values from its (2^depth, 2) leaf
    sums of gradients and hessians; 0 where a leaf holds no row."""
    vals = -step_size * leaf_stats[:, 0] / (leaf_stats[:, 1] + reg_lambda)
    return jnp.where(jnp.sum(jnp.abs(leaf_stats), axis=1) > 0, vals, 0.0)


def _gbt_body(packed, feat_of, block_start, packed_thr, y, key, mask,
              step_size, reg_lambda, gamma, min_child_weight, subsample,
              *, depth, num_rounds: int, objective: str,
              hist_mode: Optional[str],
              axis_name: Optional[str] = None,
              row_total: Optional[int] = None,
              lanes: Optional[tuple] = None,
              hist_rows: Optional[int] = None):
    """Shared boosting program with row-mask semantics (see
    _forest_body): masked rows get zero grad/hess weight; the base
    margin is the mask-weighted mean. ``axis_name`` row-shards the fit
    (psum'd histograms/means, global-sliced subsampling).

    One fit (``lanes`` None: ``mask`` (n,), scalar hyperparameters, an int
    ``depth``), or the lanes of a fold-grid program in depth blocks
    (``lanes[b]`` lanes that grow to ``depth[b]``, ascending; ``mask``
    (L, n) and the hyperparameters (L,) hold the blocks one after
    another): every per-lane step then runs under ``vmap`` over all the
    lanes, and the trees grow through :func:`_grow_blocks`.

    Returns (feats, thrs, leaves, base, margins), a tuple a block of them
    with ``lanes``: ``margins`` (n,) is the scan's final carry, the
    finished margin of EVERY row of the table, masked or not (the grower
    routes them all, and each round adds its leaf value to all of them):
    what the in-fit form of the fused fit+metric kernel scores the
    held-out rows from (see _eval_form). It is the sequential float32 sum
    over rounds, not ``base + sum(vals)``: equal to a few ulp. A caller
    that drops it compiles what it compiled without it.

    ``hist_rows``: one FOLD's body over the table in the fold's own order,
    as in _forest_body: the rounds' trees contract the leading
    ``hist_rows`` rows, the held-out rows' margins are the tail of
    ``margins``, and a round's subsample is drawn in that order."""
    n, d = packed.shape
    dtype = packed_thr.dtype
    over = _over_lanes(lanes)
    depths = (depth,) if lanes is None else depth

    def _gsum(v):
        return jax.lax.psum(v, axis_name) if axis_name else v

    def start(mask):
        msum = jnp.maximum(_gsum(jnp.sum(mask)), 1.0)
        mean_y = _gsum(jnp.sum(mask * y)) / msum
        if objective == "logistic":
            p0 = jnp.clip(mean_y, 1e-6, 1 - 1e-6)
            base = jnp.log(p0 / (1 - p0))
        else:
            base = mean_y
        return base, jnp.broadcast_to(base.astype(dtype), (n,))
    base, margins0 = over(start)(mask)

    def gain_of(reg_lambda, gamma, min_child_weight):
        return _xgb_gain(reg_lambda, gamma, min_child_weight), 0.0
    held_oh = _fold_indicator(packed, feat_of, dtype, hist_mode, hist_rows)

    def one_round(margins, rkey):
        def round_stats(margins, mask, subsample):
            if objective == "logistic":
                p = jax.nn.sigmoid(margins)
                g, h = p - y, jnp.maximum(p * (1 - p), 1e-12)
            else:
                g, h = margins - y, jnp.ones_like(y)
            m = _row_draw(
                lambda k, mm: jax.random.bernoulli(k, subsample,
                                                   (mm,)).astype(dtype),
                rkey, n, axis_name, row_total) * mask
            return jnp.stack([g * m, h * m], axis=1)

        with jax.named_scope("gbt.round"):
            stats = over(round_stats)(margins, mask, subsample)
            grower = _TreeGrower(
                packed, feat_of, block_start, packed_thr, dtype,
                max_depth=max(depths), hist_mode=hist_mode,
                axis_name=axis_name, row_total=row_total,
                hist_rows=hist_rows, bin_oh=held_oh)
            if lanes is None:
                state = grower.levels(
                    None, stats, *gain_of(reg_lambda, gamma,
                                          min_child_weight), 0, depth, depth)
                vals = _leaf_values(grower.leaves(state, stats, depth),
                                    step_size, reg_lambda)
                return (grower.add_leaf_values(margins, state, vals, depth),
                        (state.feat_heap, state.thr_heap, vals))
            grown = _grow_blocks(grower, depths, lanes, stats,
                                 (reg_lambda, gamma, min_child_weight),
                                 gain_of)
            at, new_margins, trees = 0, [], []
            for k, block_depth, (feat, thr, leaf_stats, state) in zip(
                    lanes, depths, grown):
                mine = slice(at, at + k)
                vals = jax.vmap(_leaf_values)(leaf_stats, step_size[mine],
                                              reg_lambda[mine])
                new_margins.append(jax.vmap(functools.partial(
                    grower.add_leaf_values, depth=block_depth))(
                    margins[mine], state, vals))
                trees.append((feat, thr, vals))
                at += k
            return jnp.concatenate(new_margins), tuple(trees)
    margins, trees = jax.lax.scan(
        one_round, margins0, jax.random.split(key, num_rounds))
    if lanes is None:
        return (*trees, base, margins)
    ends = np.cumsum(lanes)
    # a block's trees come out of the scan rounds first: lanes first
    return tuple(
        tuple(jnp.moveaxis(a, 0, 1) for a in block_trees)
        + (base[e - k:e], margins[e - k:e])
        for block_trees, e, k in zip(trees, ends, lanes))


@functools.partial(
    jax.jit, static_argnames=("depth", "num_rounds", "objective",
                              "hist_mode"))
def _fit_gbt(packed, feat_of, block_start, packed_thr, y, key, *, depth: int,
             num_rounds: int, step_size: float, reg_lambda: float,
             gamma: float, min_child_weight: float, subsample: float,
             objective: str, hist_mode: Optional[str]):
    return _gbt_body(packed, feat_of, block_start, packed_thr, y, key,
                     jnp.ones_like(y), step_size, reg_lambda, gamma,
                     min_child_weight, subsample, depth=depth,
                     num_rounds=num_rounds, objective=objective,
                     hist_mode=hist_mode)[:4]


def _gbt_softmax_body(packed, feat_of, block_start, packed_thr, y, key,
                      mask, step_size, reg_lambda, gamma,
                      min_child_weight, subsample, *, depth,
                      num_rounds: int, num_classes: int,
                      hist_mode: Optional[str],
                      axis_name: Optional[str] = None,
                      row_total: Optional[int] = None,
                      lanes: Optional[tuple] = None,
                      hist_rows: Optional[int] = None):
    """K-class softmax boosting: each round fits one tree PER CLASS on
    the softmax gradients/hessians (g_k = p_k - 1[y=k],
    h_k = p_k(1-p_k)) — the ``multi:softprob`` objective the reference
    reaches through xgboost4j (OpXGBoostClassifier.scala:47; MLlib GBT
    itself has no multiclass mode). The K trees of a round see the same
    fixed margins, so they vmap as one batched program (histogram width
    x K, sequential depth unchanged). Base margins are the log class
    priors. One fit, or with ``lanes`` the lanes of a fold-grid program in
    depth blocks, as in _gbt_body: the K trees of a lane are then K lanes
    of :func:`_grow_blocks`. Returns (feats (R,K,H), thrs (R,K,H), leaves
    (R,K,L), base (K,), margins (n,K)), a tuple a block of them with
    ``lanes``: the last is the scan's final carry, every row's finished
    margins (see _gbt_body). ``hist_rows`` as in _gbt_body."""
    n, d = packed.shape
    dtype = packed_thr.dtype
    K = num_classes
    over = _over_lanes(lanes)
    depths = (depth,) if lanes is None else depth

    def _gsum(v):
        return jax.lax.psum(v, axis_name) if axis_name else v

    onehot = jax.nn.one_hot(y.astype(jnp.int32), K, dtype=dtype)

    def start(mask):
        counts = _gsum(jnp.sum(mask[:, None] * onehot, axis=0))
        priors = jnp.clip(counts / jnp.maximum(jnp.sum(counts), 1.0),
                          1e-6, 1.0)
        base = jnp.log(priors)
        return base, jnp.broadcast_to(base, (n, K)).astype(dtype)
    base, margins0 = over(start)(mask)

    def gain_of(reg_lambda, gamma, min_child_weight):
        return _xgb_gain(reg_lambda, gamma, min_child_weight), 0.0
    held_oh = _fold_indicator(packed, feat_of, dtype, hist_mode, hist_rows)

    def one_round(margins, rkey):
        def round_stats(margins, mask, subsample):
            p = jax.nn.softmax(margins, axis=1)
            g = p - onehot                                  # (n, K)
            h = jnp.maximum(p * (1.0 - p), 1e-12)
            m = _row_draw(
                lambda k, mm: jax.random.bernoulli(k, subsample,
                                                   (mm,)).astype(dtype),
                rkey, n, axis_name, row_total) * mask
            return jnp.stack([g.T * m, h.T * m], axis=2)    # (K, n, 2)

        with jax.named_scope("gbt.round"):
            stats = over(round_stats)(margins, mask, subsample)
            grower = _TreeGrower(
                packed, feat_of, block_start, packed_thr, dtype,
                max_depth=max(depths), hist_mode=hist_mode,
                axis_name=axis_name, row_total=row_total,
                hist_rows=hist_rows, bin_oh=held_oh)
            if lanes is None:
                def per_class(class_stats, class_margins):
                    state = grower.levels(
                        None, class_stats, *gain_of(
                            reg_lambda, gamma, min_child_weight), 0, depth,
                        depth)
                    vals = _leaf_values(
                        grower.leaves(state, class_stats, depth), step_size,
                        reg_lambda)
                    return (grower.add_leaf_values(class_margins, state,
                                                   vals, depth),
                            state.feat_heap, state.thr_heap, vals)
                class_margins, feats, thrs, vals = jax.vmap(per_class)(
                    stats, margins.T)
                return class_margins.T, (feats, thrs, vals)
            # a lane's K trees are K lanes of the grower, a block's lanes
            # still one after another
            grown = _grow_blocks(
                grower, depths, tuple(k * K for k in lanes),
                stats.reshape((-1,) + stats.shape[2:]),
                tuple(jnp.repeat(a, K) for a in (reg_lambda, gamma,
                                                 min_child_weight)),
                gain_of)
            at, new_margins, trees = 0, [], []
            for k, block_depth, (feat, thr, leaf_stats, state) in zip(
                    lanes, depths, grown):
                mine = slice(at, at + k)
                vals = jax.vmap(_leaf_values)(
                    leaf_stats, jnp.repeat(step_size[mine], K),
                    jnp.repeat(reg_lambda[mine], K))
                # a lane's K class margins as K lanes of the grower
                class_margins = jax.vmap(functools.partial(
                    grower.add_leaf_values, depth=block_depth))(
                    jnp.swapaxes(margins[mine], 1, 2).reshape(k * K, n),
                    state, vals)
                new_margins.append(jnp.swapaxes(
                    class_margins.reshape(k, K, n), 1, 2))
                trees.append(tuple(a.reshape((k, K) + a.shape[1:])
                                   for a in (feat, thr, vals)))
                at += k
            return jnp.concatenate(new_margins), tuple(trees)
    margins, trees = jax.lax.scan(
        one_round, margins0, jax.random.split(key, num_rounds))
    if lanes is None:
        return (*trees, base, margins)
    ends = np.cumsum(lanes)
    # a block's trees come out of the scan rounds first: lanes first
    return tuple(
        tuple(jnp.moveaxis(a, 0, 1) for a in block_trees)
        + (base[e - k:e], margins[e - k:e])
        for block_trees, e, k in zip(trees, ends, lanes))


@functools.partial(
    jax.jit, static_argnames=("depth", "num_rounds", "num_classes",
                              "hist_mode"))
def _fit_gbt_softmax(packed, feat_of, block_start, packed_thr, y, key, *,
                     depth: int, num_rounds: int, num_classes: int,
                     step_size: float, reg_lambda: float, gamma: float,
                     min_child_weight: float, subsample: float,
                     hist_mode: Optional[str]):
    return _gbt_softmax_body(
        packed, feat_of, block_start, packed_thr, y, key,
        jnp.ones_like(y), step_size, reg_lambda, gamma, min_child_weight,
        subsample, depth=depth, num_rounds=num_rounds,
        num_classes=num_classes, hist_mode=hist_mode)[:4]


@functools.partial(jax.jit, static_argnames=("depth",))
def _predict_leaves(X, feats, thrs, depth: int):
    """(T, n) leaf index per tree via vmapped static-depth traversal (see
    _traverse_form). The dense form's selects fuse into their reductions
    under the ``vmap``: compiled for a v5e at 20 trees of depth 12 and 200
    columns, 0 MB of temporaries at 49,152 rows and 157 MB, the (T, n)
    carries, at 196,608 (PR 40)."""
    return jax.vmap(lambda f, t: _traverse(X, f, t, depth))(feats, thrs)


# ---------------------------------------------------------------------------
# fold x grid batched kernels (validator fast path + "models" mesh axis)
# ---------------------------------------------------------------------------
#
# The reference's per-fold/per-grid Future pool (OpValidator.scala:270)
# maps for tree families onto ONE vmapped program per static shape group
# (depth/trees/rounds/bins): each candidate = (fold mask, traced
# hyperparams). With a ("models", "data") mesh the candidate axis shards
# over chips (data replicated — trees are task-parallel here, like the
# reference's executor model). Documented deviation from the sequential
# path: bin edges come from the WHOLE prepared matrix rather than each
# fold's train rows (feature-distribution information only — standard
# for histogram-GBM cross-validation).

def _all_lanes(blocks: tuple):
    """The per-lane arguments of a fold-grid program's DEPTH BLOCKS (see
    _candidate_groups) as the tree bodies take them (see _forest_body,
    _gbt_body ``lanes``): each argument with the blocks' lanes one block
    after another on its leading axis, and the lane count a block."""
    return (tuple(jnp.concatenate(arg) for arg in zip(*blocks)),
            tuple(block[0].shape[0] for block in blocks))


def _fold_order(masks: np.ndarray, val_rows: np.ndarray
                ) -> Optional[np.ndarray]:
    """(F, n) int32 ``order``: the rows of the fitted table in each fold's
    OWN ORDER, the rows the fold trains on first (ascending) and its
    held-out rows ``val_rows[f]`` last, as they were given. A function of
    ``val_rows`` alone, and its shapes are the program's own (``n``,
    ``nv``, ``F``): k folds, the one split of a train-validation split and
    a racing rung's subset of folds alike. In that order the rows that can
    carry weight are the static head ``[0, n - nv)`` and a lane's held-out
    rows the static tail: the level histograms contract the head and not
    the whole table (see _TreeGrower ``hist_rows``), and the held-out
    rows' leaves or margins are a slice, not a per-row gather.

    None where the folds do not allow it: a fold names a row twice, or
    ``masks`` (F, n) gives weight to a row its fold holds out, whose
    statistics the head form would drop. Rows of the head may carry weight
    zero (the rows the validator drops to make the folds equal, a racing
    rung's thinned rows)."""
    F, n = masks.shape
    order = np.empty((F, n), dtype=np.int32)
    for f, held_rows in enumerate(val_rows):
        held = np.zeros(n, dtype=bool)
        held[held_rows] = True
        if held.sum() != len(held_rows) or masks[f, held].any():
            return None
        order[f] = np.concatenate([np.nonzero(~held)[0], held_rows])
    return order


def _by_fold(fit, blocks: tuple, order, tables: tuple):
    """A fold-grid program's fits a FOLD at a time: ``fit(fold_tables,
    lane_args, lanes)``, one fold's body (see _forest_body ``hist_rows``),
    mapped over the folds one after another (``lax.map``). A fold's body
    sees ``tables`` (per-row arrays of the fitted table) in the fold's own
    order, ``table[order[f]]``: row gathers once a call of the program, not
    once a tree or a level; and the fold's lanes: the per-lane arguments of
    the depth ``blocks`` (each fold-major, lane ``f * gk + j``, see
    _candidate_groups) as (F, lanes a fold) with the blocks side by side,
    ``lanes`` counting a block's lanes a fold. The body is traced once, as
    the lanes-only form is, and a fold's lanes share its design and
    indicator as the lanes of the whole table share theirs there.

    Why a map and not a ``vmap`` over the folds, which would run the
    folds' contractions as one batched one: on the chip the mapped form is
    the faster (my chip runs, PR 37, PERF.md section 6: ``.search`` 24.6
    against 22.4 models x folds/s, the binary pool 33.0 against 30.8; a
    third of the temporaries), and a second batching pass over the trees'
    or rounds' ``scan`` body costs what the first trace of it cost:
    tracing was 40-50 % slower here and ``setup_s`` went over its bound.
    Returns what the bodies return, a tuple a block, with the lanes back on
    one leading axis in the blocks' own order."""
    F = order.shape[0]
    lane_args = tuple(
        jnp.concatenate([a.reshape((F, -1) + a.shape[1:]) for a in arg],
                        axis=1) for arg in zip(*blocks))
    lanes = tuple(block[0].shape[0] // F for block in blocks)
    with jax.named_scope("fg.order"):
        fold_tables = tuple(table[order] for table in tables)
    fitted = jax.lax.map(lambda fold: fit(*fold, lanes),
                         (fold_tables, lane_args))
    return jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), fitted)


def _lane_metrics(mfn, yv, blocks: tuple, scores: tuple):
    """The validation metric of every lane of a fused fit+metric program,
    a vector a depth block: ``mfn(yv[fold], scores)`` with ``fold`` the
    last of a block's per-lane arguments and ``scores[b]`` the validation
    scores of block ``b``'s lanes. ONE ``lax.map`` over the lanes of all
    the blocks and not a ``vmap``: the chip's executable of the binary
    curve metrics (a sort, cumulative sums and a running minimum over the
    validation rows, each unrolled into some hundred small operations)
    grows with the lanes it is batched over, 22 MB at 18 lanes x 16,384
    rows and 44 MB at 54, where one lane at a time is 4 MB (compiles for a
    v5e, PERF.md section 6, PR 35): what a process pays when it loads the
    program, against some hundred small operations a lane when it runs
    (``fg.metric`` of the boosted program on the chip: 0.045 s a train
    batched, 0.037 s mapped)."""
    folds = jnp.concatenate([block[-1] for block in blocks])
    lanes = jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *scores)
    with jax.named_scope("fg.metric"):
        metrics = jax.lax.map(lambda lane: mfn(yv[lane[0]], lane[1]),
                              (folds, lanes))
    ends = np.cumsum([block[-1].shape[0] for block in blocks])
    return tuple(jnp.split(metrics, ends[:-1]))


def _shard_blocks(batched, mesh, blocks: int, traced: int, shared: int,
                  out_spec):
    """``batched(blocks, *shared_args)``, a tree family's fold-grid
    program over its depth blocks (see _candidate_groups), as it is
    jitted: itself without a mesh; with one, under ``shard_map`` with every
    leaf of every block sharded over ``models`` (a block's (lanes, n) row
    masks, then its ``traced`` per-lane vectors; each block's result
    ``out_spec``) and the ``shared`` arguments replicated: a chip holds
    its share of EVERY depth, which is why each block is padded to the
    shard count on its own."""
    if mesh is None:
        return batched
    from jax.sharding import PartitionSpec as P
    lane_specs = (P("models", None),) + (P("models"),) * traced
    return shard_map(
        batched, mesh=mesh,
        in_specs=((lane_specs,) * blocks,) + (P(),) * shared,
        out_specs=(out_spec,) * blocks, check_vma=False)


@functools.lru_cache(maxsize=32)
def _forest_fg_kernel(statics: tuple, mesh=None):
    (kind, depths, num_classes, num_trees, max_features, pool_cfg,
     impurity, bootstrap, hist_mode) = statics
    from jax.sharding import PartitionSpec as P

    # named apart from the boosted programs' ``batched``: the function a
    # ``jax.jit`` wraps names the program (``jit_forest_batched``)
    def forest_batched(blocks, packed, feat_of, block_start, packed_thr,
                       binned, col_thr, narrow, wide, y, key):
        (mask, mi, mg, sr), lanes = _all_lanes(blocks)
        with jax.named_scope("fg.forest"):
            return _forest_body(
                packed, feat_of, block_start, packed_thr, binned, col_thr,
                narrow, wide, y, key, mask, mi, mg, sr, kind=kind,
                depth=depths, num_classes=num_classes,
                num_trees=num_trees, max_features=max_features,
                pool_cfg=pool_cfg, impurity=impurity, bootstrap=bootstrap,
                hist_mode=hist_mode, lanes=lanes)

    leaves_spec = (P("models", None, None, None) if kind == "cls"
                   else P("models", None, None))
    return jax.jit(_shard_blocks(
        forest_batched, mesh, len(depths), 3, 10,
        (P("models", None, None), P("models", None, None), leaves_spec)))


@functools.lru_cache(maxsize=32)
def _gbt_fg_kernel(statics: tuple, mesh=None):
    depths, num_rounds, objective, hist_mode = statics
    from jax.sharding import PartitionSpec as P

    def batched(blocks, packed, feat_of, block_start, packed_thr, y, key):
        (mask, ss, rl, ga, mcw, sub), lanes = _all_lanes(blocks)
        with jax.named_scope("fg.gbt"):
            return tuple(fitted[:4] for fitted in _gbt_body(
                packed, feat_of, block_start, packed_thr, y, key, mask,
                ss, rl, ga, mcw, sub, depth=depths, num_rounds=num_rounds,
                objective=objective, hist_mode=hist_mode, lanes=lanes))

    return jax.jit(_shard_blocks(
        batched, mesh, len(depths), 5, 6,
        (P("models", None, None), P("models", None, None),
         P("models", None, None), P("models"))))


def _gbt_scores(spec_kind, margin):
    """A boosted candidate's validation scores from its (nv,) margins:
    the HOST model's exact score transform (evaluators/device_metrics.py
    host twins), so the device metric ranks candidates identically to
    the host evaluator."""
    from ..evaluators.device_metrics import binary_from_sigmoid
    if spec_kind == "binary":
        return binary_from_sigmoid(margin)
    return margin                           # regression values


def _candidate_scores(kind, spec_kind, depth, feats, thrs, leaves, base,
                      Xv, leaf=None):
    """Validation scores for ONE fitted tree candidate, on device: each
    validation row's leaf in every tree, leaf gather + tree reduction,
    then the host model's score transform (_gbt_scores; the forests' vote
    normalization, evaluators/device_metrics.py host twins).

    The leaf has two sources (see _eval_form). "traverse", ``leaf`` None:
    the raw validation matrix ``Xv`` (nv, d) is READ here and walked down
    every finished heap (``_traverse``, in its _traverse_form);
    the form for validation rows that are not rows of the fitted table
    (``validate_prepared``, direct callers). "in_fit", ``leaf`` (T, nv)
    given (_forest_body ``val_rows``): ``Xv`` is not read; the rows were
    routed by the fit itself. The same leaf, because a row's bin is the
    count of its column's edges strictly below ``x``, so ``packed[i, f]
    <= b`` is ``X[i, f] <= packed_thr[b]`` (exact where table and heap
    share a dtype: device binning, or host binning under x64; with
    float64 HOST binning and a float32 heap a value within one float32
    ulp above an edge bins right and walks left, and the in-fit leaf is
    the one the training rows got. A NaN at a denied split walks right;
    in the fit it stays left)."""
    from ..evaluators.device_metrics import (binary_from_votes,
                                             vote_probability)
    if leaf is None:
        leaf = jax.vmap(lambda fh, th: _traverse(Xv, fh, th, depth)
                        )(feats, thrs)
    if leaves.ndim == 3:
        # a classification forest's votes, added up a tree at a time (the
        # sum the mean of the picked (T, nv, K) leaf rows makes, in its
        # order: tests/test_tree_models.py pins the bits). One pick of all
        # the rows is laid out by the chip's compiler, for a shallow tree,
        # with the K classes along a 128-wide tile: 64x the bytes at K = 2,
        # 7.7 GB for 18 lanes x 50 trees x 16,384 rows (PERF.md section 6,
        # PR 35)
        def add_tree(votes, tree):
            tree_leaves, tree_leaf = tree
            return votes + tree_leaves[tree_leaf], None
        votes, _ = jax.lax.scan(
            add_tree, jnp.zeros((leaf.shape[1], leaves.shape[2]),
                                leaves.dtype), (leaves, leaf))
        agg = votes / leaves.shape[0]                   # (nv, K) votes
    else:
        vals = leaves[jnp.arange(leaves.shape[0])[:, None], leaf]
        if kind == "gbt":
            return _gbt_scores(spec_kind, base + jnp.sum(vals, axis=0))
        agg = jnp.mean(vals, axis=0)                    # (nv,) values
    if spec_kind == "binary":
        return binary_from_votes(agg)
    if spec_kind == "multiclass":
        return vote_probability(agg)
    return agg


@functools.lru_cache(maxsize=32)
def _forest_eval_kernel(statics: tuple, spec: tuple, mesh=None,
                        in_fit: bool = False, head: bool = False):
    """Fit + validation-metric fusion of _forest_fg_kernel: candidates
    never materialize on host — the program returns one metric scalar
    per candidate, a vector a depth block (see evaluators/device_metrics.py
    for why).

    ``val`` is the stacked validation matrix (F, nv, d) in the "traverse"
    form and, with ``in_fit``, the (F, nv) int32 positions of the
    validation rows in the fitted table instead (see _eval_form): the
    program then holds no ``_traverse`` and never sees ``X_val``. With
    ``head`` (an in-fit form; see _fold_grid_head) ``val`` is the (F, n)
    ``order`` of _fold_order, whose tail those positions are, and ``mask``
    lies in that order: every fold's lanes fit on the table in the fold's
    own order (see _by_fold) and the leaves of the held-out rows are the
    tail of every tree's nodes."""
    (kind, depths, num_classes, num_trees, max_features, pool_cfg,
     impurity, bootstrap, hist_mode) = statics
    from jax.sharding import PartitionSpec as P
    from ..evaluators.device_metrics import metric_fn
    mfn = metric_fn(*spec)
    pooled = pool_cfg is not None

    def forest_batched(blocks, val, yv, packed, feat_of, block_start,
                       packed_thr, binned, col_thr, narrow, wide, y, key):
        _eval_form(in_fit)
        body = functools.partial(
            _forest_body, kind=kind, depth=depths, num_classes=num_classes,
            num_trees=num_trees, max_features=max_features,
            pool_cfg=pool_cfg, impurity=impurity, bootstrap=bootstrap,
            hist_mode=hist_mode)

        def fold_fit(tables, lane_args, lanes):
            # a pooled tree reads ``binned`` alone, any other ``packed``
            rows, fold_y = tables
            return body(
                packed if pooled else rows, feat_of, block_start,
                packed_thr, rows if pooled else binned, col_thr, narrow,
                wide, fold_y, key, *lane_args[:4], lanes=lanes,
                hist_rows=val.shape[1] - yv.shape[1])
        with jax.named_scope("fg.forest"):
            if head:
                fitted = _by_fold(fold_fit, blocks, val,
                                  (binned if pooled else packed, y))
            else:
                (mask, mi, mg, sr, fi), lanes = _all_lanes(blocks)
                fitted = body(
                    packed, feat_of, block_start, packed_thr, binned,
                    col_thr, narrow, wide, y, key, mask, mi, mg, sr,
                    lanes=lanes, val_rows=val[fi] if in_fit else None)
            with jax.named_scope("fg.metric"):
                scores = tuple(
                    jax.vmap(lambda fold, feats, thrs, leaves, *in_fit_leaf:
                             _candidate_scores(
                                 "forest", spec[0], depth, feats, thrs,
                                 leaves, 0.0,
                                 None if in_fit else val[fold],
                                 *in_fit_leaf))(block[-1], *trees)
                    for depth, block, trees in zip(depths, blocks, fitted))
            return _lane_metrics(mfn, yv, blocks, scores)

    return jax.jit(_shard_blocks(
        forest_batched, mesh, len(depths), 4, 12,
        P("models")))


def _boosted_fits(body, head: bool, blocks: tuple, val, yv, packed,
                  feat_of, block_start, packed_thr, y, key):
    """The fits of a boosted fold-grid program's lanes, a tuple a depth
    block (``body``: _gbt_body or _gbt_softmax_body with its statics
    bound): the lanes one after another over the shared table, or with
    ``head`` every fold's lanes over the table in the fold's own order
    (see _by_fold; ``val`` is then the (F, n) ``order``)."""
    if not head:
        lane_args, lanes = _all_lanes(blocks)
        return body(packed, feat_of, block_start, packed_thr, y, key,
                    *lane_args[:6], lanes=lanes)
    return _by_fold(
        lambda tables, lane_args, lanes: body(
            tables[0], feat_of, block_start, packed_thr, tables[1], key,
            *lane_args[:6], lanes=lanes,
            hist_rows=val.shape[1] - yv.shape[1]),
        blocks, val, (packed, y))


@functools.lru_cache(maxsize=32)
def _gbt_eval_kernel(statics: tuple, spec: tuple, mesh=None,
                     in_fit: bool = False, head: bool = False):
    """Fit + validation-metric fusion of _gbt_fg_kernel; ``val``,
    ``in_fit`` and ``head`` as in _forest_eval_kernel (the in-fit form
    scores the validation rows from the fit's final margins, _gbt_body:
    picked at their positions, or with ``head`` the tail of the lane's
    rows)."""
    depths, num_rounds, objective, hist_mode = statics
    from jax.sharding import PartitionSpec as P
    from ..evaluators.device_metrics import metric_fn
    mfn = metric_fn(*spec)

    def batched(blocks, val, yv, packed, feat_of, block_start, packed_thr,
                y, key):
        _eval_form(in_fit)
        with jax.named_scope("fg.gbt"):
            fitted = _boosted_fits(
                functools.partial(
                    _gbt_body, depth=depths, num_rounds=num_rounds,
                    objective=objective, hist_mode=hist_mode),
                head, blocks, val, yv, packed, feat_of, block_start,
                packed_thr, y, key)

            def lane_scores(depth, fold, feats, thrs, leaves, base,
                            margins):
                if head:
                    return _gbt_scores(spec[0], margins[-yv.shape[1]:])
                if in_fit:
                    return _gbt_scores(spec[0], margins[val[fold]])
                return _candidate_scores("gbt", spec[0], depth, feats,
                                         thrs, leaves, base, val[fold])
            with jax.named_scope("fg.metric"):
                scores = tuple(
                    jax.vmap(functools.partial(lane_scores, depth))(
                        block[-1], *trees)
                    for depth, block, trees in zip(depths, blocks, fitted))
            return _lane_metrics(mfn, yv, blocks, scores)

    return jax.jit(_shard_blocks(
        batched, mesh, len(depths), 6, 8, P("models")))


@functools.lru_cache(maxsize=32)
def _gbt_softmax_fg_kernel(statics: tuple, mesh=None):
    """Fold×grid kernel for K-class softmax boosting (the multiclass
    XGBoost path, _gbt_softmax_body) — mirrors _gbt_fg_kernel's
    candidate contract."""
    depths, num_rounds, num_classes, hist_mode = statics
    from jax.sharding import PartitionSpec as P

    def batched(blocks, packed, feat_of, block_start, packed_thr, y, key):
        (mask, ss, rl, ga, mcw, sub), lanes = _all_lanes(blocks)
        with jax.named_scope("fg.gbt_softmax"):
            return tuple(fitted[:4] for fitted in _gbt_softmax_body(
                packed, feat_of, block_start, packed_thr, y, key, mask,
                ss, rl, ga, mcw, sub, depth=depths, num_rounds=num_rounds,
                num_classes=num_classes, hist_mode=hist_mode, lanes=lanes))

    return jax.jit(_shard_blocks(
        batched, mesh, len(depths), 5, 6,
        (P("models", None, None, None), P("models", None, None, None),
         P("models", None, None, None), P("models", None))))


def _softmax_margins(feats, thrs, leaves, base, depth: int, Xv):
    """(nv, K) margins of one softmax-boosted candidate on device —
    the exact twin of GBTMulticlassClassifierModel.predict_raw."""
    R, K, H = feats.shape
    flat_f = feats.reshape(R * K, H)
    flat_t = thrs.reshape(R * K, H)
    leaf = jax.vmap(lambda fh, th: _traverse(Xv, fh, th, depth)
                    )(flat_f, flat_t)                     # (R*K, nv)
    flat_l = leaves.reshape(R * K, -1)
    vals = flat_l[jnp.arange(R * K)[:, None], leaf]
    return base + vals.reshape(R, K, -1).sum(axis=0).T    # (nv, K)


@functools.lru_cache(maxsize=32)
def _gbt_softmax_eval_kernel(statics: tuple, spec: tuple, mesh=None,
                             in_fit: bool = False, head: bool = False):
    """Fit + validation-metric fusion of _gbt_softmax_fg_kernel: the
    multiclass metric consumes softmax probabilities, matching the host
    ClassifierModel.raw_to_probability ranking exactly. ``val``,
    ``in_fit`` and ``head`` as in _gbt_eval_kernel."""
    depths, num_rounds, num_classes, hist_mode = statics
    from jax.sharding import PartitionSpec as P
    from ..evaluators.device_metrics import metric_fn
    mfn = metric_fn(*spec)

    def batched(blocks, val, yv, packed, feat_of, block_start, packed_thr,
                y, key):
        _eval_form(in_fit)
        with jax.named_scope("fg.gbt_softmax"):
            fitted = _boosted_fits(
                functools.partial(
                    _gbt_softmax_body, depth=depths, num_rounds=num_rounds,
                    num_classes=num_classes, hist_mode=hist_mode),
                head, blocks, val, yv, packed, feat_of, block_start,
                packed_thr, y, key)

            def lane_scores(depth, fold, feats, thrs, leaves, base,
                            margins):
                if head:
                    margins = margins[-yv.shape[1]:]
                elif in_fit:
                    margins = margins[val[fold]]
                else:
                    margins = _softmax_margins(feats, thrs, leaves, base,
                                               depth, val[fold])
                return jax.nn.softmax(margins, axis=1)
            with jax.named_scope("fg.metric"):
                scores = tuple(
                    jax.vmap(functools.partial(lane_scores, depth))(
                        block[-1], *trees)
                    for depth, block, trees in zip(depths, blocks, fitted))
            return _lane_metrics(mfn, yv, blocks, scores)

    return jax.jit(_shard_blocks(
        batched, mesh, len(depths), 6, 8, P("models")))


def _gbt_softmax_fold_grid(est, X, y, masks, grid, mesh, num_classes_k,
                           eval_ctx=None, edge_rows=None):
    # mirrors _gbt_fold_grid's candidate contract for the K-class
    # softmax objective — change all three drivers together
    masks = np.asarray(masks, dtype=np.float64)
    if edge_rows is None and _fold_edges_mode():
        return _fold_edge_recurse(
            _gbt_softmax_fold_grid, est, X, y, masks, grid, mesh,
            eval_ctx, num_classes_k=num_classes_k)
    grid = [dict(p) for p in (list(grid) or [{}])]
    allowed = set(_GBT_TRACED) | set(_GBT_STATIC)
    for p in grid:
        extra = set(p) - allowed
        if extra:
            raise NotImplementedError(
                f"batched softmax-GBT kernel cannot vary {sorted(extra)}")
    F, n = masks.shape
    G = len(grid)
    d = X.shape[1]
    models = [[None] * G for _ in range(F)]
    metric_mat = np.full((F, G), np.nan)
    head = _fold_grid_head(
        y, eval_ctx, masks, mesh, lambda m: _candidate_groups(
            est, grid, m, mesh, _GBT_TILED, _GBT_SKEY))
    for cand0, blocks in head.groups:
        with _trace.span("search.design"):
            design, _ = _design_args(X, cand0.max_bins,
                                     edge_rows=edge_rows)
        statics = (tuple(b.depth for b in blocks), cand0.num_rounds,
                   num_classes_k,
                   _hist_mode(head.hist_rows or n, int(design[1].shape[0])))
        _note_compile("gbt_softmax", statics,
                      tuple(b.lanes[0].shape for b in blocks))
        key = jax.random.PRNGKey(cand0.seed)
        if eval_ctx is not None:
            fetched = _run_blocks(
                _gbt_softmax_eval_kernel(statics, head.spec, mesh,
                                         *head.form),
                blocks, True, head.val, head.yv, *design[:4], head.y, key,
                hist_row_share=head.hist_row_share)
            _scatter_block_metrics(metric_mat, blocks, fetched)
            continue
        fetched = _run_blocks(_gbt_softmax_fg_kernel(statics, mesh), blocks,
                              False, *design[:4], head.y, key)
        for f, gi, cand, (fe, th, le, base) in _block_lanes(
                blocks, fetched, F):
            models[f][gi] = GBTMulticlassClassifierModel(
                fe, th, le, depth=cand.max_depth, base=base, n_features=d)
    return metric_mat if eval_ctx is not None else models


# ---------------------------------------------------------------------------
# row-sharded (data-parallel) single fits — the Rabit-allreduce role
# ---------------------------------------------------------------------------
#
# The fold x grid kernels above shard CANDIDATES (task parallelism); the
# kernels here shard ROWS of one fit over a mesh axis: each chip holds a
# contiguous block of the binned design and psums per-level histograms
# over ICI (see _grow_tree axis_name). This is the promised data-parallel
# path of the module docstring — how one model's training scales past a
# single chip's HBM/FLOPs, the role Rabit allreduce plays for the
# reference's XGBoost (core/build.gradle:27, SURVEY §2.9).

@functools.lru_cache(maxsize=32)
def _forest_sharded_kernel(statics: tuple, mesh, axis: str):
    (kind, depth, num_classes, num_trees, max_features, pool_cfg,
     impurity, bootstrap, hist_mode, row_total) = statics
    from jax.sharding import PartitionSpec as P

    def body(packed, binned, y, mask, feat_of, block_start, packed_thr,
             col_thr, narrow, wide, key, mi, mg, sr):
        return _forest_body(
            packed, feat_of, block_start, packed_thr, binned, col_thr,
            narrow, wide, y, key, mask, mi, mg, sr, kind=kind,
            depth=depth, num_classes=num_classes, num_trees=num_trees,
            max_features=max_features, pool_cfg=pool_cfg,
            impurity=impurity, bootstrap=bootstrap, hist_mode=hist_mode,
            axis_name=axis, row_total=row_total)

    # outputs replicate: every shard reaches identical split decisions
    # from the psum'd reductions
    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis), P(axis))
        + (P(),) * 10,
        out_specs=(P(), P(), P()), check_vma=False))


@functools.lru_cache(maxsize=32)
def _gbt_sharded_kernel(statics: tuple, mesh, axis: str):
    depth, num_rounds, objective, hist_mode, row_total = statics
    from jax.sharding import PartitionSpec as P

    def body(packed, y, mask, feat_of, block_start, packed_thr, key,
             ss, rl, ga, mcw, sub):
        return _gbt_body(packed, feat_of, block_start, packed_thr, y,
                         key, mask, ss, rl, ga, mcw, sub, depth=depth,
                         num_rounds=num_rounds, objective=objective,
                         hist_mode=hist_mode, axis_name=axis,
                         row_total=row_total)[:4]

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis)) + (P(),) * 9,
        out_specs=(P(), P(), P(), P()), check_vma=False))


def _gbt_fit_sharded(est, X, y, mesh, axis: str, objective: str):
    """Shared driver for the row-sharded GBT fits (see
    _forest_sharded_kernel notes on replication and padding)."""
    shards = mesh.shape[axis]
    design, _ = _design_args(X, est.max_bins)
    packed, feat_of, block_start, packed_thr = design[:4]
    (packed_p, y_p), mask = _pad_rows(
        [np.asarray(packed), np.asarray(y)], shards)
    row_total = len(mask)
    statics = (est.max_depth, est.num_rounds, objective,
               _hist_mode(row_total // shards, int(feat_of.shape[0])),
               row_total)
    fn = _gbt_sharded_kernel(statics, mesh, axis)
    feats, thrs, leaves, base = fn(
        jnp.asarray(packed_p), jnp.asarray(y_p), jnp.asarray(mask),
        feat_of, block_start, packed_thr,
        jax.random.PRNGKey(est.seed),
        jnp.asarray(float(est.step_size)),
        jnp.asarray(float(est.reg_lambda)),
        jnp.asarray(float(est.gamma)),
        jnp.asarray(float(est.min_child_weight)),
        jnp.asarray(float(est.subsample)))
    model_cls = (GBTClassifierModel if objective == "logistic"
                 else GBTRegressorModel)
    return model_cls(to_host(feats), to_host(thrs), to_host(leaves),
                     depth=est.max_depth, base=float(to_host(base)),
                     n_features=X.shape[1])


def _pad_rows(arrays, shards: int):
    """Pad each array's leading (row) axis to a multiple of ``shards``
    by repeating row 0 (padded rows carry mask 0, so they contribute
    nothing — repeating a real row keeps every bin index in range).
    Returns (padded arrays, mask (n_padded,))."""
    n = arrays[0].shape[0]
    pad = (-n) % shards
    mask = np.concatenate([np.ones(n), np.zeros(pad)])
    if not pad:
        return list(arrays), mask
    # padding changes the global bootstrap-draw vector length, so a
    # sharded fit is no longer bit-identical to the local fit (both
    # remain valid draws) — surface it instead of silently diverging
    _log.debug("_pad_rows: %d rows padded to %d for %d shards; sharded "
               "bootstrap draws will differ from an unpadded local fit",
               n, n + pad, shards)
    out = []
    for a in arrays:
        a = np.asarray(a)
        fill = np.repeat(a[:1], pad, axis=0)
        out.append(np.concatenate([a, fill], axis=0))
    return out, mask


@functools.partial(jax.jit, static_argnames=("depth", "kind"))
def _batched_tree_raw(X, feats, thrs, leaves, bases, *, depth: int,
                      kind: str):
    """(C, ...) raw outputs for C same-shape fitted tree models against
    one matrix: vmapped static-depth traversal + leaf gather + tree
    reduction, ONE program instead of C dispatch/sync round trips (the
    per-candidate path costs a full host<->device round trip per model,
    which dominates small-data selector searches on a remote TPU)."""
    def per_candidate(f, t, l, b):
        leaf = jax.vmap(lambda fh, th: _traverse(X, fh, th, depth))(f, t)
        vals = l[jnp.arange(l.shape[0])[:, None], leaf]   # (T, n[, K])
        if kind == "forest":
            return jnp.mean(vals, axis=0)                 # probs or values
        return b + jnp.sum(vals, axis=0)                  # GBT margin
    return jax.vmap(per_candidate)(feats, thrs, leaves, bases)


def batch_predict_raw(models, X) -> dict:
    """Batched validator evaluation: raw predictions for every tree-
    family model in ``models`` (list entries of other families are
    skipped), grouped by static shape so each group is one XLA call.

    Returns {index in models: raw ndarray} matching each model's own
    ``predict_raw``/``predict_values`` contract, to be fed through its
    ``prediction_from_raw``.
    """
    groups: Dict[tuple, list] = {}
    for i, m in enumerate(models):
        if isinstance(m, (TreeEnsembleClassifierModel,
                          TreeEnsembleRegressorModel)):
            key = ("forest", m.depth, m.feats.shape, m.leaves.shape)
        elif isinstance(m, (GBTClassifierModel, GBTRegressorModel)):
            key = ("gbt", m.depth, m.feats.shape, m.leaves.shape)
        else:
            continue
        groups.setdefault(key, []).append(i)
    out: dict = {}
    if not groups:          # no tree-family models: no device transfer
        return out
    X_j = jnp.asarray(np.asarray(X, dtype=np.float64))
    for (kind, depth, _, _), idxs in groups.items():
        feats = jnp.asarray(np.stack([models[i].feats for i in idxs]))
        thrs = jnp.asarray(np.stack([models[i].thrs for i in idxs]))
        leaves = jnp.asarray(np.stack([models[i].leaves for i in idxs]))
        bases = jnp.asarray(np.array(
            [getattr(models[i], "base", 0.0) for i in idxs]))
        res = np.asarray(_batched_tree_raw(
            X_j, feats, thrs, leaves, bases, depth=depth, kind=kind))
        for j, i in enumerate(idxs):
            r = res[j]
            if isinstance(models[i], GBTClassifierModel):
                r = models[i].raw_from_margin(r)
            out[i] = r
    return out


def _pad_candidates(mesh, arrays, n_rows):
    """Pad the flattened candidate axis to a multiple of the mesh's
    ``models`` shard count (padded slots fit on all-ones masks and are
    discarded). Returns (padded arrays, original count)."""
    count = arrays[0].shape[0]
    if mesh is None:
        return arrays, count
    shards = mesh.shape["models"]
    pad = (-count) % shards
    if not pad:
        return arrays, count
    out = []
    for a in arrays:
        fill = np.ones((pad, n_rows)) if a.ndim == 2 else np.ones(pad)
        out.append(np.concatenate([a, fill.astype(a.dtype)], axis=0))
    return out, count


# ---------------------------------------------------------------------------
# fitted models
# ---------------------------------------------------------------------------

class TreeEnsembleClassifierModel(ClassifierModel):
    """RF/DT classifier model: averages per-tree leaf class distributions
    (reference RandomForestClassificationModel normalized vote averaging)."""

    def __init__(self, feats, thrs, leaves, depth: int,
                 n_features: int = 0, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.feats = np.asarray(feats, dtype=np.int32)
        self.thrs = np.asarray(thrs, dtype=np.float64)
        self.leaves = np.asarray(leaves, dtype=np.float64)  # (T, L, K)
        self.depth = int(depth)
        self.n_features = int(n_features)

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        leaf_idx = np.asarray(_predict_leaves(
            jnp.asarray(X), jnp.asarray(self.feats),
            jnp.asarray(self.thrs), self.depth))              # (T, n)
        probs = self.leaves[np.arange(len(self.feats))[:, None], leaf_idx]
        return np.mean(probs, axis=0)                          # (n, K)

    def raw_arrays(self, X):
        leaf_idx = _predict_leaves(X, jnp.asarray(self.feats),
                                   jnp.asarray(self.thrs, X.dtype),
                                   self.depth)
        probs = jnp.asarray(self.leaves, X.dtype)[
            jnp.arange(len(self.feats))[:, None], leaf_idx]
        return jnp.mean(probs, axis=0)

    def raw_to_probability(self, raw: np.ndarray) -> np.ndarray:
        s = np.sum(raw, axis=1, keepdims=True)
        return raw / np.where(s > 0, s, 1.0)

    @property
    def feature_importances(self) -> np.ndarray:
        return _split_count_importances(self.feats, self.thrs, self.n_features)


class TreeEnsembleRegressorModel(RegressionModel):
    def __init__(self, feats, thrs, leaves, depth: int,
                 n_features: int = 0, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.feats = np.asarray(feats, dtype=np.int32)
        self.thrs = np.asarray(thrs, dtype=np.float64)
        self.leaves = np.asarray(leaves, dtype=np.float64)  # (T, L)
        self.depth = int(depth)
        self.n_features = int(n_features)

    def predict_values(self, X: np.ndarray) -> np.ndarray:
        leaf_idx = np.asarray(_predict_leaves(
            jnp.asarray(X), jnp.asarray(self.feats),
            jnp.asarray(self.thrs), self.depth))
        vals = self.leaves[np.arange(len(self.feats))[:, None], leaf_idx]
        return np.mean(vals, axis=0)

    def raw_arrays(self, X):
        leaf_idx = _predict_leaves(X, jnp.asarray(self.feats),
                                   jnp.asarray(self.thrs, X.dtype),
                                   self.depth)
        vals = jnp.asarray(self.leaves, X.dtype)[
            jnp.arange(len(self.feats))[:, None], leaf_idx]
        return jnp.mean(vals, axis=0)

    @property
    def feature_importances(self) -> np.ndarray:
        return _split_count_importances(self.feats, self.thrs, self.n_features)


class GBTClassifierModel(ClassifierModel):
    """Boosted binary classifier: sigmoid over summed leaf margins."""

    def __init__(self, feats, thrs, leaves, depth: int, base: float = 0.0,
                 n_features: int = 0, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.feats = np.asarray(feats, dtype=np.int32)
        self.thrs = np.asarray(thrs, dtype=np.float64)
        self.leaves = np.asarray(leaves, dtype=np.float64)
        self.depth = int(depth)
        self.base = float(base)
        self.n_features = int(n_features)

    def margins(self, X: np.ndarray) -> np.ndarray:
        leaf_idx = np.asarray(_predict_leaves(
            jnp.asarray(X), jnp.asarray(self.feats),
            jnp.asarray(self.thrs), self.depth))
        vals = self.leaves[np.arange(len(self.feats))[:, None], leaf_idx]
        return self.base + np.sum(vals, axis=0)

    def raw_from_margin(self, m: np.ndarray) -> np.ndarray:
        """Margin vector -> raw-prediction pair; the single place that
        defines this model's raw layout (batch_predict_raw reuses it so
        the batched path cannot diverge from predict_raw)."""
        return np.stack([-m, m], axis=1)

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        return self.raw_from_margin(self.margins(X))

    def raw_arrays(self, X):
        leaf_idx = _predict_leaves(X, jnp.asarray(self.feats),
                                   jnp.asarray(self.thrs, X.dtype),
                                   self.depth)
        vals = jnp.asarray(self.leaves, X.dtype)[
            jnp.arange(len(self.feats))[:, None], leaf_idx]
        m = self.base + jnp.sum(vals, axis=0)
        return jnp.stack([-m, m], axis=1)

    def raw_to_probability(self, raw: np.ndarray) -> np.ndarray:
        p = 1.0 / (1.0 + np.exp(-raw[:, 1]))
        return np.stack([1 - p, p], axis=1)

    @property
    def feature_importances(self) -> np.ndarray:
        return _split_count_importances(self.feats, self.thrs, self.n_features)


class GBTMulticlassClassifierModel(ClassifierModel):
    """K-class softmax booster model (see _gbt_softmax_body): raw
    predictions are the per-class margins; the default max-shifted
    softmax of ClassifierModel turns them into ``multi:softprob``
    probabilities (parity with xgboost4j's multiclass output,
    OpXGBoostClassifier.scala:47)."""

    def __init__(self, feats, thrs, leaves, depth: int, base,
                 n_features: int = 0, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.feats = np.asarray(feats, dtype=np.int32)     # (R, K, H)
        self.thrs = np.asarray(thrs, dtype=np.float64)
        self.leaves = np.asarray(leaves, dtype=np.float64)  # (R, K, L)
        self.depth = int(depth)
        self.base = np.asarray(base, dtype=np.float64)      # (K,)
        self.n_features = int(n_features)

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        rounds, k, heap = self.feats.shape
        flat_f = self.feats.reshape(rounds * k, heap)
        flat_t = self.thrs.reshape(rounds * k, heap)
        leaf_idx = np.asarray(_predict_leaves(
            jnp.asarray(X), jnp.asarray(flat_f), jnp.asarray(flat_t),
            self.depth))                                   # (R*K, n)
        flat_l = self.leaves.reshape(rounds * k, -1)
        vals = flat_l[np.arange(rounds * k)[:, None], leaf_idx]
        margins = vals.reshape(rounds, k, -1).sum(axis=0).T  # (n, K)
        return self.base + margins

    def raw_arrays(self, X):
        rounds, k, heap = self.feats.shape
        leaf_idx = _predict_leaves(
            X, jnp.asarray(self.feats.reshape(rounds * k, heap)),
            jnp.asarray(self.thrs.reshape(rounds * k, heap), X.dtype),
            self.depth)                                      # (R*K, n)
        flat_l = jnp.asarray(self.leaves.reshape(rounds * k, -1), X.dtype)
        vals = flat_l[jnp.arange(rounds * k)[:, None], leaf_idx]
        margins = vals.reshape(rounds, k, -1).sum(axis=0).T  # (n, K)
        return jnp.asarray(self.base, X.dtype) + margins

    @property
    def feature_importances(self) -> np.ndarray:
        rounds, k, heap = self.feats.shape
        return _split_count_importances(
            self.feats.reshape(rounds * k, heap),
            self.thrs.reshape(rounds * k, heap), self.n_features)


class GBTRegressorModel(RegressionModel):
    def __init__(self, feats, thrs, leaves, depth: int, base: float = 0.0,
                 n_features: int = 0, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.feats = np.asarray(feats, dtype=np.int32)
        self.thrs = np.asarray(thrs, dtype=np.float64)
        self.leaves = np.asarray(leaves, dtype=np.float64)
        self.depth = int(depth)
        self.base = float(base)
        self.n_features = int(n_features)

    def predict_values(self, X: np.ndarray) -> np.ndarray:
        leaf_idx = np.asarray(_predict_leaves(
            jnp.asarray(X), jnp.asarray(self.feats),
            jnp.asarray(self.thrs), self.depth))
        vals = self.leaves[np.arange(len(self.feats))[:, None], leaf_idx]
        return self.base + np.sum(vals, axis=0)

    def raw_arrays(self, X):
        leaf_idx = _predict_leaves(X, jnp.asarray(self.feats),
                                   jnp.asarray(self.thrs, X.dtype),
                                   self.depth)
        vals = jnp.asarray(self.leaves, X.dtype)[
            jnp.arange(len(self.feats))[:, None], leaf_idx]
        return self.base + jnp.sum(vals, axis=0)

    @property
    def feature_importances(self) -> np.ndarray:
        return _split_count_importances(self.feats, self.thrs, self.n_features)


def _split_count_importances(feats: np.ndarray, thrs: np.ndarray,
                             n_features: int) -> np.ndarray:
    """Normalized real-split counts per feature, aligned with the training
    feature columns (a threshold of +inf marks a dead/no-split node)."""
    real = np.isfinite(thrs)
    if feats.size == 0 or not real.any():
        return np.zeros(n_features)
    counts = np.bincount(feats[real].ravel(),
                         minlength=n_features).astype(np.float64)
    total = counts.sum()
    return counts / total if total > 0 else counts


# ---------------------------------------------------------------------------
# estimators
# ---------------------------------------------------------------------------

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


def _fold_edges_mode() -> bool:
    """Whether fold×grid searches compute bin edges from each fold's
    train rows only (TX_TREE_EDGES=fold) instead of the whole prepared
    matrix (default; standard histogram-GBM CV practice — the edges
    carry feature-distribution information only;
    examples/edges_audit.py audits it at scale)."""
    return os.environ.get("TX_TREE_EDGES", "matrix") == "fold"


def _depth_mode() -> str:
    """How the fold×grid search lays out a grid's ``max_depth`` sweep (the
    default tree grids sweep 3 / 6 / 12, a third of the lanes each):

    - "static": one program per distinct depth, the CPU path and the tests'
      reference.
    - "blocks": ONE program per tree family and static group, as many to
      compile, load and dispatch as there are families, with the lanes in
      static DEPTH BLOCKS inside it (see _candidate_groups,
      _grow_blocks): a lane's tree is what "static" grows for its depth,
      so every lane grows to the depth its grid point asks for and no
      deeper, and a level is traced once, for the lanes of every block
      that still grows there.

    Chosen by platform, the split _hist_mode uses: blocks on an
    accelerator, static on a CPU. Nothing else selects it, and a grid with
    one depth is one block under either answer. A level costs in proportion
    to its slots (2^level, capped at _DEFAULT_NODE_CAP): summed over the
    levels a depth-12 lane asks for 1,279 slot-levels, a depth-6 lane for
    63 and a depth-3 lane for 7. The measurements: PERF.md section 6
    (PR 35)."""
    return "static" if jax.default_backend() == "cpu" else "blocks"


#: (kernel kind, statics, call shape) triples seen — each is one XLA
#: compile (the jit caches on shapes too, so factory-cache hits with new
#: lane counts still compile)
_COMPILE_KEYS: set = set()


def _note_compile(kind: str, statics: tuple, shape: tuple) -> None:
    _COMPILE_KEYS.add((kind, statics, shape))


def tree_kernel_compiles() -> int:
    """Distinct compiled fold×grid tree programs so far in this process
    (the compile-count diagnostic chip_smoke.py reports)."""
    return len(_COMPILE_KEYS)


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


#: grid params the batched forest kernel traces per candidate vs the
#: statics that partition the grid into shape groups
_FOREST_TRACED = ("min_instances_per_node", "min_info_gain",
                  "subsampling_rate")
_FOREST_STATIC = ("max_depth", "num_trees", "max_bins", "impurity",
                  "feature_subset_strategy", "seed")
_GBT_TRACED = ("step_size", "reg_lambda", "gamma", "min_child_weight",
               "subsample", "eta")
_GBT_STATIC = ("max_depth", "num_rounds", "max_bins", "seed", "num_round")
#: the kernel-facing subsets ("eta"/"num_round" are facade aliases of
#: step_size/num_rounds — valid in grids, not separate lanes/keys)
_GBT_TILED = ("step_size", "reg_lambda", "gamma", "min_child_weight",
              "subsample")
_GBT_SKEY = ("max_depth", "num_rounds", "max_bins", "seed")


class _DepthBlock(NamedTuple):
    """The lanes of a fold-grid group that grow to one ``depth`` (see
    _candidate_groups): ``members`` the block's (grid index, candidate)
    pairs; ``lanes`` its per-lane arguments ``(masks, *traced_vecs)``,
    fold-major over the members and padded to the mesh's shard count;
    ``fidx`` each lane's fold index (0 on a padding lane), which the fused
    fit+metric kernels take as one more per-lane argument; ``count`` the
    lanes before padding."""
    depth: int
    members: list
    lanes: tuple
    fidx: np.ndarray
    count: int


def tree_depth_blocks(blocks) -> dict:
    """The depth blocks of one fold-grid group (see _candidate_groups),
    ``{"blocks": b, "lane_levels": m}``, beside :func:`tree_route_forms` and
    its siblings: ``b`` the distinct depths of the group, each a block of
    lanes in its program; ``m`` the sum over the group's lanes, padding left
    out, of the depth each grows to (a default grid's 18 points x 3 folds: 3
    blocks and 18 x (3 + 6 + 12) = 378, where every lane run to depth 12
    would be 648)."""
    return {"blocks": len(blocks),
            "lane_levels": sum(b.count * b.depth for b in blocks)}


def _candidate_groups(est, grid, masks, mesh, traced_fields, skey_fields):
    """The shared fold-major candidate-batching contract of the three
    fold×grid drivers (forest / binary-GBT / softmax-GBT): partition
    grid points into static shape groups, one program each, and lay a
    group's lanes out as DEPTH BLOCKS: one block a distinct ``max_depth``
    of the group, ascending, each block fold-major over its own members
    (lane ``f * len(members) + j``), with the traced hyperparameter
    vectors tiled to its lanes, and padded to the mesh shard count on its
    own, so that every chip holds its share of every depth. Under the
    "static" depth mode ``max_depth`` is part of the group's key and every
    group is one block; under "blocks" it is not (see _depth_mode).

    Yields (cand0, blocks) per group, ``blocks`` a tuple of
    :class:`_DepthBlock`, whose depths are the static the kernels take."""
    static_depth = _depth_mode() == "static"
    F, n = masks.shape
    groups: Dict[tuple, list] = {}
    for gi, p in enumerate(grid):
        cand = est.with_params(**p)
        key = tuple(None if f == "max_depth" and not static_depth
                    else getattr(cand, f, "") for f in skey_fields)
        groups.setdefault(key, []).append((gi, cand))
    for group in groups.values():
        blocks = []
        for depth in sorted({c.max_depth for _, c in group}):
            members = [m for m in group if m[1].max_depth == depth]
            gk = len(members)
            vecs = [np.tile([float(getattr(c, f)) for _, c in members], F)
                    for f in traced_fields]
            lanes, count = _pad_candidates(
                mesh, [np.repeat(masks, gk, axis=0), *vecs], n)
            fidx = np.zeros(len(lanes[0]), dtype=np.int32)
            fidx[:count] = np.repeat(np.arange(F, dtype=np.int32), gk)
            blocks.append(_DepthBlock(depth, members, tuple(lanes), fidx,
                                      count))
        yield group[0][1], tuple(blocks)


def _eval_ctx_parts(eval_ctx):
    """(X_val, y_val, spec, val_rows) of a fold-grid driver's ``eval_ctx``:
    ``(X_val (F, nv, d), y_val (F, nv), spec[, val_rows])``. ``val_rows``
    ((F, nv) positions of each fold's validation rows in the fitted table;
    absent or None: they are foreign to it) selects the fused kernels' form
    (see _eval_form): given, ``X_val`` is never read (and may be None);
    else ``X_val`` is uploaded and walked."""
    X_val, y_val, spec, *rest = eval_ctx
    val_rows = rest[0] if rest else None
    if val_rows is not None:
        val_rows = np.asarray(val_rows, dtype=np.int32)
        if val_rows.shape != np.shape(y_val):
            raise ValueError(
                f"val_rows {val_rows.shape} must name one row of the "
                f"fitted table per validation label {np.shape(y_val)}")
    return X_val, y_val, spec, val_rows


class _FoldGridHead(NamedTuple):
    """What :func:`_fold_grid_head` hands a fold-grid driver: the labels
    ``y`` on the device; with an ``eval_ctx`` the fused kernels' ``val``
    (the validation matrix, the validation rows' positions, or every
    fold's row order: see ``form``), the validation labels ``yv`` and the
    metric ``spec``, else None; ``in_fit`` (see _eval_form);
    ``hist_rows``, the leading rows of a lane that its level histograms
    contract where the lanes see the table in their fold's own order (see
    _fold_order), else None; ``rows``, the table's; and the candidate
    ``groups``."""
    y: jnp.ndarray
    val: Optional[jnp.ndarray]
    yv: Optional[jnp.ndarray]
    spec: Optional[tuple]
    in_fit: bool
    hist_rows: Optional[int]
    rows: int
    groups: Iterable

    @property
    def form(self) -> tuple:
        """The fused kernels' ``(in_fit, head)``."""
        return self.in_fit, self.hist_rows is not None

    @property
    def hist_row_share(self) -> float:
        """The rows a lane's histograms contract over the rows it holds."""
        return (self.hist_rows or self.rows) / self.rows


def _fold_grid_head(y, eval_ctx, masks, mesh, groups_of) -> _FoldGridHead:
    """What a fold-grid driver does on the host before its first design, under
    the span ``search.head``: the labels and (with ``eval_ctx``) the stacked
    validation folds go to the device, and the first candidate group is laid
    out (``groups_of(masks)``: _candidate_groups over the driver's fields;
    the later groups stay lazy: a group's masks are lanes x rows).
    Of the validation folds the labels always go; the matrix ``X_val`` only
    in the "traverse" form (see _eval_ctx_parts): with ``val_rows`` the
    (F, nv) int32 row indices go in its place and the matrix is not read.

    With ``val_rows``, no mesh (under one a chip's lanes mix folds, see
    _shard_blocks) and a histogram mode of the ``matmul`` family (a question
    of the backend alone, see _hist_mode) the form is ``head``: every lane
    sees the table in its fold's own order where the folds allow it (see
    _fold_order). The (F, n) order goes to the device instead of the (F, nv)
    positions, which are its tail, and the groups' masks are laid out in it,
    here on the host."""
    with _trace.span("search.head"):
        y_j = jnp.asarray(y)
        val_j = yv_j = spec = hist_rows = None
        in_fit = False
        n = masks.shape[1]
        if eval_ctx is not None:
            X_val, y_val, spec, val_rows = _eval_ctx_parts(eval_ctx)
            in_fit = val_rows is not None
            order = None
            if in_fit and mesh is None and _hist_mode(n, 1) != "scatter":
                order = _fold_order(masks, val_rows)
            if order is not None:
                hist_rows = n - val_rows.shape[1]
                masks = np.take_along_axis(masks, order, axis=1)
                val_j = jnp.asarray(order)
            else:
                val_j = (jnp.asarray(val_rows) if in_fit else
                         jnp.asarray(np.asarray(X_val, dtype=np.float64)))
            yv_j = jnp.asarray(np.asarray(y_val, dtype=np.float64))
        groups = groups_of(masks)
        first = next(groups, None)
        if first is not None:
            groups = itertools.chain([first], groups)
        return _FoldGridHead(y_j, val_j, yv_j, spec, in_fit, hist_rows, n,
                             groups)


def _run_blocks(fn, blocks, fused: bool, *shared,
                hist_row_share: float = 1.0) -> list:
    """One call of a fold-grid program (see _shard_blocks) under its
    ``search.fetch`` span, and its result on the host, an entry a depth
    block (a metric vector, or a tuple of tree arrays), each block's
    padding lanes cut. A ``fused`` fit+metric kernel takes each lane's
    fold index after the block's other per-lane arguments.
    ``hist_row_share``: the span's attribute (see _FoldGridHead)."""
    counts = tree_depth_blocks(blocks)
    with _fetch_span(depth_blocks=counts["blocks"],
                     depth_lane_levels=counts["lane_levels"],
                     hist_row_share=hist_row_share):
        lanes = tuple(tuple(jnp.asarray(a) for a in
                            b.lanes + ((b.fidx,) if fused else ()))
                      for b in blocks)
        # the first call traces and lowers: a quarter of a million small
        # Python calls, which must not sit at the end of a frame-stack chunk
        out = with_frame_room(lambda: fn(lanes, *shared))
        return [jax.tree_util.tree_map(lambda a: to_host(a)[:b.count], res)
                for b, res in zip(blocks, out)]


def _scatter_block_metrics(metric_mat, blocks, fetched) -> None:
    """Write a group's metric vectors, one a depth block (fold-major over
    the block's members), back into the (F, G) matrix."""
    F = metric_mat.shape[0]
    for b, mm in zip(blocks, fetched):
        metric_mat[:, [gi for gi, _ in b.members]] = mm.reshape(
            F, len(b.members))


def _block_lanes(blocks, fetched, F: int):
    """``(f, gi, candidate, the lane's arrays)`` of every fitted lane of
    a model-materializing fold-grid call: a block's heaps are at its
    lanes' own depth."""
    for b, arrays in zip(blocks, fetched):
        for f in range(F):
            for j, (gi, cand) in enumerate(b.members):
                c = f * len(b.members) + j
                yield f, gi, cand, tuple(a[c] for a in arrays)


def _fold_edge_recurse(fold_grid_fn, est, X, y, masks, grid, mesh,
                       eval_ctx, **kw):
    """TX_TREE_EDGES=fold driver: one recursive single-fold call per
    fold, each binning with edges from THAT fold's train rows only.
    Returns the same (F, G) matrix / per-fold model lists the fold-major
    call would. Costs one extra compile per static group (single-fold
    candidate shape) but removes the only place validation rows could
    influence training (quantile edges)."""
    F = masks.shape[0]
    outs = []
    for f in range(F):
        rows = np.nonzero(masks[f] > 0)[0]
        sub_eval = None
        if eval_ctx is not None:
            # one fold of the SAME table a call: its val_rows stay valid
            X_val, y_val, spec, val_rows = _eval_ctx_parts(eval_ctx)
            sub_eval = (X_val if X_val is None else X_val[f:f + 1],
                        y_val[f:f + 1], spec,
                        val_rows if val_rows is None
                        else val_rows[f:f + 1])
        outs.append(fold_grid_fn(est, X, y, masks[f:f + 1], grid, mesh,
                                 eval_ctx=sub_eval, edge_rows=rows, **kw))
    if eval_ctx is not None:
        return np.concatenate(outs, axis=0)
    return [o[0] for o in outs]


def _forest_fold_grid(est, X, y, masks, grid, mesh, classification: bool,
                      eval_ctx=None, edge_rows=None):
    """All (fold, grid point) forest candidates in vmapped programs (one
    per static shape group), optionally sharded over a mesh ``models``
    axis — see the kernel docstrings for the bin-edge deviation.

    With ``eval_ctx`` (see _eval_ctx_parts: ``(X_val (F,nv,d), y_val
    (F,nv), spec[, val_rows])``) the fused fit+metric kernels run instead
    and the return value is the (F, G) validation-metric matrix — fitted
    trees never reach the host. ``val_rows`` given: only those row indices are
    read, the validation scores come from the leaves the fit put the rows
    in; None: ``X_val`` is uploaded and walked down the finished trees."""
    masks = np.asarray(masks, dtype=np.float64)
    if edge_rows is None and _fold_edges_mode():
        return _fold_edge_recurse(
            _forest_fold_grid, est, X, y, masks, grid, mesh, eval_ctx,
            classification=classification)
    grid = [dict(p) for p in (list(grid) or [{}])]
    allowed = set(_FOREST_TRACED) | set(_FOREST_STATIC)
    for p in grid:
        extra = set(p) - allowed
        if extra:
            raise NotImplementedError(
                f"batched tree kernel cannot vary {sorted(extra)}")
    F, n = masks.shape
    G = len(grid)
    d = X.shape[1]
    k = num_classes(y)
    models = [[None] * G for _ in range(F)]
    metric_mat = np.full((F, G), np.nan)
    head = _fold_grid_head(
        y, eval_ctx, masks, mesh, lambda m: _candidate_groups(
            est, grid, m, mesh, _FOREST_TRACED, _FOREST_STATIC))
    model_cls = (TreeEnsembleClassifierModel if classification
                 else TreeEnsembleRegressorModel)
    for cand0, blocks in head.groups:
        with _trace.span("search.design"):
            design, widths = _design_args(X, cand0.max_bins,
                                          edge_rows=edge_rows)
        mf = _resolve_max_features(cand0.feature_subset_strategy, d,
                                   classification) \
            if cand0.bootstrap else None
        (narrow, wide), pool_cfg, mf = _pool_plan(widths, mf)
        statics = ("cls" if classification else "reg",
                   tuple(b.depth for b in blocks),
                   k if classification else 0, cand0.num_trees, mf,
                   pool_cfg, getattr(cand0, "impurity", ""),
                   cand0.bootstrap,
                   _hist_mode(head.hist_rows or n, int(design[1].shape[0])))
        _note_compile("forest", statics,
                      tuple(b.lanes[0].shape for b in blocks))
        key = jax.random.PRNGKey(cand0.seed)
        if eval_ctx is not None:
            fetched = _run_blocks(
                _forest_eval_kernel(statics, head.spec, mesh, *head.form),
                blocks, True, head.val, head.yv, *design, narrow, wide,
                head.y, key, hist_row_share=head.hist_row_share)
            _scatter_block_metrics(metric_mat, blocks, fetched)
            continue
        fetched = _run_blocks(_forest_fg_kernel(statics, mesh), blocks,
                              False, *design, narrow, wide, head.y, key)
        for f, gi, cand, (fe, th, le) in _block_lanes(blocks, fetched, F):
            models[f][gi] = model_cls(fe, th, le, depth=cand.max_depth,
                                      n_features=d)
    return metric_mat if eval_ctx is not None else models


def _gbt_fold_grid(est, X, y, masks, grid, mesh, objective: str,
                   eval_ctx=None, edge_rows=None):
    # mirrors _forest_fold_grid's candidate contract (fold-major
    # flattening, static-group partitioning, padding, eval_ctx fusion,
    # TX_TREE_EDGES=fold recursion) — change both together
    masks = np.asarray(masks, dtype=np.float64)
    if edge_rows is None and _fold_edges_mode():
        return _fold_edge_recurse(
            _gbt_fold_grid, est, X, y, masks, grid, mesh, eval_ctx,
            objective=objective)
    grid = [dict(p) for p in (list(grid) or [{}])]
    allowed = set(_GBT_TRACED) | set(_GBT_STATIC)
    for p in grid:
        extra = set(p) - allowed
        if extra:
            raise NotImplementedError(
                f"batched GBT kernel cannot vary {sorted(extra)}")
    F, n = masks.shape
    G = len(grid)
    d = X.shape[1]
    models = [[None] * G for _ in range(F)]
    metric_mat = np.full((F, G), np.nan)
    model_cls = (GBTClassifierModel if objective == "logistic"
                 else GBTRegressorModel)
    head = _fold_grid_head(
        y, eval_ctx, masks, mesh, lambda m: _candidate_groups(
            est, grid, m, mesh, _GBT_TILED, _GBT_SKEY))
    for cand0, blocks in head.groups:
        with _trace.span("search.design"):
            design, _ = _design_args(X, cand0.max_bins,
                                     edge_rows=edge_rows)
        statics = (tuple(b.depth for b in blocks), cand0.num_rounds,
                   objective,
                   _hist_mode(head.hist_rows or n, int(design[1].shape[0])))
        _note_compile("gbt", statics,
                      tuple(b.lanes[0].shape for b in blocks))
        key = jax.random.PRNGKey(cand0.seed)
        if eval_ctx is not None:
            fetched = _run_blocks(
                _gbt_eval_kernel(statics, head.spec, mesh, *head.form),
                blocks, True, head.val, head.yv, *design[:4], head.y, key,
                hist_row_share=head.hist_row_share)
            _scatter_block_metrics(metric_mat, blocks, fetched)
            continue
        fetched = _run_blocks(_gbt_fg_kernel(statics, mesh), blocks, False,
                              *design[:4], head.y, key)
        for f, gi, cand, (fe, th, le, base) in _block_lanes(
                blocks, fetched, F):
            models[f][gi] = model_cls(fe, th, le, depth=cand.max_depth,
                                      base=float(base), n_features=d)
    return metric_mat if eval_ctx is not None else models


class _ForestClassifierBase(Predictor):
    num_trees = 1
    bootstrap = False

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """Validator fast path: all (fold, grid) candidates in one
        vmapped program per static group, mesh-shardable over the
        candidate axis (reference OpValidator.scala:270 parallelism)."""
        return _forest_fold_grid(self, X, y, masks, grid, mesh, True)

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None,
                              val_rows=None):
        """Device-resident search: fused fit + validation metric, (F, G)
        matrix out (see _forest_fold_grid eval_ctx). ``cand_idx``
        (racing rungs) restricts to a candidate subset — traced
        hyperparameters stay dynamic lanes; static groups a rung prunes
        entirely simply stop being compiled.

        ``val_rows`` ((F, nv) int, optional): where ``X_val[f]`` is
        ``X[val_rows[f]]``, rows of the fitted table itself, say so here
        and the validation matrix is NOT read: each candidate is scored
        from the leaves its fit already routed those rows to (the same
        leaves, no second walk; see trees._eval_form). Without it
        ``X_val`` is read, uploaded and walked down the finished trees —
        the form for validation rows foreign to ``X``."""
        if spec[0] == "binary" and num_classes(y) != 2:
            raise NotImplementedError(
                "binary device eval needs binary labels")
        if spec[0] not in ("binary", "multiclass"):
            raise NotImplementedError(
                "forest-classifier device eval needs a classification "
                "metric")
        return _forest_fold_grid(
            self, X, y, masks, subset_grid(grid, cand_idx), mesh, True,
            eval_ctx=(X_val, y_val, spec, val_rows))

    def fit_arrays_sharded(self, X, y, mesh, axis: str = "data"
                           ) -> TreeEnsembleClassifierModel:
        """Row-sharded (data-parallel) fit: each ``mesh[axis]`` shard
        holds a contiguous row block; per-level histograms psum over
        ICI (_grow_tree axis_name — the Rabit-allreduce role, SURVEY
        §2.9). Identical trees to fit_arrays when the row count divides
        the shard count (same bootstrap draws via _row_draw)."""
        k = num_classes(y)
        d = X.shape[1]
        shards = mesh.shape[axis]
        mf = _resolve_max_features(self.feature_subset_strategy, d, True) \
            if self.bootstrap else None
        design, widths = _design_args(X, self.max_bins)
        (narrow, wide), pool_cfg, mf = _pool_plan(widths, mf)
        packed, feat_of, block_start, packed_thr, binned, col_thr = design
        (packed_p, binned_p, y_p), mask = _pad_rows(
            [np.asarray(packed), np.asarray(binned), np.asarray(y)],
            shards)
        row_total = len(mask)
        statics = ("cls", self.max_depth, k, self.num_trees, mf,
                   pool_cfg, self.impurity, self.bootstrap,
                   _hist_mode(row_total // shards, int(feat_of.shape[0])),
                   row_total)
        fn = _forest_sharded_kernel(statics, mesh, axis)
        feats, thrs, leaves = fn(
            jnp.asarray(packed_p), jnp.asarray(binned_p),
            jnp.asarray(y_p), jnp.asarray(mask), feat_of, block_start,
            packed_thr, col_thr, narrow, wide,
            jax.random.PRNGKey(self.seed),
            jnp.asarray(float(self.min_instances_per_node)),
            jnp.asarray(float(self.min_info_gain)),
            jnp.asarray(float(self.subsampling_rate)))
        return TreeEnsembleClassifierModel(
            to_host(feats), to_host(thrs), to_host(leaves),
            depth=self.max_depth, n_features=d)

    def fit_arrays(self, X: np.ndarray, y: np.ndarray
                   ) -> TreeEnsembleClassifierModel:
        k = num_classes(y)
        d = X.shape[1]
        mf = _resolve_max_features(self.feature_subset_strategy, d, True) \
            if self.bootstrap else None
        design, widths = _design_args(X, self.max_bins)
        (narrow, wide), pool_cfg, mf = _pool_plan(widths, mf)
        feats, thrs, leaves = _fit_forest_classifier(
            *design, narrow, wide, jnp.asarray(y),
            jax.random.PRNGKey(self.seed), depth=self.max_depth,
            num_classes=k, num_trees=self.num_trees, max_features=mf,
            pool_cfg=pool_cfg, impurity=self.impurity,
            min_instances=float(self.min_instances_per_node),
            min_info_gain=self.min_info_gain,
            subsample=self.subsampling_rate, bootstrap=self.bootstrap,
            hist_mode=_hist_mode(X.shape[0], int(design[1].shape[0])))
        return TreeEnsembleClassifierModel(feats, thrs, leaves,
                                           depth=self.max_depth,
                                           n_features=d)


class _ForestRegressorBase(Predictor):
    num_trees = 1
    bootstrap = False

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """See _ForestClassifierBase.fit_fold_grid_arrays."""
        return _forest_fold_grid(self, X, y, masks, grid, mesh, False)

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None,
                              val_rows=None):
        """See _ForestClassifierBase.eval_fold_grid_arrays (``val_rows``
        given: ``X_val`` is not read, only those row indices are)."""
        if spec[0] != "regression":
            raise NotImplementedError(
                "forest-regressor device eval needs a regression metric")
        return _forest_fold_grid(
            self, X, y, masks, subset_grid(grid, cand_idx), mesh, False,
            eval_ctx=(X_val, y_val, spec, val_rows))

    def fit_arrays_sharded(self, X, y, mesh, axis: str = "data"
                           ) -> TreeEnsembleRegressorModel:
        """See _ForestClassifierBase.fit_arrays_sharded."""
        d = X.shape[1]
        shards = mesh.shape[axis]
        mf = _resolve_max_features(self.feature_subset_strategy, d,
                                   False) if self.bootstrap else None
        design, widths = _design_args(X, self.max_bins)
        (narrow, wide), pool_cfg, mf = _pool_plan(widths, mf)
        packed, feat_of, block_start, packed_thr, binned, col_thr = design
        (packed_p, binned_p, y_p), mask = _pad_rows(
            [np.asarray(packed), np.asarray(binned), np.asarray(y)],
            shards)
        row_total = len(mask)
        statics = ("reg", self.max_depth, 0, self.num_trees, mf,
                   pool_cfg, "", self.bootstrap,
                   _hist_mode(row_total // shards, int(feat_of.shape[0])),
                   row_total)
        fn = _forest_sharded_kernel(statics, mesh, axis)
        feats, thrs, leaves = fn(
            jnp.asarray(packed_p), jnp.asarray(binned_p),
            jnp.asarray(y_p), jnp.asarray(mask), feat_of, block_start,
            packed_thr, col_thr, narrow, wide,
            jax.random.PRNGKey(self.seed),
            jnp.asarray(float(self.min_instances_per_node)),
            jnp.asarray(float(self.min_info_gain)),
            jnp.asarray(float(self.subsampling_rate)))
        return TreeEnsembleRegressorModel(
            to_host(feats), to_host(thrs), to_host(leaves),
            depth=self.max_depth, n_features=d)

    def fit_arrays(self, X: np.ndarray, y: np.ndarray
                   ) -> TreeEnsembleRegressorModel:
        d = X.shape[1]
        mf = _resolve_max_features(self.feature_subset_strategy, d, False) \
            if self.bootstrap else None
        design, widths = _design_args(X, self.max_bins)
        (narrow, wide), pool_cfg, mf = _pool_plan(widths, mf)
        feats, thrs, leaves = _fit_forest_regressor(
            *design, narrow, wide, jnp.asarray(y),
            jax.random.PRNGKey(self.seed), depth=self.max_depth,
            num_trees=self.num_trees, max_features=mf,
            pool_cfg=pool_cfg,
            min_instances=float(self.min_instances_per_node),
            min_info_gain=self.min_info_gain,
            subsample=self.subsampling_rate, bootstrap=self.bootstrap,
            hist_mode=_hist_mode(X.shape[0], int(design[1].shape[0])))
        return TreeEnsembleRegressorModel(feats, thrs, leaves,
                                          depth=self.max_depth,
                                          n_features=d)


class DecisionTreeClassifier(_ForestClassifierBase):
    """Single CART tree, gini/entropy impurity
    (reference OpDecisionTreeClassifier.scala)."""

    def __init__(self, max_depth: int = 5, max_bins: int = 32,
                 min_instances_per_node: int = 1, min_info_gain: float = 0.0,
                 impurity: str = "gini", seed: int = 42,
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.min_instances_per_node = min_instances_per_node
        self.min_info_gain = min_info_gain
        self.impurity = impurity
        self.seed = seed
        self.num_trees = 1
        self.bootstrap = False
        self.subsampling_rate = 1.0
        self.feature_subset_strategy = "all"


class DecisionTreeRegressor(_ForestRegressorBase):
    """(reference OpDecisionTreeRegressor.scala)"""

    def __init__(self, max_depth: int = 5, max_bins: int = 32,
                 min_instances_per_node: int = 1, min_info_gain: float = 0.0,
                 seed: int = 42, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.min_instances_per_node = min_instances_per_node
        self.min_info_gain = min_info_gain
        self.seed = seed
        self.num_trees = 1
        self.bootstrap = False
        self.subsampling_rate = 1.0
        self.feature_subset_strategy = "all"


class RandomForestClassifier(_ForestClassifierBase):
    """Bagged gini trees with per-node feature subsampling
    (reference OpRandomForestClassifier.scala). Bootstrap resampling uses
    Poisson(subsamplingRate) row weights — the same approximation Spark
    MLlib's BaggedPoint uses for sampling with replacement."""

    def __init__(self, num_trees: int = 20, max_depth: int = 5,
                 max_bins: int = 32, min_instances_per_node: int = 1,
                 min_info_gain: float = 0.0, subsampling_rate: float = 1.0,
                 feature_subset_strategy: str = "auto", impurity: str = "gini",
                 seed: int = 42, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.num_trees = num_trees
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.min_instances_per_node = min_instances_per_node
        self.min_info_gain = min_info_gain
        self.subsampling_rate = subsampling_rate
        self.feature_subset_strategy = feature_subset_strategy
        self.impurity = impurity
        self.seed = seed
        self.bootstrap = True


class RandomForestRegressor(_ForestRegressorBase):
    """(reference OpRandomForestRegressor.scala)"""

    def __init__(self, num_trees: int = 20, max_depth: int = 5,
                 max_bins: int = 32, min_instances_per_node: int = 1,
                 min_info_gain: float = 0.0, subsampling_rate: float = 1.0,
                 feature_subset_strategy: str = "auto", seed: int = 42,
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.num_trees = num_trees
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.min_instances_per_node = min_instances_per_node
        self.min_info_gain = min_info_gain
        self.subsampling_rate = subsampling_rate
        self.feature_subset_strategy = feature_subset_strategy
        self.seed = seed
        self.bootstrap = True


class GBTClassifier(Predictor):
    """Gradient-boosted binary classifier with second-order (XGBoost-style)
    split gains on the logistic objective (reference OpGBTClassifier.scala;
    MLlib GBT uses first-order residual fitting — the second-order variant
    strictly dominates and is the XGBoost parity path, SURVEY §2.9)."""

    def __init__(self, num_rounds: int = 20, max_depth: int = 5,
                 step_size: float = 0.1, max_bins: int = 32,
                 reg_lambda: float = 1.0, gamma: float = 0.0,
                 min_child_weight: float = 1.0, subsample: float = 1.0,
                 seed: int = 42, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.num_rounds = num_rounds
        self.max_depth = max_depth
        self.step_size = step_size
        self.max_bins = max_bins
        self.reg_lambda = reg_lambda
        self.gamma = gamma
        self.min_child_weight = min_child_weight
        self.subsample = subsample
        self.seed = seed

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """See _ForestClassifierBase.fit_fold_grid_arrays."""
        bad = np.setdiff1d(np.unique(y), [0.0, 1.0])
        if bad.size:
            # NotImplementedError (not ValueError): the validator then
            # takes the sequential fallback, where the per-fold handler
            # drops this family out of the race instead of killing the
            # whole search
            raise NotImplementedError(
                "batched GBT kernel requires binary labels {0, 1}")
        return _gbt_fold_grid(self, X, y, masks, grid, mesh, "logistic")

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None,
                              val_rows=None):
        """Device-resident search: fused fit + validation metric, (F, G)
        matrix out (see _gbt_fold_grid eval_ctx). ``val_rows`` as in
        _ForestClassifierBase.eval_fold_grid_arrays: given, ``X_val`` is
        not read and the held-out rows are scored from the margins the
        fit already carries for them; without it ``X_val`` is walked."""
        if spec[0] != "binary":
            raise NotImplementedError(
                "GBT-classifier device eval is binary-only")
        bad = np.setdiff1d(np.unique(y), [0.0, 1.0])
        if bad.size:
            raise NotImplementedError(
                "batched GBT kernel requires binary labels {0, 1}")
        return _gbt_fold_grid(
            self, X, y, masks, subset_grid(grid, cand_idx), mesh,
            "logistic", eval_ctx=(X_val, y_val, spec, val_rows))

    def fit_arrays_sharded(self, X, y, mesh, axis: str = "data"
                           ) -> GBTClassifierModel:
        """Row-sharded (data-parallel) boosting — see
        _ForestClassifierBase.fit_arrays_sharded."""
        bad = np.setdiff1d(np.unique(y), [0.0, 1.0])
        if bad.size:
            raise ValueError(
                "GBTClassifier supports binary labels {0, 1} only")
        return _gbt_fit_sharded(self, X, y, mesh, axis, "logistic")

    def fit_arrays(self, X: np.ndarray, y: np.ndarray) -> GBTClassifierModel:
        bad = np.setdiff1d(np.unique(y), [0.0, 1.0])
        if bad.size:
            raise ValueError(
                f"GBTClassifier supports binary labels {{0, 1}} only "
                f"(as MLlib GBTClassifier does); got extra labels "
                f"{bad.tolist()} — use RandomForestClassifier or "
                f"LogisticRegression for multiclass")
        design, _ = _design_args(X, self.max_bins)
        feats, thrs, leaves, base = _fit_gbt(
            *design[:4], jnp.asarray(y),
            jax.random.PRNGKey(self.seed), depth=self.max_depth,
            num_rounds=self.num_rounds,
            step_size=self.step_size, reg_lambda=self.reg_lambda,
            gamma=self.gamma, min_child_weight=self.min_child_weight,
            subsample=self.subsample, objective="logistic",
            hist_mode=_hist_mode(X.shape[0], int(design[1].shape[0])))
        return GBTClassifierModel(feats, thrs, leaves, depth=self.max_depth,
                                  base=float(base), n_features=X.shape[1])


class GBTRegressor(Predictor):
    """Gradient-boosted regressor, squared loss
    (reference OpGBTRegressor.scala)."""

    def __init__(self, num_rounds: int = 20, max_depth: int = 5,
                 step_size: float = 0.1, max_bins: int = 32,
                 reg_lambda: float = 1.0, gamma: float = 0.0,
                 min_child_weight: float = 1.0, subsample: float = 1.0,
                 seed: int = 42, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.num_rounds = num_rounds
        self.max_depth = max_depth
        self.step_size = step_size
        self.max_bins = max_bins
        self.reg_lambda = reg_lambda
        self.gamma = gamma
        self.min_child_weight = min_child_weight
        self.subsample = subsample
        self.seed = seed

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """See _ForestClassifierBase.fit_fold_grid_arrays."""
        return _gbt_fold_grid(self, X, y, masks, grid, mesh, "squared")

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None,
                              val_rows=None):
        """See GBTClassifier.eval_fold_grid_arrays (``val_rows`` given:
        ``X_val`` is not read, only those row indices are)."""
        if spec[0] != "regression":
            raise NotImplementedError(
                "GBT-regressor device eval needs a regression metric")
        return _gbt_fold_grid(
            self, X, y, masks, subset_grid(grid, cand_idx), mesh,
            "squared", eval_ctx=(X_val, y_val, spec, val_rows))

    def fit_arrays_sharded(self, X, y, mesh, axis: str = "data"
                           ) -> GBTRegressorModel:
        """See GBTClassifier.fit_arrays_sharded."""
        return _gbt_fit_sharded(self, X, y, mesh, axis, "squared")

    def fit_arrays(self, X: np.ndarray, y: np.ndarray) -> GBTRegressorModel:
        design, _ = _design_args(X, self.max_bins)
        feats, thrs, leaves, base = _fit_gbt(
            *design[:4], jnp.asarray(y),
            jax.random.PRNGKey(self.seed), depth=self.max_depth,
            num_rounds=self.num_rounds,
            step_size=self.step_size, reg_lambda=self.reg_lambda,
            gamma=self.gamma, min_child_weight=self.min_child_weight,
            subsample=self.subsample, objective="squared",
            hist_mode=_hist_mode(X.shape[0], int(design[1].shape[0])))
        return GBTRegressorModel(feats, thrs, leaves, depth=self.max_depth,
                                 base=float(base), n_features=X.shape[1])


class XGBoostClassifier(GBTClassifier):
    """XGBoost-parameter-named facade over the same histogram booster
    (reference OpXGBoostClassifier.scala:47 — the reference's only native
    C++ component, xgboost4j + Rabit; here the booster IS the second-order
    histogram GBT above, with multi-chip reduction via psum, SURVEY §2.9).
    Unlike GBTClassifier (MLlib parity: binary-only), this facade also
    fits K-class problems via the softmax objective — the
    ``multi:softprob`` path xgboost4j takes."""

    def __init__(self, eta: float = 0.3, max_depth: int = 6,
                 num_round: int = 100, reg_lambda: float = 1.0,
                 gamma: float = 0.0, min_child_weight: float = 1.0,
                 subsample: float = 1.0, max_bins: int = 256,
                 seed: int = 42, uid: Optional[str] = None):
        GBTClassifier.__init__(
            self, num_rounds=num_round, max_depth=max_depth, step_size=eta,
            max_bins=max_bins, reg_lambda=reg_lambda, gamma=gamma,
            min_child_weight=min_child_weight, subsample=subsample,
            seed=seed, uid=uid)
        self.eta = eta
        self.num_round = num_round

    @staticmethod
    def _check_multiclass_labels(y, k: int) -> None:
        bad = np.setdiff1d(np.unique(y), np.arange(k, dtype=np.float64))
        if bad.size:
            raise NotImplementedError(
                f"softmax booster needs integer class labels 0..{k - 1};"
                f" got {bad.tolist()}")

    def fit_fold_grid_arrays(self, X, y, masks, grid, mesh=None):
        """Multiclass grids run the fused softmax fold×grid kernel
        (binary falls through to the GBT driver)."""
        k = num_classes(y)
        if k <= 2:
            return GBTClassifier.fit_fold_grid_arrays(
                self, X, y, masks, grid, mesh=mesh)
        self._check_multiclass_labels(y, k)
        check_fold_classes(y, masks)
        return _gbt_softmax_fold_grid(self, X, y, masks, grid, mesh, k)

    def eval_fold_grid_arrays(self, X, y, masks, grid, X_val, y_val,
                              spec, mesh=None, cand_idx=None,
                              val_rows=None):
        """Device-resident multiclass search: fused softmax fit +
        metric, (F, G) matrix out (_gbt_softmax_eval_kernel).
        ``val_rows`` as in GBTClassifier.eval_fold_grid_arrays: given,
        ``X_val`` is not read, only those row indices are."""
        k = num_classes(y)
        if k <= 2:
            return GBTClassifier.eval_fold_grid_arrays(
                self, X, y, masks, grid, X_val, y_val, spec, mesh=mesh,
                cand_idx=cand_idx, val_rows=val_rows)
        if spec[0] != "multiclass":
            raise NotImplementedError(
                "softmax-GBT device eval needs a multiclass metric")
        self._check_multiclass_labels(y, k)
        check_fold_classes(y, masks)
        return _gbt_softmax_fold_grid(
            self, X, y, masks, subset_grid(grid, cand_idx), mesh, k,
            eval_ctx=(X_val, y_val, spec, val_rows))

    def fit_arrays(self, X: np.ndarray, y: np.ndarray):
        k = num_classes(y)
        if k <= 2:
            return GBTClassifier.fit_arrays(self, X, y)
        bad = np.setdiff1d(np.unique(y), np.arange(k, dtype=np.float64))
        if bad.size:
            raise ValueError(
                f"XGBoostClassifier needs integer class labels 0..{k - 1};"
                f" got {bad.tolist()}")
        design, _ = _design_args(X, self.max_bins)
        feats, thrs, leaves, base = _fit_gbt_softmax(
            *design[:4], jnp.asarray(y), jax.random.PRNGKey(self.seed),
            depth=self.max_depth, num_rounds=self.num_rounds,
            num_classes=k, step_size=self.step_size,
            reg_lambda=self.reg_lambda, gamma=self.gamma,
            min_child_weight=self.min_child_weight,
            subsample=self.subsample,
            hist_mode=_hist_mode(X.shape[0], int(design[1].shape[0])))
        return GBTMulticlassClassifierModel(
            to_host(feats), to_host(thrs), to_host(leaves),
            depth=self.max_depth, base=to_host(base),
            n_features=X.shape[1])


class XGBoostRegressor(GBTRegressor):
    """(reference OpXGBoostRegressor.scala)"""

    def __init__(self, eta: float = 0.3, max_depth: int = 6,
                 num_round: int = 100, reg_lambda: float = 1.0,
                 gamma: float = 0.0, min_child_weight: float = 1.0,
                 subsample: float = 1.0, max_bins: int = 256,
                 seed: int = 42, uid: Optional[str] = None):
        GBTRegressor.__init__(
            self, num_rounds=num_round, max_depth=max_depth, step_size=eta,
            max_bins=max_bins, reg_lambda=reg_lambda, gamma=gamma,
            min_child_weight=min_child_weight, subsample=subsample,
            seed=seed, uid=uid)
        self.eta = eta
        self.num_round = num_round
