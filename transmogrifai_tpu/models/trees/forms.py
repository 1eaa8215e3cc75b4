"""Every choice of a tree program's path by backend and shape, in one place.

Each resolver is pure in what the code can observe (the platform, a
table's or a tree's shape); none reads an environment variable but
``_fold_edges_mode``. A jitted entry point pins what a resolver answered as
a static, so another answer retraces. The kernel modules read each one
through this module's attribute at call time (``_forms._hist_mode(...)``),
so a test that patches it here patches every caller.

- ``_hist_mode``: the level histograms. ``scatter`` on a CPU, a fused
  ``segment_sum``, the tests' reference; on an accelerator ``matmul``, one
  MXU contraction of one-hot slot and bin indicators a level, or
  ``matmul_chunk`` where the whole bin indicator would crowd the HBM.
- ``_route_form``, ``_sums_form``: how a level routes its rows and sums its
  nodes, by the base histogram mode and the width.
- ``_traverse_form``: how a walk of finished heaps reads its rows.
- ``_depth_mode``: a depth sweep as one program of depth blocks, or one
  program a depth.
- ``_bin_on_device``: where quantile binning runs.
- ``_fold_edges_mode``: bin edges of the whole matrix, or of each fold.
"""
from __future__ import annotations

import os

import jax
import numpy as np


#: cap on the (rows x features x stats) scatter-input materialized per
#: histogram call; larger designs chunk over feature blocks (the memory
#: bound the pre-packed per-feature scan used to provide)
_HIST_CHUNK_ELEMS = 32_000_000


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


#: widest design (columns) the ``matmul`` family still routes densely
#: (see _route_form). One level on a v5e, 196,608 rows, 32 slots (builder's
#: chip run, PR 27, PERF.md section 6): the gather form takes 5.4-6.2 ms
#: whatever the width (27-31 ns a row), the dense form 0.24 / 0.28 / 1.14 /
#: 2.18 / 4.26 ms at 100 / 200 / 1,000 / 2,000 / 4,000 columns (0.2 ms +
#: 1.0 ms per 1,000 columns; 256 slots add 0.3 ms): they meet near 5,600
#: columns, and at 4,096 the dense form still wins by a quarter
_ROUTE_DENSE_MAX_D = 4096


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


def _fold_edges_mode() -> bool:
    """Whether fold×grid searches compute bin edges from each fold's
    train rows only (TX_TREE_EDGES=fold) instead of the whole prepared
    matrix (default; standard histogram-GBM CV practice — the edges
    carry feature-distribution information only;
    examples/edges_audit.py audits it at scale)."""
    return os.environ.get("TX_TREE_EDGES", "matrix") == "fold"
