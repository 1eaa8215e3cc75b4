"""The level-wise tree grower: one complete tree of static depth over a
packed binned design (``design.py``), every level one XLA program step.

A level contracts per-(slot, packed bin) statistic histograms
(``_level_histograms``: under the accelerator's ``matmul`` family one MXU
contraction of the slot one-hot, the statistics and the bin indicator; under
``scatter``, the CPU's reference, a fused ``segment_sum``), turns them into
split gains with one segmented cumulative sum over the packed axis, and
routes every row one level down. Deep levels hold only their occupied nodes
(compressed slots, ``_carry_slots``). No shape depends on the data, so a
tree is one traced program; ``bodies.py`` runs it under ``scan`` and
``vmap`` for forests, boosting rounds and the lanes of a fold-grid program.
The split criteria close the module.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import forms as _forms


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
                          _forms._HIST_CHUNK_ELEMS // max(n, 1)))
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
    n_chunks = max(1, -(- (n * d * s_dim) // _forms._HIST_CHUNK_ELEMS))
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
        self.packed, self.feat_of = packed, feat_of
        self.block_start, self.packed_thr = block_start, packed_thr
        self.feat_map, self.axis_name = feat_map, axis_name
        self.cap = min(row_total if row_total is not None else n,
                       _DEFAULT_NODE_CAP if node_cap is None else node_cap)
        self.not_a_split = ~jnp.isfinite(packed_thr)  # last + padded bins
        # resolved here only when the caller did not pin it; jitted entry
        # points MUST pin it (static arg) or mode switches won't retrace
        hist_mode = hist_mode or _forms._hist_mode(n, TB)
        # "<mode>+sub": a value of the static that only tests pass (the
        # resolver never returns it): histogram subtraction, see _hist_mode
        self.hist_mode, _, suffix = hist_mode.partition("+")
        self.sub_enabled = suffix == "sub"
        if self.hist_mode == "matmul" and bin_oh is None:
            with jax.named_scope("tree.indicator"):
                bin_oh = _bin_indicator(self.head(packed), TB, dtype,
                                        feat_of)
        self.bin_oh = bin_oh             # None: scatter / matmul_chunk modes
        self.route = _forms._route_form(self.hist_mode, d)
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
        return _forms._sums_form(self.hist_mode,
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
