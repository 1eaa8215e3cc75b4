"""Multi-fidelity racing search: successive-halving validation.

``RacingCrossValidation`` stops paying full cross-validation cost for
losing candidates: the whole family x grid pool is first evaluated at a
LOW fidelity (a subset of folds and/or a row-subsampled train mask),
the top ``1/eta`` by the evaluator's device metric re-enter the next
rung at ``eta``x the fidelity, and only the final survivors are
evaluated under the EXACT full-CV fold protocol (successive halving /
ASHA; cf. Li et al., arxiv 1810.05934). Each rung reuses the family
``eval_fold_grid_arrays`` batched kernels, so a rung is a handful of
fused fit+metric XLA programs — candidate parameters never reach the
host; only the (folds, candidates) metric matrix does.

Fidelity axes are DYNAMIC arguments, not statics:

- row fidelity: single-fold screening rungs SLICE the subsampled train
  rows (deterministic kept-row counts -> stable rung shapes across
  runs, one compile per rung ever — the serving plan's shape-bucketing
  idiom; a zero-mask would keep full-shape FLOPs and save nothing);
  multi-fold rungs edit 0/1 values into the shared train mask (same
  shape — no retrace),
- fold fidelity slices the leading mask/validation axes (one compile
  per rung shape, cached across runs),
- candidate subsetting flows through ``cand_idx`` index vectors into
  the kernels' traced hyperparameter vectors (values stay dynamic; see
  lint rule TX-J07 for the anti-pattern this avoids).

Exactness contract (asserted in tests/test_racing.py): the final rung
evaluates survivors under the same folds, same train masks and same
metric kernel as exact full CV — a racing winner's reported metric is
directly comparable to a full-CV one. Families without a device metric
path (custom evaluators, non-traceable grids, preconditions violated)
drop out of the race and are validated at full fidelity through the
ordinary exact paths; their results join the final comparison.
"""
from __future__ import annotations

import logging
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..models.base import Predictor, pad_cand_idx
from ..observability import trace as _trace
from ..runtime import telemetry as _telemetry
from ..runtime.errors import BUG, classify_error
from ..runtime.faults import maybe_inject
from .validator import (_QUARANTINED, BestEstimator, CrossValidation,
                        ValidationResult)

__all__ = ["RacingCrossValidation", "search_compiles"]

_log = logging.getLogger(__name__)

#: (family, folds, rows, candidates, spec) signatures dispatched — each
#: is at most a few XLA programs; repeated same-shape searches add no
#: new keys (the compile-count diagnostic, mirroring
#: models/trees/fold_grid.tree_kernel_compiles and serving.plan_compiles)
_RUNG_KEYS: set = set()


def _note_rung_programs(family: str, folds: int, n_rows: int,
                        n_cands: int, spec: tuple) -> None:
    _RUNG_KEYS.add((family, folds, n_rows, n_cands, spec))


def search_compiles() -> int:
    """Distinct racing rung program signatures requested so far in this
    process. A repeated same-shape search leaves this unchanged — the
    rung kernels are memoized per (config, shape), so zero new XLA
    programs are built (the ``plan_compiles()``-style counter the
    acceptance gate reads)."""
    return len(_RUNG_KEYS)


class _Racer:
    """Bookkeeping for one raced candidate (family index, grid index)."""

    __slots__ = ("fam", "gi", "alive", "rung", "budget", "pruned_at",
                 "metrics")

    def __init__(self, fam: int, gi: int):
        self.fam = fam
        self.gi = gi
        self.alive = True
        self.rung: Optional[int] = None
        self.budget = 0.0
        self.pruned_at: Optional[int] = None
        self.metrics: List[float] = []

    def mean(self) -> float:
        arr = np.asarray(self.metrics, dtype=np.float64)
        return float(np.mean(arr)) if arr.size else float("nan")


class RacingCrossValidation(CrossValidation):
    """Successive-halving k-fold search (``validation="racing"``).

    eta          : promotion ratio — each rung keeps the top ``1/eta``
    min_fidelity : budget fraction of the first rung (full CV = 1.0);
                   default ``1/eta**2`` gives the classic 3-rung ladder
                   (e.g. eta=3 -> 1/9, 1/3, 1). The ladder always ends
                   at exactly 1.0: the final rung IS full CV for the
                   survivors.

    When NEITHER is given, the schedule comes from the TuningPolicy
    (tuning/policy.py): the persisted ``family:*`` compile-vs-execute
    records pick the ladder that amortizes recorded compile cost
    (docs/autotuning.md). A cold/absent store or ``TX_TUNE=off``
    resolves to exactly the classic (eta=3, 1/9) ladder — bitwise the
    old defaults. Explicit arguments always win (``caller`` source).
    """

    validation_type = "RacingCrossValidation"

    def __init__(self, evaluator, num_folds: int = 3,
                 eta: Optional[int] = None,
                 min_fidelity: Optional[float] = None, seed: int = 42,
                 stratify: bool = False, mesh="auto"):
        super().__init__(evaluator, num_folds=num_folds, seed=seed,
                         stratify=stratify, mesh=mesh)
        #: the TuningDecision records behind this schedule ([] when the
        #: caller pinned it); bench/tx tune surface them
        self.tuning_decisions: List = []
        if eta is None and min_fidelity is None:
            try:
                from ..tuning.policy import TuningPolicy
                eta, min_fidelity, self.tuning_decisions = \
                    TuningPolicy().racing_schedule()
            except (ImportError, OSError, ValueError,
                    KeyError, TypeError):
                # pragma: no cover - unreadable/malformed store:
                # fall through to the static schedule below
                pass
        if eta is None:
            from ..tuning.registry import STATIC_DEFAULTS
            eta = int(STATIC_DEFAULTS["search.eta"])
        if eta < 2:
            raise ValueError("eta must be >= 2")
        self.eta = int(eta)
        mf = (1.0 / (eta * eta)) if min_fidelity is None else float(
            min_fidelity)
        if not 0.0 < mf <= 1.0:
            raise ValueError("min_fidelity must be in (0, 1]")
        self.min_fidelity = mf
        #: telemetry of the last validate() call (rungs, budgets,
        #: pruned counts) — the selector copies it into
        #: ModelSelectorSummary.racing
        self.last_report: Dict = {}

    @classmethod
    def from_cross_validation(cls, cv: CrossValidation,
                              eta: Optional[int] = None,
                              min_fidelity: Optional[float] = None
                              ) -> "RacingCrossValidation":
        """Racing twin of an exact CV validator (same folds, same seed,
        same evaluator — only the schedule changes)."""
        return cls(cv.evaluator, num_folds=cv.num_folds, eta=eta,
                   min_fidelity=min_fidelity, seed=cv.seed,
                   stratify=cv.stratify, mesh=cv.mesh)

    def get_params(self):
        out = super().get_params()
        out.update({"eta": self.eta, "minFidelity": self.min_fidelity,
                    "validation": "racing"})
        return out

    # -- schedule ----------------------------------------------------------
    def _rung_budgets(self) -> List[float]:
        """Ascending budget fractions ending at exactly 1.0 (the full-CV
        rung): min_fidelity * eta^r, capped."""
        budgets: List[float] = []
        b = self.min_fidelity
        while b < 1.0 - 1e-12:
            budgets.append(b)
            b *= self.eta
        budgets.append(1.0)
        return budgets

    def _eval_rung_cands(self, est, grid, X_r, y_r, rung_masks, Xv_r,
                         yv_r, spec, alive: Sequence[int], shards: int,
                         val_rows=None):
        """One family's rung evaluation with the candidate axis padded
        to a multiple of the mesh's ``models`` shard count
        (models/base.pad_cand_idx): rung program SHAPES stay on the
        shard lattice — alive counts that differ only by pruning
        trajectory reuse one compiled program — and the padded columns
        (duplicates of the last alive candidate) are sliced off HERE,
        before anything is journaled, ranked or reported, so the prune
        decision sees the identical candidate set on every device
        count."""
        padded, n_valid = pad_cand_idx(alive, shards)
        mm = self._try_device_eval(
            est, grid, X_r, y_r, rung_masks, Xv_r, yv_r, spec,
            cand_idx=np.asarray(padded, dtype=np.int64),
            val_rows=val_rows)
        if mm is None:
            return None
        return np.asarray(mm, dtype=np.float64)[:, :n_valid]

    def _prune_rung(self, contenders: List[_Racer], rung: int) -> int:
        """The rung-boundary prune as ONE COLLECTIVE decision.

        Every family kernel returns its metric shard through
        ``parallel/mesh.to_host`` — on a multi-process mesh that is a
        ``process_allgather``, so every host holds the identical global
        (folds, candidates) table when it reaches this point. The
        global top-``1/eta`` is then computed once from that gathered
        table with a fully deterministic ordering (metric descending by
        the evaluator's sign; non-finite last; (family, grid) index as
        the tie-break) — no RNG, no wall-clock, no device-count
        dependence — so every host, and a resume on ANY mesh topology,
        prunes the exact same candidates (tests/test_sharded_search.py
        asserts rung decisions bitwise across 1/2/8 devices).

        Returns the promoted (kept) count."""
        sign = 1.0 if self.evaluator.is_larger_better else -1.0
        scored = sorted(
            contenders,
            key=lambda rc: (-(sign * rc.mean())
                            if np.isfinite(rc.mean())
                            else np.inf, rc.fam, rc.gi))
        keep = max(1, int(np.ceil(len(scored) / self.eta)))
        for rc in scored[keep:]:
            rc.alive = False
            rc.pruned_at = rung
        return keep

    def _fidelity(self, budget: float, n_folds: int) -> Tuple[int, float]:
        """(folds, train-row fraction) realizing a budget fraction.
        Budget is measured in full-CV units: folds * row_fraction =
        budget * num_folds fold-fit equivalents."""
        fold_units = budget * n_folds
        folds = min(n_folds, max(1, int(round(fold_units))))
        return folds, min(1.0, fold_units / folds)

    def _rung_masks(self, masks: np.ndarray, y: np.ndarray, rung: int,
                    folds: int, row_frac: float) -> np.ndarray:
        """Per-rung train masks: the first ``folds`` folds of the FULL
        CV protocol, with a deterministic row subsample (stratified when
        the splits are) zeroed INTO the mask. Single-fold rungs then
        slice the kept rows out (see validate) so low fidelity costs
        proportionally less compute; multi-fold rungs use the mask
        as-is — same shape, dynamic values, no retrace."""
        sub = np.array(masks[:folds], copy=True)
        if row_frac >= 1.0:
            return sub
        for f in range(folds):
            rng = np.random.default_rng(
                [int(self.seed), 104729, int(rung), f])
            idx = np.nonzero(sub[f] > 0)[0]
            if self.stratify:
                kept = [rng.permutation(ci)[:max(1, int(round(
                    len(ci) * row_frac)))]
                    for cls in np.unique(y[idx])
                    for ci in [idx[y[idx] == cls]]]
                keep = np.concatenate(kept)
            else:
                keep = rng.permutation(idx)[
                    :max(1, int(round(len(idx) * row_frac)))]
            sub[f, np.setdiff1d(idx, keep)] = 0.0
        return sub

    # -- the racing loop ---------------------------------------------------
    def validate(self,
                 models: Sequence[Tuple[Predictor, Sequence[Dict]]],
                 X: np.ndarray, y: np.ndarray) -> BestEstimator:
        t0 = time.perf_counter()
        models = [(est, list(grid) or [{}]) for est, grid in models]
        _, masks, fold_data, spec, X_val_st, y_val_st, val_rows = \
            self._build_fold_arrays(X, y)
        F = masks.shape[0]
        budgets = self._rung_budgets()
        n_total = sum(len(grid) for _, grid in models)
        if spec is None or X_val_st is None or len(budgets) < 2 \
                or n_total <= 1:
            # nothing to race (no device metric / unequal folds /
            # min_fidelity=1 / single candidate): exact full CV
            _log.info("racing disabled for this search (no device "
                      "metric path or degenerate schedule); running "
                      "exact full CV")
            best = super().validate(models, X, y)
            self.last_report = {
                "raced": False, "eta": self.eta,
                "minFidelity": self.min_fidelity, "rungs": [],
                "candidatesTotal": n_total, "candidatesPruned": 0,
                "budgetSpentFoldFits": float(n_total * F),
                "budgetFullCvFoldFits": float(n_total * F),
                "searchSeconds": round(time.perf_counter() - t0, 3)}
            return best

        ctx = self._begin_runtime(models, X, y)
        try:
            return self._validate_raced(models, X, y, masks, fold_data,
                                        spec, X_val_st, y_val_st,
                                        budgets, n_total, ctx, t0,
                                        val_rows)
        finally:
            ctx.close_journal()

    def _validate_raced(self, models, X, y, masks, fold_data, spec,
                        X_val_st, y_val_st, budgets, n_total, ctx, t0,
                        val_rows=None) -> BestEstimator:
        from ..parallel.cv import mesh_model_shards
        shards = mesh_model_shards(self.mesh)
        F = masks.shape[0]
        racers: Dict[Tuple[int, int], _Racer] = {
            (fi, gi): _Racer(fi, gi)
            for fi, (_, grid) in enumerate(models)
            for gi in range(len(grid))}
        host_fams: List[int] = []       # families validated exactly
        quarantined_fams: set = set()   # families out of the search
        rung_rows: List[Dict] = []
        for r, b in enumerate(budgets):
            # the rung-boundary kill-point: a simulated preemption here
            # loses NOTHING — every completed rung below is journaled
            # (fsync'd), so a resume replays rungs 0..r-1 and dispatches
            # only from here on (tests/test_resilience.py)
            maybe_inject("rung", str(r), "boundary")
            final = r == len(budgets) - 1
            folds_r, row_frac = self._fidelity(b, F)
            X_r, y_r = X, y
            if final:
                # the exactness invariant: the last rung IS full CV
                assert folds_r == F and row_frac >= 1.0
                rung_masks = masks
            else:
                rung_masks = self._rung_masks(masks, y, r, folds_r,
                                              row_frac)
                if folds_r == 1 and row_frac < 1.0:
                    # single-fold screening rungs SLICE the subsampled
                    # train rows out instead of zero-masking them:
                    # masked rows still cost full FLOPs (the shapes
                    # don't change), a slice makes low fidelity
                    # genuinely cheap. The kept-row count is
                    # deterministic per (seed, rung, fold sizes), so
                    # rung shapes are stable across runs — one compile
                    # per rung ever (the serving plan's shape-bucketing
                    # idiom applied to the search). Multi-fold rungs
                    # keep the mask-edit dynamics: their folds need the
                    # shared train matrix.
                    kept = np.nonzero(rung_masks[0] > 0)[0]
                    X_r, y_r = X[kept], y[kept]
                    rung_masks = np.ones((1, len(kept)))
            Xv_r, yv_r = X_val_st[:folds_r], y_val_st[:folds_r]
            # a rung that fits on X itself (masks edited, rows kept)
            # still holds every validation row where val_rows says; a
            # sliced screening table does not
            vr_r = (val_rows[:folds_r]
                    if val_rows is not None and X_r is X else None)
            fam_idx: List[Tuple[int, List[int]]] = []
            for fi, (est, grid) in enumerate(models):
                if fi in host_fams:
                    continue
                alive = [gi for gi in range(len(grid))
                         if racers[(fi, gi)].alive]
                if alive:
                    fam_idx.append((fi, alive))
            if not fam_idx:
                break
            tasks = []
            for fi, alive in fam_idx:
                est, grid = models[fi]
                # program signature uses the PADDED candidate count:
                # that is the traced shape (the shard lattice), and the
                # reason repeated searches with different pruning
                # trajectories request zero new programs
                _note_rung_programs(type(est).__name__, folds_r,
                                    rung_masks.shape[1],
                                    len(pad_cand_idx(alive, shards)[0]),
                                    spec)
                tasks.append((
                    type(est).__name__, self._family_key(fi, est),
                    tuple(alive),
                    lambda e=est, g=grid, a=alive: self._eval_rung_cands(
                        e, g, X_r, y_r, rung_masks, Xv_r, yv_r, spec,
                        a, shards, vr_r)))
            # one span per racing rung: the family dispatches below
            # parent to it, so a trace shows rung -> family -> compile
            # sections (docs/observability.md)
            with _trace.span("search.rung", rung=r, final=final,
                             folds=folds_r,
                             budget=round(float(b), 4),
                             families=len(fam_idx),
                             alive=sum(len(a) for _, a in fam_idx)):
                mats = self._dispatch_device_evals(
                    tasks, X_r, rung_masks, Xv_r, yv_r, spec, ctx=ctx,
                    rung=r, rung_label=f"rung{r}")
            n_evaluated = 0
            for (fi, alive), mm in zip(fam_idx, mats):
                est, grid = models[fi]
                if mm is _QUARANTINED:
                    # the family is out of THIS search entirely: no
                    # results, no exact fallback — the quarantine
                    # ledger (ModelSelectorSummary.quarantined) records
                    # why, and the race continues with survivors
                    quarantined_fams.add(fi)
                    for gi in range(len(grid)):
                        racers[(fi, gi)].alive = False
                    continue
                if mm is None:
                    # family can't race (non-traceable grid, labels,
                    # precondition): validate it exactly at full
                    # fidelity through the ordinary paths instead
                    _log.info("family %s leaves the race at rung %d; "
                              "validating it under exact full CV",
                              type(est).__name__, r)
                    host_fams.append(fi)
                    for gi in range(len(grid)):
                        racers[(fi, gi)].alive = False
                    continue
                mm = np.asarray(mm, dtype=np.float64)
                n_evaluated += len(alive)
                for j, gi in enumerate(alive):
                    racer = racers[(fi, gi)]
                    racer.rung = r
                    racer.budget += folds_r * row_frac
                    racer.metrics = [float(v) for v in mm[:, j]]
            contenders = [rc for rc in racers.values() if rc.alive]
            promoted = len(contenders)
            if not final and contenders:
                # collective rung-boundary decision over the gathered
                # global metric table — identical on every host and
                # every device count (_prune_rung)
                promoted = self._prune_rung(contenders, r)
            rung_rows.append({
                "rung": r, "budgetFraction": round(b, 6),
                "folds": folds_r, "rowFraction": round(row_frac, 6),
                "candidates": n_evaluated, "promoted": promoted})
        # exact validation for the families that left the race
        # (journaled under "exact" — a resume replays them too, and a
        # classified failure here quarantines instead of dying)
        host_results: Dict[int, List[ValidationResult]] = {}
        for fi in host_fams:
            est, grid = models[fi]
            key = self._family_key(fi, est)
            cands = tuple(range(len(grid)))
            cached = ctx.journal_lookup(key, "exact", cands)
            if cached is not None:
                host_results[fi] = self._results_from_journal(
                    est, grid, cached)
                continue
            try:
                mm = self._try_device_eval(est, grid, X, y, masks,
                                           X_val_st, y_val_st, spec,
                                           val_rows=val_rows)
                host_results[fi] = (
                    self._results_from_matrix(est, grid, mm)
                    if mm is not None else
                    self._family_host_results(est, grid, X, y, masks,
                                              fold_data))
            except Exception as e:
                kind = classify_error(e)
                if kind == BUG:
                    raise
                ctx.quarantine(type(est).__name__,
                               f"{type(e).__name__}: {e}", kind=kind,
                               error_type=type(e).__name__)
                quarantined_fams.add(fi)
                host_results[fi] = []
                continue
            _telemetry.note_dispatch(key, "exact", cands, F)
            ctx.journal_record(
                key, "exact", cands,
                [r.metric_values for r in host_results[fi]], F)
        # assemble results in the exact-path family/grid order
        results: List[ValidationResult] = []
        rank_pool: List[ValidationResult] = []
        for fi, (est, grid) in enumerate(models):
            if fi in quarantined_fams:
                continue
            if fi in host_fams:
                results.extend(host_results[fi])
                # full-fidelity metrics: they compete with finalists
                rank_pool.extend(host_results[fi])
                continue
            for gi, params in enumerate(grid):
                rc = racers[(fi, gi)]
                res = ValidationResult(
                    model_name=type(est).__name__, model_uid=est.uid,
                    grid_index=gi, params=dict(params),
                    metric_values=list(rc.metrics),
                    rung=rc.rung if rc.rung is not None else 0,
                    budget_spent=round(rc.budget, 6),
                    pruned_at=rc.pruned_at)
                results.append(res)
                if rc.pruned_at is None and rc.rung is not None:
                    rank_pool.append(res)
        spent = sum(rc.budget for rc in racers.values()) \
            + float(sum(len(models[fi][1]) for fi in host_fams
                        if fi not in quarantined_fams)) * F
        self.last_report = {
            "raced": True, "eta": self.eta,
            "minFidelity": self.min_fidelity, "rungs": rung_rows,
            "candidatesTotal": n_total,
            "candidatesPruned": sum(
                1 for rc in racers.values() if rc.pruned_at is not None),
            "budgetSpentFoldFits": round(spent, 3),
            "budgetFullCvFoldFits": float(n_total * F),
            "searchSeconds": round(time.perf_counter() - t0, 3)}
        return self._pick_best(models, results, rank_pool=rank_pool,
                               ctx=ctx)
