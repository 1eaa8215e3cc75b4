"""TuningPolicy: turn cost-model predictions into knob decisions.

Every decision flows through one :class:`TuningDecision` record —
knob, chosen value, static default, predicted cost both ways,
confidence, source — consumed by three layers:

- **serving** (serving/server.py): the coalescer target when a plan has
  no local bucket profile yet, the ScoringPlan bucket range, and the
  pre-warm set compiled before traffic,
- **search** (selector/racing.py): the racing ``eta``/``min_fidelity``
  schedule, chosen so the rung ladder amortizes the recorded
  compile-vs-execute split (the final rung stays exact full CV — the
  exactness contract is structural, not a tuning outcome),
- **prepare** (plans/placement.py): the host-vs-device seed records and
  comparison margin, so a fresh process places its FIRST fit from
  cross-run history.

Cold-start safety is the contract: with an empty/absent store every
decision is bitwise the static default (``source="default"``), and
``TX_TUNE=off`` disables the whole layer (``source="disabled"``).
Operators inspect and pin decisions with ``tx tune`` (cli/tune.py);
pinned values live in the store's ``tuning.overrides`` block and win
over the model (``source="override"``).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from ..observability.store import ProfileStore, default_store_path
from .lattice import (choose_lattice, default_lattice, floor_pow2,
                      grow_pow2)
from .model import DEFAULT, CostModel
from .model_v2 import CostModelV2
from .registry import STATIC_DEFAULTS, knob as _knob_meta

__all__ = ["TuningDecision", "TuningPolicy", "tuning_enabled"]

_OFF_VALUES = ("off", "0", "false", "disabled", "no")

#: decision sources
SOURCE_MODEL = "model"
SOURCE_DEFAULT = "default"
SOURCE_OVERRIDE = "override"
SOURCE_DISABLED = "disabled"
SOURCE_CALLER = "caller"


def tuning_enabled() -> bool:
    """``TX_TUNE=off`` kills the whole autotuning layer."""
    return os.environ.get("TX_TUNE", "on").strip().lower() \
        not in _OFF_VALUES


@dataclass(frozen=True)
class TuningDecision:
    """One knob's resolution: what was chosen, what the static default
    would have been, and why."""
    knob: str
    chosen: Any
    default: Any
    #: model's cost estimate (seconds) under the chosen value / under
    #: the static default — None when the model has no basis
    predicted_chosen: Optional[float]
    predicted_default: Optional[float]
    confidence: str            # recorded | interpolated | default
    source: str                # model | default | override | disabled
    reason: str

    def tuned(self) -> bool:
        return self.chosen != self.default \
            and self.source in (SOURCE_MODEL, SOURCE_OVERRIDE)

    def to_json(self) -> dict:
        rnd = (lambda v: None if v is None else round(float(v), 6))
        chosen = (list(self.chosen)
                  if isinstance(self.chosen, tuple) else self.chosen)
        default = (list(self.default)
                   if isinstance(self.default, tuple) else self.default)
        return {"knob": self.knob, "chosen": chosen, "default": default,
                "predictedChosen": rnd(self.predicted_chosen),
                "predictedDefault": rnd(self.predicted_default),
                "confidence": self.confidence, "source": self.source,
                "tuned": self.tuned(), "reason": self.reason}


def _coerce(knob_name: str, value: Any) -> Any:
    """Normalize a persisted/CLI override to the knob's declared
    kind (overrides round-trip through JSON and argv strings)."""
    meta = _knob_meta(knob_name)
    kind = meta.kind if meta else "float"
    if kind == "int":
        return int(value)
    if kind == "float":
        return None if value is None else float(value)
    if kind == "str":
        return None if value is None else str(value)
    if kind == "int_tuple":
        if isinstance(value, str):
            value = [v for v in value.split(",") if v.strip()]
        return tuple(int(v) for v in value)
    return value


class TuningPolicy:
    """One store snapshot's worth of decisions. Construction reads the
    store once; consumers build a policy per long-lived object (server,
    validator, prepare plan) so a fresh process always honors freshly
    persisted overrides."""

    def __init__(self, path: Optional[str] = None,
                 enabled: Optional[bool] = None,
                 model: Optional[CostModel] = None):
        self.path = path or default_store_path()
        self.enabled = tuning_enabled() if enabled is None else \
            bool(enabled)
        self.store = ProfileStore(self.path)
        if self.enabled:
            self.model = model or CostModelV2.from_store(self.path)
            self.overrides = self.store.tuning_overrides()
        else:
            self.model = CostModel({})
            self.overrides = {}

    # -- shared resolution skeleton ----------------------------------------
    def _static(self, knob_name: str, reason: str) -> TuningDecision:
        default = STATIC_DEFAULTS[knob_name]
        return TuningDecision(
            knob=knob_name, chosen=default, default=default,
            predicted_chosen=None, predicted_default=None,
            confidence=DEFAULT,
            source=SOURCE_DISABLED if not self.enabled
            else SOURCE_DEFAULT,
            reason="TX_TUNE=off — autotuning disabled"
            if not self.enabled else reason)

    def _override(self, knob_name: str) -> Optional[Any]:
        if self.enabled and knob_name in self.overrides:
            return _coerce(knob_name, self.overrides[knob_name])
        return None

    # -- serving -----------------------------------------------------------
    def target_batch(self, max_wait_ms: float,
                     max_batch: int) -> TuningDecision:
        """The coalescer target for a plan with NO local bucket profile:
        the largest bucket whose PREDICTED per-dispatch execute cost
        fits inside the wait budget — the cross-run twin of
        ``ServingServer._target_batch``'s process-local rule."""
        name = "serving.target_batch"
        default = STATIC_DEFAULTS[name]
        ov = self._override(name)
        budget_s = float(max_wait_ms) / 1000.0
        if ov is not None:
            est = self.model.predict("score", bucket=int(ov))
            dflt = self.model.predict("score", bucket=default)
            return TuningDecision(
                name, int(ov), default, est.execute, dflt.execute,
                est.confidence, SOURCE_OVERRIDE,
                f"pinned by tx tune --set (store {self.path})")
        known = self.model.recorded_buckets("score") if self.enabled \
            else {}
        if not known:
            return self._static(
                name, "no score:b* records in the store yet")
        best, best_est = 0, None
        cap = max(int(max_batch), 1)
        for b in default_lattice(
                int(STATIC_DEFAULTS["serving.min_bucket"]), cap):
            if b > cap:
                continue
            est = self.model.predict("score", bucket=b)
            if est.known() and est.execute is not None \
                    and est.execute <= budget_s and b > best:
                best, best_est = b, est
        dflt_est = self.model.predict("score", bucket=default)
        if not best:
            return self._static(
                name, f"no bucket's predicted dispatch cost fits the "
                      f"{max_wait_ms}ms budget")
        return TuningDecision(
            name, best, default, best_est.execute, dflt_est.execute,
            best_est.confidence, SOURCE_MODEL,
            f"largest bucket with predicted per-dispatch execute "
            f"{best_est.execute * 1e3:.3f}ms <= max_wait_ms budget "
            f"{max_wait_ms}ms ({len(known)} recorded buckets)")

    def bucket_range(self, max_batch: Optional[int] = None
                     ) -> Tuple[TuningDecision, TuningDecision]:
        """(min_bucket, max_bucket) decisions: clamp the ScoringPlan's
        bucket ladder onto the shapes the store has actually seen, so
        a fresh process compiles profiled programs instead of the full
        static ladder."""
        lo_name, hi_name = "serving.min_bucket", "serving.max_bucket"
        lo_d = int(STATIC_DEFAULTS[lo_name])
        hi_d = int(STATIC_DEFAULTS[hi_name])
        lo_ov, hi_ov = self._override(lo_name), self._override(hi_name)
        known = self.model.recorded_buckets("score") if self.enabled \
            else {}
        if known:
            lo_m, hi_m = min(known), max(known)
            if max_batch is not None:
                hi_m = grow_pow2(hi_m, min(int(max_batch), hi_d))
            source, conf = SOURCE_MODEL, "recorded"
            reason = (f"recorded dispatch shapes span b{lo_m}..b{hi_m} "
                      f"({len(known)} buckets)")
        else:
            lo_m, hi_m = lo_d, hi_d
            source, conf = (SOURCE_DISABLED if not self.enabled
                            else SOURCE_DEFAULT), DEFAULT
            reason = ("TX_TUNE=off — autotuning disabled"
                      if not self.enabled
                      else "no score:b* records in the store yet")
        lo = int(lo_ov) if lo_ov is not None else lo_m
        hi = int(hi_ov) if hi_ov is not None else hi_m
        hi = max(hi, lo)
        mk = (lambda nm, chosen, ov, dflt: TuningDecision(
            nm, chosen, dflt, None, None,
            conf if ov is None else "recorded",
            SOURCE_OVERRIDE if ov is not None else source,
            f"pinned by tx tune --set (store {self.path})"
            if ov is not None else reason))
        return (mk(lo_name, lo, lo_ov, lo_d),
                mk(hi_name, hi, hi_ov, hi_d))

    def prewarm_buckets(self, max_batch: Optional[int] = None
                        ) -> TuningDecision:
        """Buckets to pre-compile BEFORE traffic: every recorded
        dispatch shape within the serve cap. Predicted cost both ways
        is the same compile bill — tuned pays it behind the readiness
        gate, static pays it inside the first requests' latency."""
        name = "serving.prewarm"
        default = STATIC_DEFAULTS[name]
        ov = self._override(name)
        if ov is not None:
            chosen = tuple(sorted(set(int(b) for b in ov)))
            comp = sum((self.model.predict("score", bucket=b).compile
                        or 0.0) for b in chosen)
            return TuningDecision(
                name, chosen, default, comp, comp, "recorded",
                SOURCE_OVERRIDE,
                f"pinned by tx tune --set (store {self.path})")
        known = self.model.recorded_buckets("score") if self.enabled \
            else {}
        chosen = tuple(sorted(
            b for b in known
            if max_batch is None or b <= int(max_batch)))
        if not chosen:
            return self._static(
                name, "no score:b* records in the store yet")
        comp = sum((known[b].compile or 0.0) for b in chosen)
        return TuningDecision(
            name, chosen, default, comp, comp, "recorded",
            SOURCE_MODEL,
            f"pre-compiling {len(chosen)} recorded buckets moves a "
            f"predicted {comp:.2f}s compile bill out of first-request "
            f"latency")

    def admission_queue_rows(self, max_batch: int = 256
                             ) -> TuningDecision:
        """Per-lane admission bound (rows) for the overload controller
        (serving/admission.py): the largest power of two whose backlog
        drains within ~250ms at the store's recorded dispatch rate, so
        the shed edge engages where queue wait would start dominating
        the SLO instead of at an arbitrary depth."""
        name = "serving.admission_queue_rows"
        default = int(STATIC_DEFAULTS[name])
        ov = self._override(name)
        if ov is not None:
            return TuningDecision(
                name, int(ov), default, None, None, "recorded",
                SOURCE_OVERRIDE,
                f"pinned by tx tune --set (store {self.path})")
        known = self.model.recorded_buckets("score") if self.enabled \
            else {}
        rates = [(b / max(e.execute or e.wall or 0.0, 1e-9), b)
                 for b, e in known.items()
                 if b <= max(int(max_batch), 1)
                 and (e.execute or e.wall)]
        if not rates:
            return self._static(
                name, "no score:b* records in the store yet")
        rate, _bucket = max(rates)
        budget_s = 0.25
        rows = floor_pow2(rate * budget_s)
        chosen = max(min(rows, 4 * default), int(max_batch))
        return TuningDecision(
            name, chosen, default, chosen / rate, default / rate,
            "recorded", SOURCE_MODEL,
            f"recorded drain rate ~{rate:.0f} rows/s: a {chosen}-row "
            f"backlog clears in {chosen / rate * 1e3:.0f}ms "
            f"(~{budget_s * 1e3:.0f}ms budget; {len(known)} recorded "
            f"buckets)")

    def admission_quantum(self) -> TuningDecision:
        """DRR quantum for the admission dispatch-grant ring
        (override-only: the model keeps the static granularity)."""
        name = "serving.admission_quantum"
        ov = self._override(name)
        if ov is not None:
            return TuningDecision(
                name, int(ov), STATIC_DEFAULTS[name], None, None,
                "recorded", SOURCE_OVERRIDE,
                f"pinned by tx tune --set (store {self.path})")
        return self._static(
            name, "model keeps the static fairness granularity")

    def lattice_max_rungs(self) -> TuningDecision:
        """Rung bound for tuned bucket lattices (override-only: the
        bound is a compile-budget policy, like the waste ceiling)."""
        name = "tuning.lattice_max_rungs"
        ov = self._override(name)
        if ov is not None:
            return TuningDecision(
                name, int(ov), STATIC_DEFAULTS[name], None, None,
                "recorded", SOURCE_OVERRIDE,
                f"pinned by tx tune --set (store {self.path})")
        return self._static(
            name, "rung bound is a compile-budget policy choice")

    def bucket_lattice(self, min_bucket: Optional[int] = None,
                       max_bucket: Optional[int] = None
                       ) -> TuningDecision:
        """THE padding decision: the bucket lattice ScoringPlans
        dispatch on, chosen by the recorded occupancy histogram ×
        predicted per-bucket cost (tuning/lattice.py). Cold start
        (no occupancy) or TX_TUNE=off keeps the default power-of-two
        ladder bitwise."""
        name = "serving.bucket_lattice"
        lo = int(STATIC_DEFAULTS["serving.min_bucket"]
                 if min_bucket is None else min_bucket)
        hi = int(STATIC_DEFAULTS["serving.max_bucket"]
                 if max_bucket is None else max_bucket)
        dflt = default_lattice(lo, hi)
        if not self.enabled:
            return TuningDecision(
                name, dflt, dflt, None, None, DEFAULT, SOURCE_DISABLED,
                "TX_TUNE=off — autotuning disabled")
        occ = self.store.occupancy("score")
        if not occ:
            return TuningDecision(
                name, dflt, dflt, None, None, DEFAULT, SOURCE_DEFAULT,
                "no recorded occupancy histogram yet")
        known = self.model.recorded_buckets("score")
        exec_cost = compile_cost = None
        if known:
            exec_cost = (lambda b:
                         self.model.predict("score", bucket=b).execute)
            compile_cost = (lambda b:
                            self.model.predict("score",
                                               bucket=b).compile)
        choice = choose_lattice(
            occ, min_bucket=lo, max_bucket=hi,
            max_rungs=int(self.lattice_max_rungs().chosen),
            exec_cost=exec_cost, compile_cost=compile_cost)
        if not choice.tuned():
            return TuningDecision(
                name, dflt, dflt, choice.predicted_cost,
                choice.predicted_default_cost,
                "recorded" if known else DEFAULT, SOURCE_DEFAULT,
                choice.reason)
        conf = (self.model.predict(
            "score", bucket=choice.lattice[0]).confidence
            if known else DEFAULT)
        return TuningDecision(
            name, choice.lattice, dflt, choice.predicted_cost,
            choice.predicted_default_cost, conf, SOURCE_MODEL,
            choice.reason)

    def coalesce_policy(self, caller: Optional[str] = None,
                        lattice_tuned: bool = False) -> TuningDecision:
        """How the serving coalescer closes a batch. The model only
        moves off the fixed deadline-or-full rule when a tuned lattice
        is active AND it has recorded dispatch costs to predict
        marginal cost from — cold start stays bitwise on the old
        rule."""
        name = "serving.coalesce_policy"
        default = STATIC_DEFAULTS[name]
        valid = ("deadline_or_full", "predicted_cost")
        ov = self._override(name)
        if ov is not None:
            if ov not in valid:
                return TuningDecision(
                    name, default, default, None, None, DEFAULT,
                    SOURCE_DEFAULT,
                    f"override {ov!r} is not one of {valid} — "
                    f"keeping the default rule")
            return TuningDecision(
                name, str(ov), default, None, None, "recorded",
                SOURCE_OVERRIDE,
                f"pinned by tx tune --set (store {self.path})")
        if caller is not None:
            chosen = caller if caller in valid else default
            return TuningDecision(
                name, chosen, default, None, None, DEFAULT,
                SOURCE_CALLER,
                f"requested by the serve config"
                if caller in valid else
                f"config value {caller!r} is not one of {valid} — "
                f"keeping the default rule")
        if self.enabled and lattice_tuned \
                and self.model.recorded_buckets("score"):
            return TuningDecision(
                name, "predicted_cost", default, None, None,
                "recorded", SOURCE_MODEL,
                "tuned lattice active — split batches against the "
                "model's predicted per-row marginal cost")
        return self._static(
            name, "fixed deadline-or-full rule (no tuned lattice)")

    # -- search ------------------------------------------------------------
    def _schedule_cost(self, eta: int, mf: float,
                       compile_s: float, execute_s: float) -> float:
        """Predicted per-family search cost of one racing ladder:
        every rung compiles one program (~family compile cost) and
        executes its budget fraction over the ~1/eta**r survivors.
        Full exact CV is ``compile_s + execute_s`` on this scale."""
        budgets: List[float] = []
        b = float(mf)
        while b < 1.0 - 1e-12:
            budgets.append(b)
            b *= eta
        budgets.append(1.0)
        cost = 0.0
        for r, budget in enumerate(budgets):
            cost += compile_s + execute_s * budget * (eta ** -r)
        return cost

    def racing_schedule(self) -> Tuple[int, float, List[TuningDecision]]:
        """(eta, min_fidelity, [eta decision, min_fidelity decision]).

        The model picks the ladder minimizing predicted per-family
        search cost from the recorded compile-vs-execute split of
        ``family:*`` records: compile-dominated workloads get a
        SHALLOWER ladder (fewer rung programs to compile),
        execute-dominated ones a DEEPER ladder (cheaper screening
        rungs). The final rung is full CV in every candidate —
        exactness is structural."""
        eta_name, mf_name = "search.eta", "search.min_fidelity"
        eta_d = int(STATIC_DEFAULTS[eta_name])
        mf_d = 1.0 / (eta_d * eta_d)
        eta_ov, mf_ov = self._override(eta_name), self._override(mf_name)
        fam = self.model.family_totals() if self.enabled else None

        chosen_eta, chosen_mf = eta_d, mf_d
        source, conf = SOURCE_DEFAULT, DEFAULT
        pred_c = pred_d = None
        reason = "no family:* records in the store yet"
        if not self.enabled:
            source, reason = SOURCE_DISABLED, \
                "TX_TUNE=off — autotuning disabled"
        elif fam is not None:
            c, e = fam.compile or 0.0, fam.execute or 0.0
            cands = [(eta, 1.0 / eta ** depth)
                     for eta in (3, 4) for depth in (1, 2, 3)]
            scored = sorted(
                cands,
                key=lambda p: (round(self._schedule_cost(
                    p[0], p[1], c, e), 9),
                    (p[0], p[1]) != (eta_d, mf_d), p[0], -p[1]))
            chosen_eta, chosen_mf = scored[0]
            pred_c = self._schedule_cost(chosen_eta, chosen_mf, c, e)
            pred_d = self._schedule_cost(eta_d, mf_d, c, e)
            source, conf = SOURCE_MODEL, fam.confidence
            share = c / max(c + e, 1e-12)
            reason = (f"recorded family cost is {share:.0%} compile "
                      f"({fam.calls} calls): ladder minimizing "
                      f"predicted per-family search cost "
                      f"{pred_c:.2f}s (static {pred_d:.2f}s)")
        decisions = []
        for nm, chosen, ov, dflt in (
                (eta_name, chosen_eta, eta_ov, eta_d),
                (mf_name, chosen_mf, mf_ov,
                 STATIC_DEFAULTS[mf_name])):
            if ov is not None:
                decisions.append(TuningDecision(
                    nm, ov, dflt, pred_c, pred_d, conf,
                    SOURCE_OVERRIDE,
                    f"pinned by tx tune --set (store {self.path})"))
            else:
                shown = chosen if nm == eta_name else (
                    dflt if source in (SOURCE_DEFAULT, SOURCE_DISABLED)
                    else chosen)
                decisions.append(TuningDecision(
                    nm, shown, dflt, pred_c, pred_d, conf, source,
                    reason))
        eta = int(eta_ov) if eta_ov is not None else chosen_eta
        mf = float(mf_ov) if mf_ov is not None else chosen_mf
        if eta_ov is not None and mf_ov is None \
                and source in (SOURCE_DEFAULT, SOURCE_DISABLED):
            mf = 1.0 / (eta * eta)
        return eta, mf, decisions

    # -- prepare -----------------------------------------------------------
    def placement_margin(self) -> TuningDecision:
        """Host-vs-device comparison margin (override-only: the model
        keeps the plain 1.0 comparison)."""
        name = "prepare.placement_margin"
        ov = self._override(name)
        if ov is not None:
            return TuningDecision(
                name, float(ov), STATIC_DEFAULTS[name], None, None,
                "recorded", SOURCE_OVERRIDE,
                f"pinned by tx tune --set (store {self.path})")
        return self._static(
            name, "model keeps the plain steady-state comparison")

    def placement_seed(self) -> Tuple[Dict[Tuple[str, str], dict],
                                      TuningDecision]:
        """Cross-run (stage class, placement) fit records to seed a
        fresh process's PlacementPolicy, plus the decision record."""
        name = "prepare.placement_seed"
        seeds = self.model.placement_records() if self.enabled else {}
        if not seeds:
            decision = TuningDecision(
                name, (), (), None, None, DEFAULT,
                SOURCE_DISABLED if not self.enabled else SOURCE_DEFAULT,
                "TX_TUNE=off — autotuning disabled"
                if not self.enabled
                else "no placement:* records in the store yet")
            return {}, decision
        labels = tuple(sorted(f"{cls}:{where}"
                              for cls, where in seeds))
        total = sum(r["seconds"] for r in seeds.values())
        decision = TuningDecision(
            name, labels, (), None, total, "recorded", SOURCE_MODEL,
            f"seeding {len(seeds)} cross-run fit records so the first "
            f"decide_fit is data-driven instead of optimistic-device")
        return seeds, decision

    # -- audit -------------------------------------------------------------
    def waste_ceiling(self) -> TuningDecision:
        """TX-P04 padding-waste bound (override-only: the tolerable
        padded-rows-per-real-row ratio is a policy choice, not
        something the cost model can learn from timings)."""
        name = "audit.waste_ceiling"
        ov = self._override(name)
        if ov is not None:
            return TuningDecision(
                name, float(ov), STATIC_DEFAULTS[name], None, None,
                "recorded", SOURCE_OVERRIDE,
                f"pinned by tx tune --set (store {self.path})")
        return self._static(
            name, "waste tolerance is a policy choice, not learnable")

    # -- the full decision table (tx tune) ---------------------------------
    def decisions(self, max_wait_ms: float = 5.0,
                  max_batch: int = 256) -> List[TuningDecision]:
        """Every knob's resolution under the given serving context —
        the table ``tx tune`` renders (``ProfileStore.record_autotune``
        persists one)."""
        out = [self.target_batch(max_wait_ms, max_batch)]
        out.extend(self.bucket_range(max_batch))
        out.append(self.prewarm_buckets(max_batch))
        out.append(self.admission_queue_rows(max_batch))
        out.append(self.admission_quantum())
        lattice = self.bucket_lattice()
        out.append(lattice)
        out.append(self.coalesce_policy(lattice_tuned=lattice.tuned()))
        out.append(self.lattice_max_rungs())
        _eta, _mf, racing = self.racing_schedule()
        out.extend(racing)
        out.append(self.placement_margin())
        out.append(self.placement_seed()[1])
        out.append(self.waste_ceiling())
        return out
