"""The tunable-knob registry: the SINGLE home of every performance
default the autotuner may move.

Every knob that shapes a hot path — the serving coalescer target, the
ScoringPlan bucket range, the racing ``eta``/``min_fidelity`` schedule,
the host-vs-device placement margin — is declared HERE, once, with its
static default. Consumers import the default from
:data:`STATIC_DEFAULTS` instead of re-stating the number; lint rule
TX-T01 (lint/rules_jax.py) enforces that a numeric literal default for
a registered knob outside ``tuning/`` is an error, so a knob can never
fork into two disagreeing copies the :class:`~.policy.TuningPolicy`
doesn't know about.

This module is a LEAF: stdlib only, no jax, no observability imports —
``plans/common.py`` and ``serving/server.py`` import it at module
scope, and the lint rules import the registered-name sets.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

__all__ = ["Knob", "KNOBS", "STATIC_DEFAULTS", "static_default",
           "TUNABLE_CONST_NAMES", "TUNABLE_PARAM_NAMES",
           "TUNABLE_PARAM_SCOPES"]


@dataclass(frozen=True)
class Knob:
    """One registered tunable: its identity, static default and the
    layer that consumes the decision."""
    name: str
    default: Any
    consumer: str
    kind: str          # int | float | str | int_pair | int_tuple
    description: str
    #: the constant / parameter spellings TX-T01 polices for this knob
    const_names: Tuple[str, ...] = ()
    param_names: Tuple[str, ...] = ()


#: the registry — ordering is the ``tx tune`` display order
KNOBS: Tuple[Knob, ...] = (
    Knob(name="serving.target_batch", default=64,
         consumer="serving/server.py ServingServer._target_batch",
         kind="int",
         description="coalescer target batch when the plan has no "
                     "local bucket profile yet (deadline-or-full's "
                     "'full')",
         const_names=("_DEFAULT_TARGET", "DEFAULT_TARGET_BATCH"),
         param_names=()),
    Knob(name="serving.min_bucket", default=8,
         consumer="plans/common.py bucket_for / serving ScoringPlan",
         kind="int",
         description="smallest padded power-of-two batch — "
                     "single-record requests share one program",
         const_names=("DEFAULT_MIN_BUCKET",),
         param_names=()),
    Knob(name="serving.max_bucket", default=8192,
         consumer="plans/common.py bucket_for / serving ScoringPlan",
         kind="int",
         description="largest padded batch — bigger inputs chunk so "
                     "compiles stay bounded at log2(max/min)+1 "
                     "programs per plan",
         const_names=("DEFAULT_MAX_BUCKET",),
         param_names=()),
    Knob(name="serving.prewarm", default=(),
         consumer="serving/server.py ServingServer.prewarm",
         kind="int_tuple",
         description="bucket sizes pre-compiled before traffic — "
                     "empty means no prewarm (today's behavior); the "
                     "policy fills it from the store's recorded "
                     "dispatch shapes",
         const_names=(), param_names=()),
    Knob(name="serving.admission_queue_rows", default=512,
         consumer="serving/admission.py AdmissionController",
         kind="int",
         description="per-(model, tenant) lane admission bound in "
                     "queued rows — arrivals beyond it are shed with "
                     "a retry_after_ms hint; the policy sizes it so "
                     "the worst-case backlog drains in ~250ms at the "
                     "recorded dispatch rate",
         const_names=("DEFAULT_ADMISSION_QUEUE_ROWS",),
         param_names=()),
    Knob(name="serving.admission_quantum", default=32,
         consumer="serving/admission.py AdmissionController",
         kind="int",
         description="deficit-round-robin quantum in rows credited "
                     "per tenant visit of the dispatch-grant ring — "
                     "larger favors batch throughput, smaller favors "
                     "fairness granularity",
         const_names=("DEFAULT_ADMISSION_QUANTUM",),
         param_names=()),
    Knob(name="serving.coalesce_policy", default="deadline_or_full",
         consumer="serving/server.py ServingServer._collect",
         kind="str",
         description="how the coalescer closes a batch: "
                     "'deadline_or_full' (the fixed rule — dispatch at "
                     "the wait deadline or the target fill) or "
                     "'predicted_cost' (additionally split the popped "
                     "batch at a lattice rung when the cost model's "
                     "predicted per-row marginal cost says the smaller "
                     "dispatch is cheaper)",
         const_names=("DEFAULT_COALESCE_POLICY",),
         param_names=()),
    Knob(name="tuning.lattice_max_rungs", default=12,
         consumer="tuning/lattice.py choose_lattice",
         kind="int",
         description="rung bound for tuned non-power-of-two bucket "
                     "lattices — caps per-plan compiles exactly like "
                     "the log2(max/min)+1 bound the default ladder "
                     "carries (11 rungs at 8..8192)",
         const_names=("DEFAULT_LATTICE_MAX_RUNGS",),
         param_names=()),
    Knob(name="search.eta", default=3,
         consumer="selector/racing.py RacingCrossValidation",
         kind="int",
         description="racing promotion ratio: each rung keeps the "
                     "top 1/eta",
         const_names=("DEFAULT_ETA",),
         param_names=("eta",)),
    Knob(name="search.min_fidelity", default=None,
         consumer="selector/racing.py RacingCrossValidation",
         kind="float",
         description="budget fraction of the first racing rung (None "
                     "derives the classic 1/eta**2 three-rung "
                     "ladder); the final rung is ALWAYS exact full "
                     "CV regardless",
         const_names=("DEFAULT_MIN_FIDELITY",),
         param_names=("min_fidelity",)),
    Knob(name="prepare.placement_margin", default=1.0,
         consumer="plans/placement.py PlacementPolicy.decide_fit",
         kind="float",
         description="host-vs-device comparison margin: the device "
                     "fit wins while steady-state device seconds <= "
                     "margin * host seconds (1.0 = plain comparison, "
                     "today's rule)",
         const_names=("DEFAULT_PLACEMENT_MARGIN",),
         param_names=("placement_margin",)),
    Knob(name="audit.waste_ceiling", default=16.0,
         consumer="analysis/rules.py TX-P04 padding-waste bound",
         kind="float",
         description="max tolerated padded_rows/real_rows ratio per "
                     "bucket (vs the ProfileStore's recorded "
                     "occupancy) before the plan auditor's TX-P04 "
                     "escalates to ERROR — 16.0 tolerates the "
                     "worst-case single-row-in-min-bucket shape while "
                     "catching systematically mis-sized ladders",
         const_names=("DEFAULT_WASTE_CEILING",),
         param_names=("waste_ceiling",)),
)

#: knob name -> static default; THE values consumers import. An entry
#: here is what "bitwise identical to static defaults" means for an
#: empty store / TX_TUNE=off.
STATIC_DEFAULTS: Dict[str, Any] = {k.name: k.default for k in KNOBS}

#: module-level constant spellings TX-T01 polices (a numeric literal
#: assigned to one of these outside tuning/ is a forked default)
TUNABLE_CONST_NAMES = frozenset(
    n for k in KNOBS for n in k.const_names)

#: function-parameter spellings TX-T01 polices (a numeric literal
#: default for one of these outside tuning/ bypasses the policy)
TUNABLE_PARAM_NAMES = frozenset(
    n for k in KNOBS for n in k.param_names)

#: param spelling -> the consumer packages where TX-T01 polices it.
#: Scope discipline: ``eta`` is ALSO a gradient-boosting learning rate
#: (models/trees/estimators.py) — only in the knob's own consumer layer does the
#: spelling mean the registered knob.
TUNABLE_PARAM_SCOPES: Dict[str, frozenset] = {}
for _k in KNOBS:
    _pkg = _k.consumer.split("/", 1)[0]
    for _n in _k.param_names:
        TUNABLE_PARAM_SCOPES[_n] = (
            TUNABLE_PARAM_SCOPES.get(_n, frozenset()) | {_pkg})
del _k, _pkg, _n


def knob(name: str) -> Optional[Knob]:
    for k in KNOBS:
        if k.name == name:
            return k
    return None


def static_default(name: str) -> Any:
    if name not in STATIC_DEFAULTS:
        raise KeyError(f"unknown tunable knob {name!r}; registered: "
                       f"{sorted(STATIC_DEFAULTS)}")
    return STATIC_DEFAULTS[name]
