"""Error classes + classifier for the fault-tolerant runtime.

The Spark reference leans on executor-level fault tolerance: a lost
worker re-runs its tasks, a sick executor is blacklisted, and the
driver aggregates what survived. The JAX port has no executors — a
raised `XlaRuntimeError` in one family's dispatch thread used to kill
the whole ``Workflow.train``. This module restores the *triage* half
of that machinery: every exception crossing a family-dispatch or
compile boundary is classified into one of three buckets:

- ``"transient"`` — preemption/RESOURCE_EXHAUSTED/UNAVAILABLE-shaped
  backend errors: worth retrying with backoff (runtime/retry.py);
  after retries are exhausted the family is quarantined.
- ``"family"`` — deterministic family-scoped failures (compile
  rejections, precondition violations, a poisoned metric matrix):
  retrying is futile; the family is quarantined immediately and the
  search continues with survivors.
- ``"bug"`` — everything else. A genuine code defect must PROPAGATE,
  not be silently absorbed into a quarantine record (the same
  discipline lint rule TX-R01 enforces statically on ``except``
  blocks in the selector/serving hot paths).

Classification is structural (type names + message patterns), not
``isinstance``-against-jaxlib: the classifier must work identically
whether the error came from a real TPU runtime, a CPU test process, or
the deterministic fault injector (runtime/faults.py).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import List, Optional

__all__ = ["classify_error", "QuarantineRecord", "AllFamiliesFailedError",
           "TRANSIENT", "FAMILY", "BUG"]

TRANSIENT = "transient"
FAMILY = "family"
BUG = "bug"

#: backend error shapes worth retrying: resource pressure that may
#: clear (another family just freed its HBM), preempted/restarting
#: workers, flaky transport. Mirrors the gRPC/absl status names the
#: TPU runtime stamps into XlaRuntimeError messages.
_TRANSIENT_RE = re.compile(
    r"RESOURCE_EXHAUSTED|DEADLINE_EXCEEDED|UNAVAILABLE|ABORTED"
    r"|preempt(?:ed|ion)?|out of memory|allocat\w* failure"
    r"|connection (?:reset|refused|closed)|socket closed"
    r"|temporarily unavailable",
    re.IGNORECASE)

#: deterministic family-scoped failure shapes: the backend rejected
#: THIS program/data and will again (compile failures, numerical
#: blow-ups surfacing as runtime errors).
_FAMILY_RE = re.compile(
    r"INTERNAL|INVALID_ARGUMENT|FAILED_PRECONDITION|UNIMPLEMENTED"
    r"|compilation fail|lowering fail|injected family fault",
    re.IGNORECASE)

#: python-level exception types that behave like transient infra
#: failures regardless of message
_TRANSIENT_TYPES = (ConnectionError, TimeoutError, InterruptedError,
                    BrokenPipeError)


def _type_names(exc: BaseException) -> List[str]:
    return [c.__name__ for c in type(exc).__mro__]


def classify_error(exc: BaseException) -> str:
    """``"transient"`` / ``"family"`` / ``"bug"`` for one exception.

    ``XlaRuntimeError`` (matched by type NAME so jaxlib need not be
    importable) is never a "bug": the program crossed the compile
    bridge, so the defect is family-scoped at worst — transient when
    the status code says so, quarantinable otherwise."""
    if isinstance(exc, _TRANSIENT_TYPES):
        return TRANSIENT
    msg = f"{type(exc).__name__}: {exc}"
    if _TRANSIENT_RE.search(msg):
        return TRANSIENT
    names = _type_names(exc)
    if isinstance(exc, MemoryError):
        return FAMILY
    if "XlaRuntimeError" in names:
        return FAMILY if not _TRANSIENT_RE.search(msg) else TRANSIENT
    from ..models.base import FamilyPreconditionError
    if isinstance(exc, (FamilyPreconditionError, FloatingPointError)):
        return FAMILY
    if _FAMILY_RE.search(msg):
        return FAMILY
    return BUG


@dataclass
class QuarantineRecord:
    """One family removed from a search, and why — surfaced in
    ``ModelSelectorSummary.quarantined`` and ``model_insights()``."""
    family: str
    reason: str
    kind: str = FAMILY          # "transient" | "family" | "deadline" | "metrics"
    error_type: str = ""
    rung: Optional[int] = None
    retries: int = 0

    def to_json(self) -> dict:
        out = {"family": self.family, "reason": self.reason,
               "kind": self.kind, "errorType": self.error_type,
               "retries": self.retries}
        if self.rung is not None:
            out["rung"] = self.rung
        return out

    @classmethod
    def from_json(cls, d: dict) -> "QuarantineRecord":
        return cls(family=d.get("family", ""), reason=d.get("reason", ""),
                   kind=d.get("kind", FAMILY),
                   error_type=d.get("errorType", ""),
                   rung=d.get("rung"), retries=d.get("retries", 0))

    def __str__(self) -> str:
        tag = f" at rung {self.rung}" if self.rung is not None else ""
        return (f"{self.family}{tag}: [{self.kind}] {self.reason}"
                + (f" (after {self.retries} retries)" if self.retries
                   else ""))


class AllFamiliesFailedError(RuntimeError):
    """Every candidate family was quarantined (or produced no finite
    metric): there is nothing left to select. Raised ONCE with the full
    aggregated quarantine ledger instead of whichever family happened
    to die first — the operator sees every failure reason in one
    traceback."""

    def __init__(self, records: List[QuarantineRecord],
                 detail: str = ""):
        self.records = list(records)
        lines = "\n".join(f"  - {r}" for r in self.records) or "  (none)"
        super().__init__(
            f"all candidate families failed validation"
            + (f" ({detail})" if detail else "")
            + f"; quarantine ledger:\n{lines}")
