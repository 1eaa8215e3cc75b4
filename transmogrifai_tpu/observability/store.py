"""Persisted performance-profile store: the queryable cost record the
telemetry-autotuning roadmap item consumes.

One atomic-merge JSON writer over the checkout-level
``BENCH_STATE.json`` (untracked — a store is a measurement of ONE
machine and is never committed): the per-(stage, family, bucket)
wall/compile/execute records that ``utils/compile_time`` sections and
the validator's family profile observe merge through one
read-modify-write (temp file + ``os.replace``) so concurrent writers
never tear the store and repeated runs ACCUMULATE cost history instead
of overwriting it.

Layout (top-level keys are independent namespaces)::

    {
      "profiles": {"_schema":          1,
                   "_compacted":       {keys, calls, ...},   # if capped
                   "score:b64":        {calls, wall_seconds,
                                        compile_seconds,
                                        execute_seconds, rows,
                                        updated},
                   "family:GBT":       {...},
                   "placement:...":    {...},
                   "prepare:seg:...":  {...}},
      "tuning":   {"overrides": {"serving.target_batch": 32, ...}},
      "autotune": {...}    # record_autotune: a decision trail
    }

Reserved ``profiles`` keys start with ``_`` (real labels are
colon-namespaced section names): ``_schema`` versions the block, and
``_compacted`` is the loud marker + merged remainder the key cap
leaves behind. Concurrent writers serialize their read-merge-write
through an advisory ``flock`` on ``<path>.lock`` (best-effort — the
atomic replace alone already prevents torn documents; the lock
prevents LOST records when two processes merge at once).

``TX_PROFILE_STORE`` overrides the path (tests point it at a tmp dir);
``TX_PROFILE_KEY_CAP`` overrides the growth cap (default 512 keys).
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Dict, Optional

__all__ = ["ProfileStore", "atomic_write_json", "default_store_path",
           "gather_process_profiles", "persist_process_profiles",
           "PROFILES_SCHEMA"]

#: accumulating numeric fields of one profile record; everything else
#: (``updated``, foreign keys) overwrites on merge
_ACCUMULATE = ("calls", "wall_seconds", "compile_seconds",
               "execute_seconds", "rows")

#: version stamp written into ``profiles["_schema"]`` on every merge
PROFILES_SCHEMA = 1

#: growth cap on real profile keys before deterministic merge-out
_DEFAULT_KEY_CAP = 512


@contextlib.contextmanager
def _merge_lock(path: str):
    """Advisory cross-process lock for the read-merge-write cycle —
    two concurrent ``record_profiles`` calls must not both read the
    same base state and have the second ``os.replace`` erase the
    first's merge. Best-effort: platforms/paths without ``flock``
    degrade to the unlocked (still torn-free, possibly lossy)
    behavior."""
    try:
        import fcntl
    except ImportError:  # pragma: no cover - non-posix
        yield
        return
    try:
        fh = open(path + ".lock", "a+")
    except OSError:  # pragma: no cover - read-only checkout
        yield
        return
    try:
        fcntl.flock(fh.fileno(), fcntl.LOCK_EX)
        yield
    finally:
        with contextlib.suppress(OSError):
            fcntl.flock(fh.fileno(), fcntl.LOCK_UN)
        fh.close()


def atomic_write_json(path: str, doc: dict, *, indent: int = 1,
                      fsync: bool = False) -> bool:
    """THE shared state-file writer (lint rule TX-R04 enforces its use
    in ``serving/``): serialize ``doc`` to ``path + ".tmp"``, then
    ``os.replace`` onto the live path, so a concurrent reader never
    sees a torn document and a crashed writer leaves the previous
    state intact. Returns False (after cleaning up the temp file)
    instead of raising on an unwritable target."""
    tmp = path + ".tmp"
    try:
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=indent, sort_keys=True)
            fh.write("\n")
            if fsync:
                fh.flush()
                os.fsync(fh.fileno())
        os.replace(tmp, path)
        return True
    except OSError:  # pragma: no cover - read-only checkout
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def default_store_path() -> str:
    """``TX_PROFILE_STORE`` if set, else the checkout-level
    ``BENCH_STATE.json`` at its root (untracked: whatever this
    checkout's own runs have recorded, empty on a fresh one)."""
    env = os.environ.get("TX_PROFILE_STORE")
    if env:
        return env
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), "BENCH_STATE.json")


class ProfileStore:
    """Atomic read-merge-write over one JSON file. Every mutation is a
    whole-file rewrite through a temp file + ``os.replace`` (the
    save_model idiom) so a concurrent reader never sees a torn store
    and a crashed writer leaves the previous state intact."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_store_path()

    def load(self) -> dict:
        try:
            with open(self.path, encoding="utf-8") as fh:
                d = json.load(fh)
            return d if isinstance(d, dict) else {}
        except (OSError, ValueError):
            return {}

    def _write(self, state: dict) -> bool:
        return atomic_write_json(self.path, state)

    # -- cost profiles -----------------------------------------------------
    def record_profiles(self, records: Dict[str, dict]) -> bool:
        """Accumulate ``{key: {calls, wall_seconds, compile_seconds,
        execute_seconds, rows}}`` into ``profiles`` — numeric fields
        SUM (repeated runs build history), ``updated`` stamps the last
        contribution."""
        if not records:
            return True
        with _merge_lock(self.path):
            state = self.load()
            profiles = state.setdefault("profiles", {})
            now = time.time()
            for key, rec in records.items():
                if key.startswith("_"):     # reserved namespace
                    continue
                cur = profiles.setdefault(key, {})
                for f in _ACCUMULATE:
                    if f in rec:
                        total = round(float(cur.get(f, 0.0))
                                      + float(rec[f] or 0.0), 6)
                        cur[f] = int(total) if f in ("calls", "rows") \
                            else total
                cur["updated"] = now
            profiles["_schema"] = PROFILES_SCHEMA
            self._compact(profiles, now)
            return self._write(state)

    @staticmethod
    def _compact(profiles: Dict[str, Any], now: float) -> None:
        """Growth hardening: when real keys exceed the cap
        (``TX_PROFILE_KEY_CAP``, default 512), merge out the
        oldest/lowest-calls records — deterministic order (updated
        ascending, calls ascending, key) — into the loud
        ``_compacted`` marker, so ``BENCH_STATE.json`` stays bounded
        as bench modes and tenants multiply but no cost mass is ever
        silently dropped."""
        try:
            cap = int(os.environ.get("TX_PROFILE_KEY_CAP",
                                     _DEFAULT_KEY_CAP))
        except ValueError:
            cap = _DEFAULT_KEY_CAP
        if cap <= 0:
            return
        real = [k for k in profiles if not k.startswith("_")]
        excess = len(real) - cap
        if excess <= 0:
            return
        order = sorted(real, key=lambda k: (
            float(profiles[k].get("updated", 0.0)),
            int(profiles[k].get("calls", 0) or 0), k))
        merged = profiles.setdefault("_compacted", {
            "keys": 0, "calls": 0, "wall_seconds": 0.0,
            "compile_seconds": 0.0, "execute_seconds": 0.0,
            "rows": 0})
        for key in order[:excess]:
            rec = profiles.pop(key)
            merged["keys"] = int(merged.get("keys", 0)) + 1
            for f in _ACCUMULATE:
                total = round(float(merged.get(f, 0.0))
                              + float(rec.get(f, 0.0) or 0.0), 6)
                merged[f] = int(total) if f in ("calls", "rows") \
                    else total
        merged["updated"] = now
        try:
            from ..runtime import telemetry
            telemetry.count("profiles_compacted", excess)
            telemetry.event("profiles_compacted", evicted=excess,
                            cap=cap)
        except Exception:  # pragma: no cover - telemetry optional
            pass

    def record_ir_features(self, features: Dict[str, dict]) -> bool:
        """Attach the plan auditor's per-bucket lowered-IR features
        (op count, fusion count, byte sizes, canonical fingerprint —
        analysis/audit.py) under each profile record's ``ir`` field.
        OVERWRITE semantics, unlike the accumulating cost fields: the
        IR of a (plan, bucket) program is a fact about the current
        build, not a running total — re-auditing replaces it. Keys
        match the cost records (``score:b8``, ``prepare:seg0:b512``)
        so cost-model-v2 reads features and targets off one row."""
        if not features:
            return True
        with _merge_lock(self.path):
            state = self.load()
            profiles = state.setdefault("profiles", {})
            now = time.time()
            for key, doc in features.items():
                if key.startswith("_"):     # reserved namespace
                    continue
                cur = profiles.setdefault(key, {})
                cur["ir"] = dict(doc)
                cur["updated"] = now
            profiles["_schema"] = PROFILES_SCHEMA
            self._compact(profiles, now)
            return self._write(state)

    # -- occupancy histograms (rows per dispatch, pre-padding) -------------
    def record_occupancy(self, hists: Dict[str, Dict[int, int]]) -> bool:
        """Accumulate ``{namespace: {real_rows: dispatches}}`` under
        the ``occupancy`` block — the padded cost records can never
        recover the real batch-size distribution, and the lattice
        chooser (tuning/lattice.py) needs exactly that."""
        if not any(h for h in (hists or {}).values()):
            return True
        with _merge_lock(self.path):
            state = self.load()
            occ = state.setdefault("occupancy", {})
            for ns, hist in hists.items():
                dst = occ.setdefault(str(ns), {})
                for size, count in hist.items():
                    key = str(int(size))
                    dst[key] = int(dst.get(key, 0)) + int(count)
            return self._write(state)

    def occupancy(self, namespace: str = "score") -> Dict[int, int]:
        """Cross-run rows-per-dispatch histogram for one namespace."""
        block = self.load().get("occupancy", {}).get(namespace, {})
        out: Dict[int, int] = {}
        if isinstance(block, dict):
            for size, count in block.items():
                try:
                    out[int(size)] = int(count)
                except (TypeError, ValueError):
                    continue
        return out

    def profiles(self, prefix: str = "") -> Dict[str, dict]:
        """Real (non-reserved) profile records; ``_schema`` and
        ``_compacted`` are internal — read them via :meth:`meta`."""
        return {k: dict(v) for k, v in
                self.load().get("profiles", {}).items()
                if k.startswith(prefix) and not k.startswith("_")}

    def meta(self) -> Dict[str, Any]:
        """The reserved bookkeeping of the ``profiles`` block: schema
        version and (when the key cap has triggered) the compaction
        marker."""
        block = self.load().get("profiles", {})
        return {"schema": block.get("_schema"),
                "compacted": block.get("_compacted")}

    # -- tuning overrides (tx tune --set / --reset) ------------------------
    def tuning_overrides(self) -> Dict[str, Any]:
        """The persisted override block the TuningPolicy honors."""
        block = self.load().get("tuning", {})
        ov = block.get("overrides", {})
        return dict(ov) if isinstance(ov, dict) else {}

    def set_tuning_override(self, knob: str, value: Any) -> bool:
        with _merge_lock(self.path):
            state = self.load()
            block = state.setdefault("tuning", {})
            block.setdefault("overrides", {})[knob] = value
            block["updated"] = time.time()
            return self._write(state)

    def clear_tuning_overrides(self, knob: Optional[str] = None
                               ) -> bool:
        """Drop one override (or all, ``knob=None``)."""
        with _merge_lock(self.path):
            state = self.load()
            block = state.get("tuning", {})
            if knob is None:
                block.pop("overrides", None)
            else:
                block.get("overrides", {}).pop(knob, None)
            block["updated"] = time.time()
            state["tuning"] = block
            return self._write(state)

    # -- named diagnostic blocks -------------------------------------------
    def record_section(self, name: str, doc: dict) -> bool:
        """Persist one named, timestamped diagnostic block (e.g.
        ``aot_restart``) wholesale. Callers own the namespace — pick a
        name that is not one of the structural blocks (``profiles``,
        ``tuning``, ``autotune``)."""
        with _merge_lock(self.path):
            state = self.load()
            out = dict(doc)
            out["time"] = time.time()
            state[str(name)] = out
            return self._write(state)

    # -- autotune decision trail -------------------------------------------
    def record_autotune(self, doc: dict) -> bool:
        """Persist a full TuningDecision list + tuned-vs-static deltas,
        so the perf trajectory records WHY a knob moved, not just that
        it did."""
        with _merge_lock(self.path):
            state = self.load()
            out = dict(doc)
            out["time"] = time.time()
            state["autotune"] = out
            return self._write(state)


def gather_process_profiles() -> Dict[str, dict]:
    """Everything this process has measured so far, keyed for the
    store:

    - ``utils/compile_time`` sections (``prepare:*`` fit/segment
      labels, ``score:<plan>:b<bucket>`` dispatch labels — plan ids
      are process-local, so bucket labels normalize to
      ``score:b<bucket>``),
    - the validator's per-family compile/wall profile
      (``family:<Name>``),
    - the fit-placement policy's measured (stage class, host|device)
      records (``placement:<Class>:<where>`` — what this process
      MEASURED, never the cross-run seeds it loaded), so the cost
      model and future processes see placement history.
    """
    from ..utils.compile_time import seconds_by_section
    out: Dict[str, dict] = {}

    def _acc(key: str, wall: float, compile_s: float, calls: int,
             rows: int = 0) -> None:
        rec = out.setdefault(key, {"calls": 0, "wall_seconds": 0.0,
                                   "compile_seconds": 0.0,
                                   "execute_seconds": 0.0, "rows": 0})
        rec["calls"] += int(calls)
        rec["wall_seconds"] += float(wall)
        rec["compile_seconds"] += float(compile_s)
        rec["execute_seconds"] += max(float(wall) - float(compile_s),
                                      0.0)
        rec["rows"] += int(rows)

    for label, rec in seconds_by_section().items():
        parts = label.split(":")
        if len(parts) == 3 and parts[2].startswith("b") \
                and parts[1].isdigit():
            label = f"{parts[0]}:{parts[2]}"     # strip the plan id
        _acc(label, rec["seconds"], rec["compile"], rec["calls"])

    try:
        from ..selector.validator import family_profile
        for row in family_profile():
            _acc(f"family:{row['family']}", row["seconds"],
                 row["compileSeconds"], row["calls"])
    except Exception:  # pragma: no cover - selector not imported yet
        pass

    try:
        from ..plans.placement import placement_report
        for row in placement_report():
            _acc(f"placement:{row['stage']}:{row['placement']}",
                 row["seconds"], row["compileSeconds"], row["calls"],
                 row["rows"])
    except Exception:  # pragma: no cover - plans not imported yet
        pass
    return out


def persist_process_profiles(path: Optional[str] = None
                             ) -> Dict[str, dict]:
    """Gather + merge this process's cost records into the store; the
    bench modes call this after measuring, and a traced ``tx serve``
    session calls it at shutdown. Returns what was merged."""
    records = gather_process_profiles()
    store = ProfileStore(path)
    store.record_profiles(records)
    try:
        # plan-auditor IR features (analysis/audit.py): any audit run
        # in this process leaves per-bucket op/fusion/bytes features —
        # merge them onto the same rows so cost-model-v2 has training
        # features next to the recorded costs from day one
        from ..analysis.audit import process_ir_features
        store.record_ir_features(process_ir_features())
    except Exception:  # pragma: no cover - analysis layer optional
        pass
    try:
        # real rows-per-dispatch histograms (plans/common.py
        # record_rows): the occupancy side of the lattice decision
        from ..plans.common import row_histograms
        store.record_occupancy(row_histograms())
    except Exception:  # pragma: no cover - plans not imported yet
        pass
    return records
