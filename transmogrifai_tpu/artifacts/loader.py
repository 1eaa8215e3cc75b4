"""Artifact loading: deserialize instead of compile
(docs/aot_artifacts.md).

:func:`load_or_compile` is the ONE sanctioned way for serving and CLI
code to turn a model into a compiled :class:`~..serving.plan.ScoringPlan`
(lint rule TX-R06 flags direct ``ScoringPlan(...).compile()`` call
sites in those trees). It builds the plan (trace only — building the
jitted fn compiles nothing), then tries to attach the model dir's
AOT-compiled executables per bucket. On the happy path the serve
process never invokes XLA.

Every validity failure falls back to live compile LOUDLY — its own
telemetry counter + one ``serve_aot_fallback`` event — and never
raises (except ``require`` mode, the fleet-replica contract):

==================  =====================================================
fallback class      meaning
==================  =====================================================
``missing``         no artifact store in the model dir (legacy save,
                    export disabled, or crash before manifest)
``jax_version``     artifacts compiled under a different jax
``platform``        different backend, or a different CPU machine
                    fingerprint (XLA:CPU code is host-ISA-specific)
``fingerprint``     canonical plan fingerprint drift — the program
                    this environment lowers differs from the exported
                    one (kernel edit since save)
``bucket_ladder``   this plan dispatches buckets the store does not
                    cover (the tuning knob moved past the exported
                    range) — covered buckets still load; a serving
                    ladder that is a SUBSET of the exported one is the
                    normal healthy case and no fallback at all
``torn``            checksum/deserialize failure on ANY entry — the
                    whole store is discarded (audit-cache poisoning
                    contract), loud stderr
==================  =====================================================
"""
from __future__ import annotations

import collections
import logging
import sys
from typing import Any, Dict, Optional, Tuple

from ..runtime import telemetry as _telemetry
from . import store as _store

_log = logging.getLogger(__name__)

__all__ = ["ArtifactsRequired", "load_or_compile",
           "load_scoring_artifacts", "seed_prepare_registry",
           "prepare_executable", "clear_prepare_registry"]


class ArtifactsRequired(RuntimeError):
    """``TX_AOT_ARTIFACTS=require`` (or ``tx serve --artifacts
    require``) and a model could not load valid artifacts — a fleet
    replica that would otherwise compile in-band refuses to boot."""


def record_aot_fallback(reason: str, model_dir: Optional[str],
                        **fields: Any) -> None:
    """The loud-degradation contract (TX-R01 vocabulary): every
    artifact miss is a counted, evented, logged fallback to live
    compile — visible in metrics_snapshot()['counters'] and the
    warm-restart snapshot."""
    _telemetry.count("serve_aot_fallbacks")
    _telemetry.count(f"serve_aot_fallback_{reason}")
    _telemetry.event("serve_aot_fallback", reason=reason,
                     model_dir=model_dir or "", **fields)
    _log.warning("AOT artifacts unavailable (%s) for %s — falling "
                 "back to live compile%s", reason, model_dir or
                 "<in-memory model>",
                 "".join(f"; {k}={v}" for k, v in fields.items()))


def _poison(model_dir: str, why: str) -> None:
    """Torn/tampered store: discard EVERYTHING (never serve a mix of
    loaded and suspect programs) — the audit-cache poisoning idiom."""
    print(f"tx-artifacts: WARNING: artifact store poisoned ({why}) — "
          f"discarding {_store.artifact_dir(model_dir)} contents and "
          f"live-compiling every bucket", file=sys.stderr)


def _execution_devices():
    """The one device an unsharded plan program is compiled for and
    dispatched on. ``deserialize_and_load`` otherwise binds the
    executable to EVERY device of the backend, and a one-device program
    loaded over N devices fails at dispatch ("expected N shards") —
    on any multi-chip host."""
    import jax
    return jax.devices()[:1]


def _tree_defs(plan, bucket: int, n_outputs: int):
    """Recompute the serialized executable's calling-convention pytree
    defs from the plan itself — deterministic, so they are never
    persisted (export._serialize drops them)."""
    import jax.tree_util as jtu
    inputs, mask = plan.device_input_avals(int(bucket))
    in_tree = jtu.tree_structure(((inputs, mask), {}))
    out_tree = jtu.tree_structure(tuple(range(int(n_outputs))))
    return in_tree, out_tree


def _check_key(plan, manifest: dict) -> Optional[Tuple[str, dict]]:
    """Validity key comparison; ``(fallback_class, detail)`` on the
    first mismatch, None when the store is valid for this process."""
    env = _store.env_stamp()
    if str(manifest.get("jax")) != env["jax"]:
        return "jax_version", {"saved": str(manifest.get("jax")),
                               "current": env["jax"]}
    if str(manifest.get("platform")) != env["platform"]:
        return "platform", {"saved": str(manifest.get("platform")),
                            "current": env["platform"]}
    if str(manifest.get("machine")) != env["machine"]:
        return "platform", {"detail": "machine fingerprint",
                            "saved": str(manifest.get("machine"))[:12],
                            "current": env["machine"][:12]}
    return None


def _current_fingerprint(plan, model_dir: str) -> Optional[str]:
    """The plan's canonical fingerprint in THIS environment, through
    the PR-16 audit cache (pure hashing on a warm boot — the cache was
    seeded at save time)."""
    try:
        from ..analysis.audit import _fingerprint_via_cache
        return _fingerprint_via_cache(plan.model, model_dir)
    except Exception as e:
        _log.warning("AOT artifacts: fingerprint not computable "
                     "(%s: %s)", type(e).__name__, e)
        return None


def load_scoring_artifacts(plan, model_dir: str
                           ) -> Tuple[Optional[Dict[int, Any]],
                                      Optional[dict]]:
    """Deserialize the model dir's scoring executables for ``plan``.
    Returns ``({bucket: Compiled}, manifest)`` on success or
    ``(None, None)`` after a counted fallback. Never raises."""
    manifest, state = _store.read_manifest(model_dir)
    if manifest is None:
        if state == "torn":
            _poison(model_dir, "unreadable manifest")
        record_aot_fallback("torn" if state == "torn" else "missing",
                            model_dir)
        return None, None
    mismatch = _check_key(plan, manifest)
    if mismatch is not None:
        reason, detail = mismatch
        record_aot_fallback(reason, model_dir, **detail)
        return None, None
    # bucket coverage: the store must cover the ladder THIS plan will
    # dispatch. The serving side tunes its ladder to a subrange of the
    # export-time default (tuning/policy.bucket_range), so a SUBSET is
    # the normal healthy case — zero compiles. Buckets the store lacks
    # (tuning knob moved past the exported range, or a hand-edited
    # ladder) degrade loudly: the overlap still loads, the missing
    # buckets live-compile on first dispatch.
    exported = {int(e.get("bucket", 0))
                for e in (manifest.get("score") or {}).values()}
    wanted = [int(b) for b in plan.buckets()]
    missing = [b for b in wanted if b not in exported]
    if missing:
        record_aot_fallback(
            "bucket_ladder", model_dir,
            saved=sorted(exported), current=wanted, missing=missing)
        if len(missing) == len(wanted):
            return None, None
    expected = manifest.get("fingerprint")
    current = _current_fingerprint(plan, model_dir)
    if current is None or current != expected:
        record_aot_fallback("fingerprint", model_dir,
                            saved=str(expected),
                            current=str(current))
        return None, None
    from jax.experimental import serialize_executable as _se
    n_outputs = int(manifest.get("nOutputs", 0))
    execs: Dict[int, Any] = {}
    for label, entry in sorted((manifest.get("score") or {}).items()):
        bucket = int(entry.get("bucket", 0))
        if bucket not in wanted:
            continue            # exported superset: not dispatchable here
        payload = _store.read_payload(model_dir, entry)
        if payload is None:
            _poison(model_dir, f"checksum/read failure on {label}")
            record_aot_fallback("torn", model_dir, entry=label)
            return None, None
        try:
            in_tree, out_tree = _tree_defs(plan, bucket, n_outputs)
            execs[bucket] = _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=_execution_devices())
        except Exception as e:
            _poison(model_dir,
                    f"deserialize failure on {label}: "
                    f"{type(e).__name__}")
            record_aot_fallback("torn", model_dir, entry=label,
                                error=f"{type(e).__name__}: {e}")
            return None, None
    if not execs:
        record_aot_fallback("missing", model_dir,
                            detail="manifest has no scoring entries")
        return None, None
    _telemetry.count("serve_aot_loads")
    _telemetry.count("serve_aot_loaded_buckets", len(execs))
    _telemetry.event("serve_aot_loaded", model_dir=model_dir,
                     buckets=sorted(execs))
    return execs, manifest


def load_or_compile(model, model_dir: Optional[str] = None,
                    require: Optional[bool] = None,
                    **plan_kwargs: Any):
    """Build + compile a ScoringPlan for ``model``, attaching the
    model dir's AOT artifacts when valid — THE serving/CLI entry point
    (TX-R06). ``model_dir`` defaults to the dir the model was saved
    to / loaded from (``model.model_dir``); an in-memory model with no
    dir live-compiles silently (there is nothing to have loaded).
    ``require=True`` (or ``TX_AOT_ARTIFACTS=require``) raises
    :class:`ArtifactsRequired` instead of falling back."""
    from ..serving.plan import ScoringPlan
    plan = ScoringPlan(model, **plan_kwargs).compile()  # tx-lint: disable=TX-R06 (this IS the artifact loader)
    mode = _store.load_mode()
    if require is None:
        require = mode == "require"
    if mode == "off":
        return plan
    mdir = model_dir or getattr(model, "model_dir", None)
    if not mdir:
        if require:
            raise ArtifactsRequired(
                "artifacts required but the model has no model dir "
                "to load them from")
        return plan
    if not getattr(plan, "_device_steps", None):
        return plan             # host-only plan: nothing to load
    execs, manifest = load_scoring_artifacts(plan, mdir)
    if execs is None:
        if require:
            raise ArtifactsRequired(
                f"artifacts required but {mdir} has no valid artifact "
                f"store for this environment (see the "
                f"serve_aot_fallback event for the class)")
        return plan
    plan.attach_artifacts(execs, manifest)
    seed_prepare_registry(mdir, manifest=manifest)
    return plan


# ---------------------------------------------------------------------------
# prepare-segment registry (plans/prepare.py consults it per dispatch)
# ---------------------------------------------------------------------------

#: (segment signature digest, bucket) -> deserialized executable.
#: Bounded LRU like the in-process segment cache — a long-lived
#: lifecycle process seeds one model zoo's worth, not unbounded.
_PREPARE_REGISTRY: "collections.OrderedDict[Tuple[str, int], Any]" = \
    collections.OrderedDict()
_PREPARE_REGISTRY_MAX = 128


def prepare_executable(sig_digest: Optional[str],
                       bucket: int) -> Optional[Any]:
    """The AOT executable for one (segment signature, bucket), or
    None — the prepare plan's per-dispatch lookup (plans/prepare.py).
    """
    if sig_digest is None or _store.load_mode() == "off":
        return None
    hit = _PREPARE_REGISTRY.get((sig_digest, int(bucket)))
    if hit is not None:
        _PREPARE_REGISTRY.move_to_end((sig_digest, int(bucket)))
    return hit


def clear_prepare_registry() -> None:
    _PREPARE_REGISTRY.clear()


def seed_prepare_registry(model_dir: str,
                          manifest: Optional[dict] = None) -> int:
    """Deserialize a model dir's prepare-segment artifacts into the
    process registry so the NEXT train of a state-identical workflow
    (the lifecycle retrain path) dispatches without compiling. Torn
    entries are skipped loudly (the scoring store's validity was
    already checked when this is called from load_or_compile).
    Returns the number of executables seeded."""
    if _store.load_mode() == "off":
        return 0
    if manifest is None:
        manifest, _state = _store.read_manifest(model_dir)
        if manifest is None:
            return 0
        if _check_key_env_only(manifest):
            return 0
    import numpy as np
    import jax
    import jax.tree_util as jtu
    from jax.experimental import serialize_executable as _se
    seeded = 0
    for label, entry in sorted((manifest.get("prepare") or {}).items()):
        sig = entry.get("sig")
        bucket = int(entry.get("bucket", 0))
        if not sig or (sig, bucket) in _PREPARE_REGISTRY:
            continue
        payload = _store.read_payload(model_dir, entry)
        if payload is None:
            record_aot_fallback("torn", model_dir, entry=label)
            continue
        try:
            avals = tuple(
                jax.ShapeDtypeStruct((bucket,) + tuple(shape),
                                     np.dtype(dtype))
                for shape, dtype in entry.get("inAvals") or ())
            mask = jax.ShapeDtypeStruct((bucket,), np.float64)
            in_tree = jtu.tree_structure(((avals, mask), {}))
            out_tree = jtu.tree_structure(
                tuple(range(int(entry.get("nOutputs", 0)))))
            ex = _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=_execution_devices())
        except Exception as e:
            record_aot_fallback("torn", model_dir, entry=label,
                                error=f"{type(e).__name__}: {e}")
            continue
        _PREPARE_REGISTRY[(sig, bucket)] = ex
        _PREPARE_REGISTRY.move_to_end((sig, bucket))
        seeded += 1
    while len(_PREPARE_REGISTRY) > _PREPARE_REGISTRY_MAX:
        _PREPARE_REGISTRY.popitem(last=False)
    if seeded:
        _telemetry.count("serve_aot_prepare_seeded", seeded)
        _telemetry.event("serve_aot_prepare_seeded",
                         model_dir=model_dir, executables=seeded)
    return seeded


def _check_key_env_only(manifest: dict) -> bool:
    """True when the manifest's ENVIRONMENT key mismatches this
    process (the plan-independent half of _check_key — what a
    standalone prepare-registry seed can verify)."""
    env = _store.env_stamp()
    return (str(manifest.get("jax")) != env["jax"]
            or str(manifest.get("platform")) != env["platform"]
            or str(manifest.get("machine")) != env["machine"])
