"""``python -m transmogrifai_tpu.cli artifacts`` — inspect / verify /
re-export a saved model's AOT artifact store (docs/aot_artifacts.md).

Lists the store's validity key (jax version, platform, machine
fingerprint, canonical plan fingerprint, bucket ladder) and every
scoring-bucket / prepare-segment entry with its size and checksum
state. ``--verify`` additionally replays the loader's full validity
check against THIS environment — the answer to "will the serve process
on this host compile, or load?" — and exits 0 valid / 1 invalid /
2 internal error. ``--export`` (re-)compiles and swaps in a fresh
store for the current environment: the repair path after a jax
upgrade, platform move, or kernel edit.

    tx artifacts MODEL_DIR                  # key + entry table
    tx artifacts MODEL_DIR --verify         # would this host load it?
    tx artifacts MODEL_DIR --export         # re-export for this env
    tx artifacts MODEL_DIR --format json    # machine-readable
"""
from __future__ import annotations

import json
import os
import sys
from typing import List, Optional, Tuple

__all__ = ["add_artifacts_parser", "run_artifacts"]


def add_artifacts_parser(sub) -> None:
    ar = sub.add_parser(
        "artifacts",
        help="inspect/verify/re-export a saved model's AOT-compiled "
             "plan artifacts (exit 0 valid / 1 invalid / 2 error)")
    ar.add_argument("model_dir",
                    help="saved model directory (WorkflowModel.save)")
    ar.add_argument("--verify", action="store_true",
                    help="replay the loader's validity check against "
                         "this environment: checksums, jax/platform/"
                         "machine key, bucket ladder, canonical plan "
                         "fingerprint")
    ar.add_argument("--export", action="store_true",
                    help="(re-)export artifacts for the CURRENT "
                         "environment — AOT-compiles every bucket and "
                         "swaps the store in atomically")
    ar.add_argument("--format", choices=["text", "json"],
                    default="text", help="output format (default: text)")


def _entry_rows(model_dir: str, manifest: dict,
                check: bool) -> Tuple[List[tuple], int]:
    """(table rows, bad-entry count). ``check`` re-reads every payload
    through the checksum gate; otherwise the sha column is trusted."""
    from ..artifacts import store as _store
    rows, bad = [], 0
    for kind in ("score", "prepare"):
        for label, entry in sorted((manifest.get(kind) or {}).items()):
            if check:
                ok = _store.read_payload(model_dir, entry) is not None
                bad += 0 if ok else 1
                status = "ok" if ok else "TORN"
            else:
                status = "-"
            rows.append((kind, label, str(entry.get("bucket", "?")),
                         str(entry.get("bytes", "?")),
                         str(entry.get("sha256", ""))[:12], status))
    return rows, bad


def _key_checks(model_dir: str, manifest: dict) -> List[dict]:
    """The loader's validity key, check by check — each dict carries
    ``{check, saved, current, ok}`` (docs/aot_artifacts.md fallback
    matrix)."""
    from ..artifacts import store as _store
    env = _store.env_stamp()
    checks = [
        {"check": "jax_version", "saved": str(manifest.get("jax")),
         "current": env["jax"]},
        {"check": "platform", "saved": str(manifest.get("platform")),
         "current": env["platform"]},
        {"check": "machine", "saved": str(manifest.get("machine")),
         "current": env["machine"]},
    ]
    for c in checks:
        c["ok"] = c["saved"] == c["current"]
    try:
        from ..workflow.persistence import load_model
        model = load_model(model_dir)
        from ..serving.plan import ScoringPlan
        ladder = [int(b) for b in ScoringPlan(model).buckets()]
        exported = sorted(int(e.get("bucket", 0)) for e in
                          (manifest.get("score") or {}).values())
        # subset coverage is the loader's contract: the (possibly
        # tuned) serving ladder must be covered, not equal
        checks.append({"check": "bucket_ladder",
                       "saved": exported, "current": ladder,
                       "ok": set(ladder) <= set(exported)})
        from ..analysis.audit import _fingerprint_via_cache
        fp = _fingerprint_via_cache(model, model_dir)
        checks.append({"check": "fingerprint",
                       "saved": str(manifest.get("fingerprint")),
                       "current": str(fp),
                       "ok": str(manifest.get("fingerprint")) == str(fp)})
    except Exception as e:            # model unloadable != torn store
        checks.append({"check": "model_load",
                       "saved": "-",
                       "current": f"{type(e).__name__}: {e}",
                       "ok": False})
    return checks


def _format_text(model_dir: str, manifest: dict, rows, bad: int,
                 checks: Optional[List[dict]]) -> Tuple[str, int]:
    from ..artifacts.store import manifest_summary
    s = manifest_summary(manifest) or {}
    lines = [f"artifact store: {model_dir}",
             f"  jax={s.get('jax')} platform={s.get('platform')} "
             f"machine={str(manifest.get('machine'))[:12]}",
             f"  fingerprint={s.get('fingerprint')}",
             f"  buckets={s.get('buckets')} "
             f"prepareSegments={s.get('prepareSegments')}",
             ""]
    table = [("kind", "entry", "bucket", "bytes", "sha256", "check")]
    table += [tuple(r) for r in rows]
    widths = [max(len(r[i]) for r in table)
              for i in range(len(table[0]))]
    lines += ["  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
              for r in table]
    rc = 0
    if checks is not None:
        lines.append("")
        failed = [c for c in checks if not c["ok"]]
        for c in checks:
            mark = "ok " if c["ok"] else "FAIL"
            lines.append(f"{mark} {c['check']}: saved={c['saved']} "
                         f"current={c['current']}")
        if bad or failed:
            what = [f"{bad} torn entr{'y' if bad == 1 else 'ies'}"] \
                if bad else []
            what += [c["check"] for c in failed]
            lines.append(f"INVALID for this environment "
                         f"({', '.join(what)}) — the serve process "
                         f"would fall back to live compile")
            rc = 1
        else:
            lines.append(f"valid: this environment loads "
                         f"{len(rows)} executable(s), 0 compiles")
    return "\n".join(lines), rc


def run_artifacts(args) -> int:
    try:
        from ..artifacts import store as _store
        if args.export:
            # explicit CLI export overrides the save-side env gate
            os.environ["TX_AOT_EXPORT"] = "on"
            from ..artifacts.export import export_model_artifacts
            from ..workflow.persistence import load_model
            model = load_model(args.model_dir)
            manifest = export_model_artifacts(model, args.model_dir)
            if manifest is None:
                print("tx-artifacts: nothing exported (plan has no "
                      "device program)", file=sys.stderr)
                return 2
            n = len(manifest.get("score") or {})
            print(f"exported {n} scoring bucket(s) for "
                  f"jax={manifest.get('jax')} "
                  f"platform={manifest.get('platform')}")
        manifest, state = _store.read_manifest(args.model_dir)
        if manifest is None:
            print(f"tx-artifacts: no artifact store in "
                  f"{args.model_dir} ({state}) — the serve process "
                  f"live-compiles this model "
                  f"(repair: tx artifacts {args.model_dir} --export)",
                  file=sys.stderr)
            return 1
        rows, bad = _entry_rows(args.model_dir, manifest,
                                check=args.verify)
        checks = _key_checks(args.model_dir, manifest) \
            if args.verify else None
        if args.format == "json":
            doc = {
                "modelDir": args.model_dir,
                "manifest": {k: v for k, v in manifest.items()
                             if k not in ("score", "prepare")},
                "entries": [dict(zip(("kind", "entry", "bucket",
                                      "bytes", "sha256", "check"), r))
                            for r in rows],
                "checks": checks,
                "valid": (not bad
                          and all(c["ok"] for c in checks or ()))
                if args.verify else None,
            }
            print(json.dumps(doc, indent=1))
            return 0 if not args.verify or doc["valid"] else 1
        text, rc = _format_text(args.model_dir, manifest, rows, bad,
                                checks)
        print(text)
        return rc
    except BrokenPipeError:  # pragma: no cover
        raise
    except Exception as e:
        print(f"tx-artifacts: internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2
