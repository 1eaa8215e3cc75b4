"""``python -m transmogrifai_tpu.cli audit`` — static HLO-level audit
of compiled plans (docs/plan_audit.md).

Lowers every bucket program of a model's scoring plan (and, in --demo
mode, the prepare segment programs of a freshly trained demo pipeline)
via ``jax.jit(...).lower()`` — no execution, no devices — and reports
per-bucket op/fusion/byte features, the canonical IR fingerprint, and
the TX-P rule findings. Exit codes match ``tx lint``: 0 clean /
1 findings / 2 internal error.

    tx audit MODEL_DIR                 # audit a saved model's plan
    tx audit --demo                    # self-contained demo workload
    tx audit MODEL_DIR --format json   # machine-readable document
    tx audit MODEL_DIR --fingerprint   # print the canonical key only
"""
from __future__ import annotations

import json
import sys
from typing import List, Optional

__all__ = ["add_audit_parser", "run_audit"]


def add_audit_parser(sub) -> None:
    au = sub.add_parser(
        "audit",
        help="static HLO-level audit of a model's compiled plan "
             "programs (exit 0 clean / 1 findings / 2 internal error)")
    au.add_argument("model_dir", nargs="?", default=None,
                    help="saved model directory (WorkflowModel.save)")
    au.add_argument("--demo", action="store_true",
                    help="audit the self-contained demo pipeline "
                         "(trains once, cached under the tempdir) — "
                         "scoring buckets AND prepare segments")
    au.add_argument("--format", choices=["text", "json"],
                    default="text", help="output format (default: text)")
    au.add_argument("--fingerprint", action="store_true",
                    help="print only the canonical plan fingerprint "
                         "(the AOT artifact identity key) and exit 0")
    au.add_argument("--no-compile", action="store_true",
                    help="lower only, skip the XLA compile step "
                         "(faster; fusion counts report as -1)")
    au.add_argument("--fresh", action="store_true",
                    help="ignore the audit cache (and retrain the "
                         "demo model) — everything re-lowers")
    au.add_argument("--cache", default=None, metavar="FILE",
                    help="audit cache file (default: TX_AUDIT_CACHE "
                         "env or a per-checkout file under the system "
                         "tempdir; 'off' disables)")
    au.add_argument("--store", default=None, metavar="FILE",
                    help="ProfileStore path for the occupancy rules "
                         "TX-P03/TX-P04 and the IR-feature merge "
                         "(default: TX_PROFILE_STORE env or "
                         "BENCH_STATE.json)")
    au.add_argument("--waste-ceiling", type=float, default=None,
                    help="TX-P04 padded/real row ratio ceiling "
                         "(default: the audit.waste_ceiling tuning "
                         "knob)")
    au.add_argument("--no-persist", action="store_true",
                    help="do not merge the per-bucket IR features "
                         "into the ProfileStore profiles block")


def _format_table(audits, findings, stats) -> str:
    rows = [("plan:bucket", "ops", "fus", "const-B", "param-B",
             "out-B", "host", "dyn", "fingerprint")]
    for a in audits:
        rows.append((f"{a.plan}:{a.label}", str(a.n_ops),
                     str(a.fusions) if a.fusions >= 0 else "-",
                     str(a.constant_bytes), str(a.parameter_bytes),
                     str(a.output_bytes), str(len(a.host_transfer_ops)),
                     str(len(a.dynamic_shape_ops)),
                     a.fingerprint.rsplit(":", 1)[-1][:16]))
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    lines = ["  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
             for r in rows]
    lines.append("")
    if findings:
        lines.extend(str(f) for f in findings)
        errors = sum(1 for f in findings if f.severity == "error")
        lines.append(f"{len(findings)} finding(s), {errors} error(s)")
    else:
        lines.append(f"clean: {len(audits)} program(s) audited, "
                     f"0 findings")
    if stats:
        lines.append(f"cache: {stats.get('hits', 0)} hit(s), "
                     f"{stats.get('misses', 0)} miss(es)")
    return "\n".join(lines)


def _format_json_doc(audits, findings, stats, model_dir) -> str:
    return json.dumps({
        "modelDir": model_dir,
        "audits": [a.to_json() for a in audits],
        "findings": [f.to_json() for f in findings],
        "summary": {
            "programs": len(audits),
            "findings": len(findings),
            "errors": sum(1 for f in findings
                          if f.severity == "error"),
        },
        "cache": dict(stats or {}),
    }, indent=1)


def run_audit(args) -> int:
    try:
        from ..analysis.audit import audit_demo, audit_model, \
            plan_fingerprint
        from ..analysis.rules import audit_findings, occupancy_findings
        from ..observability.store import ProfileStore

        if args.fresh:
            import os
            os.environ.setdefault("TX_AUDIT_CACHE", "off")
        cache_path = args.cache
        if cache_path == "off":
            cache_path = ""
        compiled = not args.no_compile

        if args.demo:
            result = audit_demo(cache_path=cache_path,
                                compiled=compiled, fresh=args.fresh)
        elif args.model_dir:
            from ..workflow.persistence import load_model
            model = load_model(args.model_dir)
            if args.fingerprint:
                print(plan_fingerprint(model))
                return 0
            result = audit_model(model, model_dir=args.model_dir,
                                 compiled=compiled,
                                 cache_path=cache_path)
        else:
            print("tx-audit: give a MODEL_DIR or --demo",
                  file=sys.stderr)
            return 2

        if args.fingerprint:
            score = [a for a in result.audits if a.plan == "score"]
            if not score:
                print("tx-audit: plan has no device program",
                      file=sys.stderr)
                return 2
            print(min(score, key=lambda a: a.bucket).fingerprint)
            return 0

        # IR rules (TX-P01/P02) are pure functions of the audits —
        # cheap, so recomputed; the store-dependent occupancy rules
        # (TX-P03/P04) always run FRESH against the live record,
        # never through the audit cache
        store = ProfileStore(args.store)
        ceiling = args.waste_ceiling
        if ceiling is None:
            from ..tuning.policy import TuningPolicy
            ceiling = float(TuningPolicy(path=store.path)
                            .waste_ceiling().chosen)
        findings: List = list(result.findings)
        findings.extend(audit_findings(result.audits))
        findings.extend(occupancy_findings(
            result.audits, store=store,
            waste_ceiling=ceiling))

        if not args.no_persist:
            from ..analysis.audit import process_ir_features
            store.record_ir_features(process_ir_features())

        if args.format == "json":
            print(_format_json_doc(result.audits, findings,
                                   result.stats, result.model_dir))
        else:
            print(_format_table(result.audits, findings, result.stats))
        return 1 if findings else 0
    except BrokenPipeError:  # pragma: no cover
        raise
    except Exception as e:
        print(f"tx-audit: internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2
