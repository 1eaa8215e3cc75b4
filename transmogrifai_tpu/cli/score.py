"""``python -m transmogrifai_tpu.cli score`` — batch scoring through the
compiled serving plan (docs/serving.md), plus a self-contained
``--bench`` smoke mode that prints one JSON line:

    {"metric": "score_rows_per_s", "value": ..., ...}

Scoring a saved model over a CSV/Avro file::

    python -m transmogrifai_tpu.cli score --model DIR --input data.csv \\
        --output scores.json [--engine compiled|columnar]

Benchmark (compiled plan vs the per-record ScoreFunction loop; trains a
tiny synthetic pipeline when --model/--input are not given)::

    python -m transmogrifai_tpu.cli score --bench [--rows N]
"""
from __future__ import annotations

import json
import time
from typing import List, Optional

__all__ = ["add_score_parser", "run_score"]


def add_score_parser(sub) -> None:
    sc = sub.add_parser(
        "score",
        help="score records through a saved model's compiled serving "
             "plan (--bench: compiled-vs-loop throughput smoke)")
    sc.add_argument("--model", default=None,
                    help="saved model directory (WorkflowModel.save)")
    sc.add_argument("--input", default=None,
                    help="CSV or Avro (.avro) records to score")
    sc.add_argument("--output", default=None,
                    help="write scores as JSON rows here "
                         "(default: stdout summary only)")
    sc.add_argument("--engine", choices=["compiled", "columnar"],
                    default="compiled",
                    help="scoring engine (default: compiled plan)")
    sc.add_argument("--bench", action="store_true",
                    help="measure compiled-plan vs per-record-loop "
                         "throughput and print one JSON metric line")
    sc.add_argument("--rows", type=int, default=2000,
                    help="benchmark batch size (--bench; default 2000)")
    sc.add_argument("--no-guardrails", action="store_true",
                    help="disable schema admission / output guards / "
                         "breaker (guardrails are ON for CLI scoring; "
                         "docs/serving_guardrails.md)")
    sc.add_argument("--no-sentinel", action="store_true",
                    help="disable the online drift sentinel (no drift "
                         "summary, never exit 2 on drift)")
    sc.add_argument("--drift-warn", type=float, default=None,
                    help="drift sentinel warn threshold (JS divergence)")
    sc.add_argument("--drift-degrade", type=float, default=None,
                    help="drift sentinel degrade threshold — crossing "
                         "it makes the command exit 2")


def _read_records(path: str) -> List[dict]:
    if path.endswith(".avro"):
        from ..readers import AvroProductReader
        return AvroProductReader(path).read_records()
    from ..readers import CSVAutoReader
    return CSVAutoReader(path).read_records()


def _tiny_pipeline(n_rows: int = 400):
    """Train a small synthetic pipeline covering the common feature
    families — the self-contained --bench workload."""
    import numpy as np

    from ..features.builder import FeatureBuilder
    from ..models import LogisticRegression
    from ..ops import transmogrify
    from ..testkit import RandomData, RandomReal, RandomText
    from ..types import PickList, Real, RealNN
    from ..workflow import Workflow

    records = (RandomData(seed=7)
               .with_column("x", RandomReal.normal(0, 1, seed=1))
               .with_column("y", RandomReal.uniform(0, 10, seed=2))
               .with_column("cat", RandomText.picklists(
                   ["a", "b", "c", "d"], seed=3))).records(n_rows)
    rng = np.random.default_rng(4)
    for r in records:
        r["label"] = float((r["x"] or 0) + 0.3 * rng.normal() > 0)
    x = FeatureBuilder.of("x", Real).extract(
        lambda r: r.get("x")).as_predictor()
    y = FeatureBuilder.of("y", Real).extract(
        lambda r: r.get("y")).as_predictor()
    cat = FeatureBuilder.of("cat", PickList).extract(
        lambda r: r.get("cat")).as_predictor()
    label = FeatureBuilder.of("label", RealNN).extract(
        lambda r: r.get("label")).as_response()
    pred = LogisticRegression(reg_param=0.01).set_input(
        label, transmogrify([x, y, cat])).get_output()
    model = (Workflow().set_result_features(pred)
             .set_input_records(records).train(validate="off"))
    return model, records


def _bench(model, records, rows: int) -> dict:
    from ..local import ScoreFunction
    from ..serving import plan_compiles

    batch = (records * (rows // max(len(records), 1) + 1))[:rows]
    fn = ScoreFunction(model)
    # warm: first compiled call pays plan compile + XLA trace
    t0 = time.perf_counter()
    fn.score_batch(batch[:min(16, rows)])
    warm_s = time.perf_counter() - t0
    compiles0 = plan_compiles()
    t0 = time.perf_counter()
    fn.score_batch(batch)
    compiled_s = time.perf_counter() - t0
    repeat0 = plan_compiles()
    fn.score_batch(batch)          # same bucket again: 0 new compiles
    repeat_compiles = plan_compiles() - repeat0
    loop_rows = min(rows, 200)
    t0 = time.perf_counter()
    fn.score_batch(batch[:loop_rows], engine="records")
    loop_s_per_row = (time.perf_counter() - t0) / loop_rows
    value = rows / max(compiled_s, 1e-9)
    loop_rps = 1.0 / max(loop_s_per_row, 1e-9)
    plan = fn._scoring_plan()
    return {
        "metric": "score_rows_per_s",
        "value": round(value, 1),
        "unit": "rows/s",
        "vs_baseline": round(value / loop_rps, 2),
        "loop_rows_per_s": round(loop_rps, 1),
        "speedup": round(value / loop_rps, 2),
        "batch_rows": rows,
        "warmup_seconds": round(warm_s, 3),
        "new_compiles": plan_compiles() - compiles0,
        "repeat_compiles": repeat_compiles,
        "coverage": plan.coverage.to_json() if plan else None,
    }


def run_score(args) -> int:
    if args.bench:
        if args.model:
            from ..workflow import WorkflowModel
            model = WorkflowModel.load(args.model)
            records = _read_records(args.input) if args.input else None
            if not records:
                raise ValueError("--bench with --model needs --input")
        else:
            model, records = _tiny_pipeline()
        print(json.dumps(_bench(model, records, args.rows)))
        return 0
    if not args.model or not args.input:
        raise ValueError("score needs --model and --input (or --bench)")
    from ..workflow import WorkflowModel
    model = WorkflowModel.load(args.model)
    records = _read_records(args.input)
    guard_report = None
    drift = None
    t0 = time.perf_counter()
    if args.engine == "compiled" and not (args.no_guardrails
                                          and args.no_sentinel):
        # CLI scoring runs guarded by default: malformed rows are
        # quarantined with reasons instead of crashing the run, and
        # the drift sentinel compares the batch against training
        from ..serving import DriftThresholds
        thresholds = None
        if args.drift_warn is not None or args.drift_degrade is not None:
            d = DriftThresholds()
            thresholds = DriftThresholds(
                warn=args.drift_warn if args.drift_warn is not None
                else d.warn,
                degrade=args.drift_degrade
                if args.drift_degrade is not None else d.degrade)
        # artifact-first (artifacts/loader.py, TX-R06): `tx score` on
        # a saved model deserializes the exported bucket programs —
        # compile-free invocation; loud counted fallback otherwise
        from ..artifacts.loader import load_or_compile
        plan = load_or_compile(model, model_dir=args.model)
        if args.no_guardrails:
            # sentinel only: no admission/breaker, just drift watching
            from ..serving.sentinel import DriftSentinel
            plan.sentinel = DriftSentinel.for_model(
                model, thresholds=thresholds)
        else:
            plan.with_guardrails(sentinel=not args.no_sentinel,
                                 thresholds=thresholds)
        result = plan.score_guarded(records)
        scored, guard_report = result.scored, result
        if not args.no_sentinel:
            drift = plan.drift_report()
    else:
        scored = model.score(records, engine=args.engine)
    dt = time.perf_counter() - t0
    if args.output:
        from ..local.scoring import _unbox
        names = [f.name for f in model.result_features]
        bad_rows = set()
        guard_by_row = {}
        if guard_report is not None:
            for r in (guard_report.quarantined
                      + guard_report.invalidated):
                bad_rows.add(r.row)
                guard_by_row.setdefault(r.row, []).append(r.to_json())
        rows = []
        for i in range(scored.n_rows):
            if i in bad_rows:
                # guarded-out rows ship their reasons, not garbage
                rows.append({**{n: None for n in names},
                             "_guard": guard_by_row[i]})
            else:
                rows.append({n: _unbox(scored[n].boxed(i))
                             for n in names})
        with open(args.output, "w") as fh:
            json.dump(rows, fh)
    print(f"scored {scored.n_rows} rows in {dt:.3f}s "
          f"({scored.n_rows / max(dt, 1e-9):.0f} rows/s, "
          f"engine={args.engine})"
          + (f" -> {args.output}" if args.output else ""))
    if guard_report is not None:
        nq = len(guard_report.quarantined_rows)
        ni = len(guard_report.invalidated_rows)
        print(f"guardrails: {scored.n_rows - nq - ni} ok, "
              f"{nq} quarantined, {ni} invalidated"
              + (" (host fallback)" if guard_report.used_host_fallback
                 else ""))
        for r in (guard_report.quarantined
                  + guard_report.invalidated)[:10]:
            print(f"  row {r.row}: {r.code} [{r.feature}] {r.detail}")
    if drift is not None and drift.get("enabled"):
        worst = drift["features"][0] if drift["features"] else None
        print(f"drift sentinel: status={drift['status']} over "
              f"{drift['rowsSeen']} rows"
              + (f"; worst feature {worst['feature']} "
                 f"js={worst['jsDivergence']:.3f}" if worst else ""))
        if drift["status"] == "degrade":
            print("drift sentinel: DEGRADE threshold crossed — "
                  "scored traffic no longer matches training "
                  "(exit 2; --no-sentinel to ignore)")
            return 2
    return 0
