"""``python -m transmogrifai_tpu.cli trace`` — summarize and convert a
span trace (docs/observability.md).

Reads the schema-versioned JSONL a traced run wrote
(``TX_TRACE=/path/trace.jsonl``) and answers the questions the raw
file cannot: where did the time go (top spans by SELF time — own wall
minus child spans), how much of it was XLA compile vs execute (the
sections' recorded split), and what one request actually did (its
critical path: the span tree with durations and the child-coverage
fraction). ``--perfetto`` converts to Chrome ``trace_event`` JSON that
loads straight into ui.perfetto.dev / chrome://tracing.

::

    tx trace /tmp/serve.jsonl                    # summary
    tx trace /tmp/serve.jsonl --request req-...  # one request's path
    tx trace /tmp/serve.jsonl --perfetto out.json
    tx trace /tmp/serve.jsonl --format json
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional

__all__ = ["add_trace_parser", "run_trace", "summarize_trace",
           "critical_path"]


def add_trace_parser(sub) -> None:
    tr = sub.add_parser(
        "trace",
        help="summarize / convert a span trace JSONL "
             "(docs/observability.md)")
    tr.add_argument("file", help="trace JSONL written under TX_TRACE")
    tr.add_argument("--format", choices=["text", "json"],
                    default="text")
    tr.add_argument("--top", type=int, default=10,
                    help="rows in the top-self-time table")
    tr.add_argument("--request", default=None, metavar="TRACE_ID",
                    help="render one trace's span tree + critical "
                         "path (a request id, or any trace id from "
                         "the summary)")
    tr.add_argument("--perfetto", default=None, metavar="OUT_JSON",
                    help="write Chrome/Perfetto trace_event JSON")


def _self_times(records: List[dict]) -> Dict[int, float]:
    """Span id -> self time (own duration minus direct children)."""
    child_sum: Dict[int, float] = {}
    for r in records:
        p = r.get("parent")
        if p is not None:
            child_sum[p] = child_sum.get(p, 0.0) + (r.get("dur") or 0.0)
    return {r["sid"]: max((r.get("dur") or 0.0)
                          - child_sum.get(r["sid"], 0.0), 0.0)
            for r in records}


def summarize_trace(records: List[dict], top: int = 10) -> dict:
    """The ``tx trace`` summary document: span/trace counts, top span
    NAMES by total self time, compile share (the ``compile.program``
    spans' trace + lower + backend seconds where the file has such
    spans, else the sections' recorded compile seconds, vs total root
    wall), the five costliest programs, and the request traces
    present."""
    selfs = _self_times(records)
    by_name: Dict[str, dict] = {}
    for r in records:
        rec = by_name.setdefault(
            r.get("name", "?"),
            {"count": 0, "total_seconds": 0.0, "self_seconds": 0.0})
        rec["count"] += 1
        rec["total_seconds"] += r.get("dur") or 0.0
        rec["self_seconds"] += selfs.get(r["sid"], 0.0)
    roots = [r for r in records if r.get("parent") is None]
    root_wall = sum(r.get("dur") or 0.0 for r in roots)
    programs = sorted(
        ({"program": a.get("program", "?"), "thread": a.get("thread", "?"),
          "cache": a.get("cache", "?"),
          "seconds": round(sum(a.get(k, 0.0) for k in (
              "trace_s", "lower_s", "backend_s")), 6)}
         for a in (r.get("attrs") or {} for r in records
                   if r.get("name") == "compile.program")),
        key=lambda row: -row["seconds"])
    # sections nest (a segment's seconds hold its stages'), the
    # programs do not: where a file has them they are the compile bill
    compile_s = sum(row["seconds"] for row in programs) if programs \
        else sum((r.get("attrs") or {}).get("compile_seconds", 0.0)
                 for r in records)
    requests = sorted({r["trace"] for r in records
                       if r.get("name") == "serve.request"})
    events = sum(len(r.get("events") or ()) for r in records)
    return {
        "spans": len(records),
        "traces": len({r.get("trace") for r in records}),
        "root_spans": len(roots),
        "root_wall_seconds": round(root_wall, 6),
        "compile_seconds": round(compile_s, 6),
        "compile_share": round(compile_s / root_wall, 4)
        if root_wall > 0 else 0.0,
        "costliest_programs": programs[:5],
        "span_events": events,
        "requests": requests[:200],
        "request_count": len(requests),
        "top_self_time": sorted(
            ({"name": k,
              "count": v["count"],
              "total_seconds": round(v["total_seconds"], 6),
              "self_seconds": round(v["self_seconds"], 6)}
             for k, v in by_name.items()),
            key=lambda r: -r["self_seconds"])[:top],
    }


def critical_path(records: List[dict], trace_id: str) -> dict:
    """One trace rendered as its critical path: the span tree in start
    order with durations, per-span share of the root wall, and the
    root's direct-child coverage (the >=95% acceptance metric). The
    ``path`` list is the chain root -> heaviest child -> ... — the
    sequence that bounds the trace's latency."""
    from ..observability.trace import coverage, span_tree
    roots = span_tree(records, trace_id)
    if not roots:
        raise ValueError(f"no spans for trace {trace_id!r}")
    root = roots[0]
    total = root["span"].get("dur") or 0.0

    def node_row(node, depth):
        s = node["span"]
        return {"depth": depth, "name": s.get("name", "?"),
                "seconds": round(s.get("dur") or 0.0, 6),
                "share": round((s.get("dur") or 0.0) / total, 4)
                if total > 0 else 0.0,
                "attrs": s.get("attrs") or {},
                "events": [e.get("name") for e in
                           (s.get("events") or ())]}

    tree_rows: List[dict] = []

    def walk(node, depth):
        tree_rows.append(node_row(node, depth))
        for c in node["children"]:
            walk(c, depth + 1)

    walk(root, 0)
    path, node = [], root
    while True:
        path.append(node["span"].get("name", "?"))
        if not node["children"]:
            break
        node = max(node["children"],
                   key=lambda c: c["span"].get("dur") or 0.0)
    return {"trace": trace_id,
            "wall_seconds": round(total, 6),
            "coverage": round(coverage(records, trace_id), 4),
            "path": path,
            "tree": tree_rows}


def _print_text(summary: dict, request: Optional[dict]) -> None:
    print(f"{summary['spans']} span(s) in {summary['traces']} "
          f"trace(s); {summary['request_count']} serve request(s); "
          f"{summary['span_events']} span event(s)")
    print(f"root wall {summary['root_wall_seconds']:.4f}s, compile "
          f"{summary['compile_seconds']:.4f}s "
          f"({summary['compile_share']:.1%} of root wall)")
    for row in summary["costliest_programs"]:
        print(f"  {row['program']:<32} {row['cache']:<9} "
              f"{row['seconds']:>9.4f}s  on {row['thread']}")
    print("\ntop spans by self time:")
    print(f"  {'name':<32} {'calls':>6} {'self s':>10} {'total s':>10}")
    for row in summary["top_self_time"]:
        print(f"  {row['name']:<32} {row['count']:>6} "
              f"{row['self_seconds']:>10.4f} "
              f"{row['total_seconds']:>10.4f}")
    if request is not None:
        print(f"\nrequest {request['trace']}: "
              f"{request['wall_seconds'] * 1000:.3f}ms wall, child "
              f"coverage {request['coverage']:.1%}")
        print("critical path: " + " -> ".join(request["path"]))
        for row in request["tree"]:
            pad = "  " * row["depth"]
            evs = (f"  events={','.join(row['events'])}"
                   if row["events"] else "")
            print(f"  {pad}{row['name']:<{max(30 - 2 * row['depth'], 8)}}"
                  f" {row['seconds'] * 1000:>9.3f}ms "
                  f"{row['share']:>6.1%}{evs}")


def run_trace(args) -> int:
    from ..observability.trace import read_trace, to_perfetto
    try:
        meta, records = read_trace(args.file)
    except (OSError, ValueError) as e:
        print(f"error: {e}")
        return 2
    if not records:
        print(f"{args.file}: no spans")
        return 1
    summary = summarize_trace(records, top=args.top)
    request = None
    if args.request is not None:
        try:
            request = critical_path(records, args.request)
        except ValueError as e:
            print(f"error: {e}")
            return 2
    if args.perfetto:
        with open(args.perfetto, "w", encoding="utf-8") as fh:
            json.dump(to_perfetto(meta, records), fh)
        summary["perfetto"] = args.perfetto
    if args.format == "json":
        out = {"meta": meta, "summary": summary}
        if request is not None:
            out["request"] = request
        print(json.dumps(out, indent=1, default=str))
    else:
        _print_text(summary, request)
        if args.perfetto:
            print(f"\nperfetto trace written to {args.perfetto} "
                  f"(load at ui.perfetto.dev)")
    return 0
