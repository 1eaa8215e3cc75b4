"""Observability tests (transmogrifai_tpu/observability/ + the span
threading through serve/train/search).

The acceptance contracts, in the ISSUE's words:

- a traced serve session yields a JSONL trace where >= 95% of a
  request's measured wall-clock is covered by child spans
  (wait/encode/dispatch/guard), ``tx trace`` renders its critical
  path, and the Perfetto export loads;
- spans stay BALANCED (every enter has an exit) under fault injection;
- the disabled tracer allocates no spans (and ``span()`` is one shared
  no-op object);
- repeat trains keep span counts flat;
- the serving request-id round-trips through the TCP protocol;
- the telemetry event stream is a bounded ring with an explicit
  overflow marker + dropped counter;
- the profile store merges atomically and carries the bench probe
  verdict + transcript.

Everything tier-1-safe on the 1-CPU container: one small trained model
per module, sub-second drills.
"""
import asyncio
import json
import os
import time

import numpy as np
import pytest

from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.models import LogisticRegression
from transmogrifai_tpu.observability import (LatencyHistogram,
                                             ProfileStore,
                                             gather_process_profiles,
                                             trace)
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.runtime import FaultInjector, telemetry
from transmogrifai_tpu.serving import (ScoringPlan, ServeConfig,
                                       ServingServer, serve_in_process)
from transmogrifai_tpu.types import PickList, Real, RealNN
from transmogrifai_tpu.utils import compile_time
from transmogrifai_tpu.workflow import Workflow


@pytest.fixture(autouse=True)
def _fresh_state():
    telemetry.reset()
    trace.configure(False)
    trace.reset()
    yield
    trace.configure(False)
    trace.reset()
    telemetry.reset()


def _records(n=120, seed=7):
    rng = np.random.default_rng(seed)
    cats = ["a", "b", "c"]
    recs = []
    for _ in range(n):
        x = float(rng.normal())
        recs.append({"x": x, "z": float(rng.uniform(0, 4)),
                     "cat": cats[int(rng.integers(0, len(cats)))],
                     "label": float(x + 0.5 * rng.normal() > 0)})
    return recs


def _features():
    x = FeatureBuilder.of("x", Real).extract(
        lambda r: r.get("x")).as_predictor()
    z = FeatureBuilder.of("z", RealNN).extract(
        lambda r: r.get("z")).as_predictor()
    cat = FeatureBuilder.of("cat", PickList).extract(
        lambda r: r.get("cat")).as_predictor()
    label = FeatureBuilder.of("label", RealNN).extract(
        lambda r: r.get("label")).as_response()
    return label, transmogrify([x, z, cat])


@pytest.fixture(scope="module")
def trained():
    recs = _records()
    label, feats = _features()
    pred = LogisticRegression(reg_param=0.01).set_input(
        label, feats).get_output()
    model = (Workflow().set_result_features(pred)
             .set_input_records(recs).train(validate="off"))
    return model, recs, pred.name


# ---------------------------------------------------------------------------
# the tracer core
# ---------------------------------------------------------------------------

class TestTracer:
    def test_nesting_parents_and_events(self):
        trace.configure(True)
        with trace.span("outer", kind="test"):
            trace.add_event("mark", n=1)
            with trace.span("inner"):
                pass
        spans = trace.spans()
        assert [s["name"] for s in spans] == ["inner", "outer"]
        inner, outer = spans
        assert inner["parent"] == outer["sid"]
        assert inner["trace"] == outer["trace"]
        assert outer["attrs"]["kind"] == "test"
        assert outer["events"][0] == pytest.approx(
            outer["events"][0]) and outer["events"][0]["n"] == 1
        assert all(s["dur"] is not None and s["dur"] >= 0
                   for s in spans)

    def test_explicit_cross_thread_parent(self):
        trace.configure(True)
        import threading
        with trace.span("root"):
            parent = trace.current_ref()

            def worker():
                # a fresh thread has an empty context stack: without
                # the explicit parent this would become its own root
                with trace.span("child", parent=parent):
                    pass
            th = threading.Thread(target=worker)
            th.start()
            th.join()
        child = next(s for s in trace.spans() if s["name"] == "child")
        root = next(s for s in trace.spans() if s["name"] == "root")
        assert child["parent"] == root["sid"]
        assert child["trace"] == root["trace"]

    def test_balanced_on_exception(self):
        trace.configure(True)
        with pytest.raises(ValueError):
            with trace.span("boom"):
                raise ValueError("x")
        (s,) = trace.spans()
        assert s["dur"] is not None
        assert s["attrs"]["status"] == "error"
        assert "ValueError" in s["attrs"]["error"]

    def test_disabled_allocates_nothing(self):
        assert not trace.enabled()
        with trace.span("nope", big="attr"):
            trace.add_event("dropped")
        assert trace.spans() == []
        assert trace.add_span("nope", 0.0, 1.0) is None
        # the disabled path hands back ONE shared no-op object
        assert trace.span("a") is trace.span("b")
        assert trace.current_ref() is None

    def test_in_memory_ring_is_bounded(self, monkeypatch):
        monkeypatch.setenv("TX_TRACE_BUFFER", "32")
        trace.configure(True)
        for i in range(100):
            with trace.span(f"s{i}"):
                pass
        assert len(trace.spans()) == 32
        assert trace.spans()[-1]["name"] == "s99"

    def test_request_ids_unique(self):
        ids = {trace.new_request_id() for _ in range(100)}
        assert len(ids) == 100
        assert all(i.startswith("req-") for i in ids)

    def test_enabled_span_reaches_a_running_profiler(self, tmp_path):
        """An open ``span()`` is also a ``jax.profiler.TraceAnnotation``
        with the span's scalar attributes: under a running profiler trace
        the package's spans sit on a host line of the profile. A disabled
        ``span()`` is still the shared no-op and writes nothing there."""
        import glob
        import jax
        jax.profiler.start_trace(str(tmp_path))
        try:
            with trace.span("off.span", family="GBT"):
                pass
            assert trace.span("off.span") is trace._NOOP
            trace.configure(True)
            with trace.span("search.family", family="GBT", lanes=54,
                            skipped=None):
                with trace.span("search.fetch"):
                    pass
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        seen = {}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for event in line.events:
                    if event.name in ("search.family", "search.fetch",
                                      "off.span"):
                        seen[event.name] = (plane.name, dict(event.stats),
                                            event.start_ns, event.end_ns)
        assert set(seen) == {"search.family", "search.fetch"}
        plane, stats, start, end = seen["search.family"]
        assert plane.startswith("/host:")
        assert stats["family"] == "GBT" and int(stats["lanes"]) == 54
        assert "skipped" not in stats       # scalars only
        assert start <= seen["search.fetch"][2] \
            and seen["search.fetch"][3] <= end
        # and the tracer's own record is what it always was
        family = next(s for s in trace.spans()
                      if s["name"] == "search.family")
        assert family["attrs"] == {"family": "GBT", "lanes": 54,
                                   "skipped": None}


class TestSectionSpans:
    def test_section_attaches_to_enclosing_span(self):
        trace.configure(True)
        with trace.span("work"):
            with compile_time.section("obs-test:inner"):
                time.sleep(0.002)
        sec = [s for s in trace.spans()
               if s["name"] == "section:obs-test:inner"]
        assert len(sec) == 1
        work = next(s for s in trace.spans() if s["name"] == "work")
        assert sec[0]["parent"] == work["sid"]
        assert sec[0]["attrs"]["execute_seconds"] >= 0.0
        assert "compile_seconds" in sec[0]["attrs"]
        compile_time.reset_sections("obs-test:")

    def test_section_outside_any_span_is_dropped(self):
        trace.configure(True)
        with compile_time.section("obs-test:orphan"):
            pass
        assert trace.spans() == []
        compile_time.reset_sections("obs-test:")


# ---------------------------------------------------------------------------
# telemetry: ring buffer + span events
# ---------------------------------------------------------------------------

class TestTelemetryRing:
    def test_overflow_marker_and_dropped_counter(self, monkeypatch):
        monkeypatch.setenv("TX_TELEMETRY_EVENTS_CAP", "16")
        mark = telemetry.events_mark()
        for i in range(40):
            telemetry.event("drill", i=i)
        evs = telemetry.events_since(mark)
        assert evs[0]["event"] == telemetry.OVERFLOW_EVENT
        assert evs[0]["dropped"] == 24
        assert telemetry.events_dropped() == 24
        assert telemetry.counters()["telemetry_events_dropped"] == 24
        # the ring keeps the NEWEST events
        assert [e["i"] for e in evs[1:]] == list(range(24, 40))

    def test_mark_semantics_without_overflow(self, monkeypatch):
        monkeypatch.setenv("TX_TELEMETRY_EVENTS_CAP", "64")
        telemetry.event("a")
        mark = telemetry.events_mark()
        telemetry.event("b")
        telemetry.event("c")
        assert [e["event"] for e in telemetry.events_since(mark)] \
            == ["b", "c"]
        assert telemetry.events_dropped() == 0

    def test_mark_taken_after_overflow_sees_no_marker(self, monkeypatch):
        monkeypatch.setenv("TX_TELEMETRY_EVENTS_CAP", "16")
        for i in range(40):
            telemetry.event("drill", i=i)
        mark = telemetry.events_mark()
        telemetry.event("fresh")
        evs = telemetry.events_since(mark)
        assert [e["event"] for e in evs] == ["fresh"]

    def test_events_become_span_events_when_tracing(self):
        trace.configure(True)
        with trace.span("dispatch"):
            telemetry.event("retry", family="GBT", attempt=1)
        (s,) = trace.spans()
        assert s["events"][0]["name"] == "retry"
        assert s["events"][0]["family"] == "GBT"


# ---------------------------------------------------------------------------
# JSONL file + perfetto + tx trace CLI
# ---------------------------------------------------------------------------

class TestTraceFile:
    def test_roundtrip_header_and_torn_tail(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        trace.configure(True, path=path)
        with trace.span("a"):
            with trace.span("b"):
                pass
        trace.flush()
        with open(path, "a") as fh:
            fh.write('{"kind": "span", "torn')    # killed mid-write
        meta, spans = trace.read_trace(path)
        assert meta["schema"] == trace.SCHEMA_VERSION
        assert "anchor_monotonic" in meta
        assert [s["name"] for s in spans] == ["b", "a"]

    def test_appended_segments_do_not_alias(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        header = {"kind": "header", "schema": 1, "anchor_epoch": 0.0,
                  "anchor_monotonic": 0.0, "pid": 1}
        span = {"kind": "span", "v": 1, "sid": 1, "parent": None,
                "trace": "t1", "name": "x", "t0": 0.0, "dur": 1.0,
                "attrs": {}, "events": []}
        with open(path, "w") as fh:
            for _ in range(2):          # two processes appended
                fh.write(json.dumps(header) + "\n")
                fh.write(json.dumps(span) + "\n")
        _, spans = trace.read_trace(path)
        assert len({s["sid"] for s in spans}) == 2
        assert len({s["trace"] for s in spans}) == 2

    def test_perfetto_export_loads(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        trace.configure(True, path=path)
        with trace.span("op", kind="x"):
            trace.add_event("ev", n=3)
        trace.flush()
        meta, spans = trace.read_trace(path)
        pf = trace.to_perfetto(meta, spans)
        doc = json.loads(json.dumps(pf))      # fully serializable
        phases = {e["ph"] for e in doc["traceEvents"]}
        assert phases == {"X", "i"}
        x = next(e for e in doc["traceEvents"] if e["ph"] == "X")
        assert x["name"] == "op" and x["dur"] >= 0


class TestTraceCli:
    def _write_trace(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        trace.configure(True, path=path)
        with trace.span("root"):
            with trace.span("step1"):
                time.sleep(0.002)
            with trace.span("step2"):
                pass
        trace.flush()
        return path

    def test_summary_and_critical_path(self, tmp_path, capsys):
        from transmogrifai_tpu.cli.gen import main
        path = self._write_trace(tmp_path)
        _, spans = trace.read_trace(path)
        root_trace = spans[-1]["trace"]
        rc = main(["trace", path, "--request", root_trace])
        out = capsys.readouterr().out
        assert rc == 0
        assert "top spans by self time" in out
        assert "critical path: root -> step1" in out

    def test_json_format_and_perfetto_flag(self, tmp_path, capsys):
        from transmogrifai_tpu.cli.gen import main
        path = self._write_trace(tmp_path)
        pf_path = str(tmp_path / "pf.json")
        rc = main(["trace", path, "--format", "json",
                   "--perfetto", pf_path])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["summary"]["spans"] == 3
        assert doc["summary"]["top_self_time"]
        pf = json.load(open(pf_path))
        assert len(pf["traceEvents"]) == 3

    def test_missing_file_is_exit_2(self, tmp_path, capsys):
        from transmogrifai_tpu.cli.gen import main
        assert main(["trace", str(tmp_path / "nope.jsonl")]) == 2


# ---------------------------------------------------------------------------
# spans through train / scoring, balance under faults, flat counts
# ---------------------------------------------------------------------------

class TestTrainSpans:
    def test_repeat_trains_keep_span_counts_flat(self):
        recs = _records(n=60)
        trace.configure(True)

        def one_train():
            trace.reset()
            label, feats = _features()
            pred = LogisticRegression(reg_param=0.01).set_input(
                label, feats).get_output()
            (Workflow().set_result_features(pred)
             .set_input_records(recs).train(validate="off"))
            return trace.spans()

        first = one_train()
        second = one_train()
        third = one_train()
        # the cold train carries extra per-stage TRACE-cost sections
        # (compiles happen once); warm repeats are span-for-span flat
        assert [s["name"] for s in second] \
            == [s["name"] for s in third]
        assert len(second) <= len(first)
        assert any(s["name"] == "train" for s in second)
        # balanced: every span record is CLOSED (has a duration)
        assert all(s["dur"] is not None
                   for s in first + second + third)

    def test_search_spans_say_where_the_host_waits(self):
        """One traced tiny train with a tree family: the family's design
        and its blocking fetch are children of ``search.family``, the
        winner's refit and training-set evaluation descendants of
        ``train``, outside every family."""
        from transmogrifai_tpu.models import GBTClassifier
        from transmogrifai_tpu.selector import \
            BinaryClassificationModelSelector
        label, feats = _features()
        selector = BinaryClassificationModelSelector.with_cross_validation(
            num_folds=2, seed=3, models=[
                (GBTClassifier(num_rounds=2, max_depth=2, max_bins=8),
                 [{"gamma": 0.0}, {"gamma": 0.1}])])
        pred = selector.set_input(label, feats).get_output()
        trace.configure(True)
        (Workflow().set_result_features(label, pred)
         .set_input_records(_records(n=80)).train())
        spans = trace.spans()
        by_sid = {s["sid"]: s for s in spans}

        def ancestors(span):
            out = []
            while span.get("parent") in by_sid:
                span = by_sid[span["parent"]]
                out.append(span["name"])
            return out

        def named(name):
            found = [s for s in spans if s["name"] == name]
            assert found, f"no {name} span"
            return found

        for name in ("search.design", "search.fetch"):
            for s in named(name):
                assert by_sid[s["parent"]]["name"] == "search.family"
                assert ancestors(s)[-1] == "train"
        for name in ("search.refit", "search.train_eval"):
            (s,) = named(name)
            assert "train" in ancestors(s)
            assert "search.family" not in ancestors(s)
        (refit,) = named("search.refit")
        assert refit["attrs"]["family"] == "GBTClassifier"
        # a fetch says which histogram mode its own program was compiled
        # with (the CPU's ``scatter``: its levels gather to route, keep
        # segment_sum for their node sums and gather their rows' leaf
        # values), and carries no count of the process's traces
        for s in named("search.fetch"):
            assert s["attrs"]["hist_mode"] == "scatter"
            assert not [k for k in s["attrs"] if k.startswith(
                ("route_", "sums_", "pick_"))]
        # the winner's scoring is a plain span (a CPU walks by gathers)
        (train_eval,) = named("search.train_eval")
        assert train_eval["attrs"] == {}
        assert all(s["dur"] is not None for s in spans)

    def test_scoring_spans_nest_under_guarded(self, trained):
        model, recs, _pred = trained
        trace.configure(True)
        plan = ScoringPlan(model).compile().with_guardrails(
            sentinel=False)
        plan.score_guarded([dict(r) for r in recs[:8]])
        spans = trace.spans()
        guarded = next(s for s in spans
                       if s["name"] == "score.guarded")
        enc = next(s for s in spans if s["name"] == "score.encode")
        disp = next(s for s in spans if s["name"] == "score.dispatch")
        assert enc["parent"] == guarded["sid"]
        assert disp["parent"] == guarded["sid"]
        # the bucket section reported into the dispatch span with the
        # compile/execute split
        bucket = [s for s in spans
                  if s["name"].startswith("section:score:")
                  and s["parent"] == disp["sid"]]
        assert bucket and "compile_seconds" in bucket[0]["attrs"]


class TestFaultBalance:
    def test_spans_balanced_under_dispatch_fault(self, trained):
        model, recs, _pred = trained
        trace.configure(True)
        plan = ScoringPlan(model).compile().with_guardrails(
            sentinel=False)
        plan.score_guarded([dict(r) for r in recs[:8]])  # warm
        trace.reset()
        mark = telemetry.events_mark()
        with FaultInjector.plan("plan:device:dispatch:1=oom"):
            res = plan.score_guarded([dict(r) for r in recs[:8]])
        # the injected OOM retried (or fell back) — either way every
        # span closed and the run still answered
        assert res.scored.n_rows == 8
        spans = trace.spans()
        assert spans and all(s["dur"] is not None for s in spans)
        # the retry/fallback telemetry event landed INSIDE a span
        evs = [e for s in spans for e in s["events"]]
        names = {e["name"] for e in evs}
        assert names & {"retry", "serving_fallback"}, \
            telemetry.events_since(mark)

    def test_spans_balanced_when_error_propagates(self, trained):
        # an UNGUARDED plan has no breaker/fallback: a non-transient
        # injected fault propagates to the caller — and every span
        # still closes, the failing one carrying status=error
        from transmogrifai_tpu.runtime.faults import InjectedFamilyBug
        model, recs, _pred = trained
        trace.configure(True)
        plan = ScoringPlan(model).compile()
        plan.score([dict(r) for r in recs[:8]])          # warm
        trace.reset()
        with FaultInjector.plan("plan:device:dispatch:1=bug"):
            with pytest.raises(InjectedFamilyBug):
                plan.score([dict(r) for r in recs[:8]])
        spans = trace.spans()
        assert spans and all(s["dur"] is not None for s in spans)
        disp = next(s for s in spans if s["name"] == "score.dispatch")
        assert disp["attrs"].get("status") == "error"
        assert "InjectedFamilyBug" in disp["attrs"]["error"]


# ---------------------------------------------------------------------------
# the serving loop: request spans, coverage, TCP round trip, metrics
# ---------------------------------------------------------------------------

class TestServingTrace:
    def test_request_spans_cover_95_percent(self, trained, tmp_path):
        model, recs, _pred = trained
        path = str(tmp_path / "serve.jsonl")
        server, client = serve_in_process(
            {"m": model}, ServeConfig(max_wait_ms=5.0, sentinel=False))
        try:
            client.score_many([dict(r) for r in recs[:16]])  # warm
            trace.configure(True, path=path)
            client.score_many([dict(r) for r in recs[:48]])
            trace.flush()
        finally:
            trace.configure(False)
            server.stop()
        meta, spans = trace.read_trace(path)
        reqs = [s for s in spans if s["name"] == "serve.request"]
        assert len(reqs) == 48
        covs = [trace.coverage(spans, r["trace"]) for r in reqs]
        assert min(covs) >= 0.95, sorted(covs)[:3]
        # children are the documented four segments
        kids = {s["name"] for s in spans
                if s.get("parent") == reqs[0]["sid"]}
        assert kids == {"serve.wait", "serve.encode",
                        "serve.dispatch", "serve.guard"}
        # the critical path renders for a request id
        from transmogrifai_tpu.cli.trace import critical_path
        cp = critical_path(spans, reqs[0]["trace"])
        assert cp["coverage"] >= 0.95
        assert cp["path"][0] == "serve.request"

    def test_request_id_round_trips_through_tcp(self, trained):
        model, recs, _pred = trained
        from transmogrifai_tpu.cli.serve import serve_forever

        async def drive():
            server = ServingServer(
                ServeConfig(max_wait_ms=5.0, sentinel=False))
            server.add_model("m", model)
            port_box = {}
            task = asyncio.ensure_future(serve_forever(
                server, "127.0.0.1", 0, max_requests=2,
                ready_cb=lambda p: port_box.setdefault("p", p)))
            while "p" not in port_box:
                await asyncio.sleep(0.005)
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port_box["p"])
            writer.write((json.dumps(
                {"record": recs[0], "model": "m"}) + "\n").encode())
            writer.write((json.dumps(
                {"record": recs[1], "model": "m",
                 "id": "client-req-42"}) + "\n").encode())
            await writer.drain()
            outs = [json.loads(await reader.readline())
                    for _ in range(2)]
            writer.close()
            await task
            return outs

        outs = asyncio.run(drive())
        assert outs[0]["ok"] and outs[1]["ok"]
        # server-generated id on request 1, client id echoed on 2
        assert outs[0]["request_id"].startswith("req-")
        assert outs[1]["request_id"] == "client-req-42"

    def test_metrics_control_request_and_http_port(self, trained):
        model, recs, _pred = trained
        from transmogrifai_tpu.cli.serve import serve_forever

        async def drive():
            server = ServingServer(
                ServeConfig(max_wait_ms=5.0, sentinel=False))
            server.add_model("m", model)
            boxes = {}
            task = asyncio.ensure_future(serve_forever(
                server, "127.0.0.1", 0, max_requests=1,
                ready_cb=lambda p: boxes.setdefault("tcp", p),
                metrics_port=0,
                metrics_ready_cb=lambda p: boxes.setdefault("http", p)))
            while "tcp" not in boxes or "http" not in boxes:
                await asyncio.sleep(0.005)
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", boxes["tcp"])
            # a control request answers metrics WITHOUT consuming the
            # max_requests budget
            writer.write(b'{"metrics": true}\n')
            await writer.drain()
            m = json.loads(await reader.readline())
            # the HTTP endpoint serves the same document (fetched
            # BEFORE the scoring request — answering it ends the
            # max_requests=1 session)
            hr, hw = await asyncio.open_connection(
                "127.0.0.1", boxes["http"])
            hw.write(b"GET / HTTP/1.1\r\nHost: x\r\n\r\n")
            await hw.drain()
            raw = await hr.read()
            hw.close()
            writer.write((json.dumps(
                {"record": recs[0], "model": "m"}) + "\n").encode())
            await writer.drain()
            scored = json.loads(await reader.readline())
            writer.close()
            await task
            return m, scored, raw

        m, scored, raw = asyncio.run(drive())
        assert m["ok"] and m["metrics"]["schema"] >= 1
        assert scored["ok"]
        head, _, body = raw.partition(b"\r\n\r\n")
        assert b"200 OK" in head
        doc = json.loads(body)
        assert doc["schema"] >= 1
        assert "latency_ms" in doc and "queue_depth" in doc

    def test_metrics_snapshot_fields(self, trained):
        model, recs, _pred = trained
        server, client = serve_in_process(
            {"m": model}, ServeConfig(max_wait_ms=5.0, sentinel=False))
        try:
            client.score_many([dict(r) for r in recs[:24]],
                              tenant="tenant-a")
            snap = server.metrics_snapshot()
        finally:
            server.stop()
        assert snap["requests"] == 24 and snap["rows"] == 24
        assert snap["answered"] == 24
        lat = snap["latency_ms"]["tenant-a"]
        assert lat["count"] == 24
        assert 0 < lat["p50_ms"] <= lat["p95_ms"] <= lat["p99_ms"]
        assert snap["plan_cache"]["resident"] == 1
        assert snap["plan_cache"]["misses"] >= 1
        assert "m/tenant-a" in snap["breakers"]
        assert snap["queue_depth"] == {"m/tenant-a": 0}
        assert snap["counters"]["serve_requests"] == 24


# ---------------------------------------------------------------------------
# the profile store
# ---------------------------------------------------------------------------

class TestProfileStore:
    def test_merge_accumulates_atomically(self, tmp_path):
        path = str(tmp_path / "state.json")
        store = ProfileStore(path)
        rec = {"calls": 1, "wall_seconds": 1.0, "compile_seconds": 0.4,
               "execute_seconds": 0.6, "rows": 64}
        assert store.record_profiles({"score:b64": rec})
        assert store.record_profiles({"score:b64": rec})
        got = store.profiles()["score:b64"]
        assert got["calls"] == 2 and got["wall_seconds"] == 2.0
        assert got["rows"] == 128 and got["updated"] > 0
        # no torn temp file left behind
        assert not os.path.exists(path + ".tmp")

    def test_sections_and_profiles_merge_independently(self, tmp_path):
        path = str(tmp_path / "state.json")
        store = ProfileStore(path)
        store.record_section("aot_restart", {"compiles": 0})
        # result sections and profiles share one store, merged
        # independently
        store.record_profiles({"family:GBT": {"calls": 1,
                                              "wall_seconds": 2.0}})
        store.record_section("fleet", {"replicas": 2})
        state = store.load()
        assert state["aot_restart"]["compiles"] == 0
        assert state["fleet"]["replicas"] == 2
        assert "family:GBT" in store.profiles()

    def test_gather_normalizes_bucket_labels(self, trained, tmp_path,
                                             monkeypatch):
        model, recs, _pred = trained
        plan = ScoringPlan(model).compile()
        plan.score([dict(r) for r in recs[:8]])
        records = gather_process_profiles()
        score_keys = [k for k in records if k.startswith("score:")]
        assert score_keys
        # plan ids are process-local: normalized out of the store key
        assert all(k.count(":") == 1 and k.split(":")[1].startswith("b")
                   for k in score_keys)
        monkeypatch.setenv("TX_PROFILE_STORE",
                           str(tmp_path / "profiles.json"))
        from transmogrifai_tpu.observability import \
            persist_process_profiles
        merged = persist_process_profiles()
        assert set(score_keys) <= set(merged)
        stored = ProfileStore().profiles("score:")
        assert stored


class TestLatencyHistogram:
    def test_quantiles_and_bounded_memory(self):
        h = LatencyHistogram(max_bins=32)
        rng = np.random.default_rng(0)
        for v in rng.exponential(0.01, size=2000):
            h.observe(float(v))
        d = h.to_json()
        assert d["count"] == 2000
        assert d["p50_ms"] < d["p95_ms"] < d["p99_ms"] <= d["max_ms"]
        assert h._hist.centroids.size <= 32

    def test_empty(self):
        assert LatencyHistogram().to_json() == {"count": 0}
