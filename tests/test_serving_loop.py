"""Serving-loop tests (transmogrifai_tpu/serving/server.py + cli/serve.py).

The acceptance contracts, in the ISSUE's words:

- a spawned in-process loop scores 100 CONCURRENT requests with zero
  plan recompiles after warmup and per-request results bitwise
  identical to offline ``score_guarded()`` on the same rows;
- deadline-or-full coalescing: a short queue dispatches at the
  ``max_wait_ms`` deadline, a filled bucket dispatches early;
- breaker trip -> host fallback -> half-open recovery MID-STREAM, with
  per-tenant isolation (one tenant's trip must not stall another's
  queue), plus a ``TX_FAULT_PLAN`` hang drill proving the per-batch
  deadline ORPHANS the dispatch without wedging the loop;
- the multi-model plan cache evicts under its LRU budget (counted)
  and transparently recompiles on next use;
- ``ScoringPlan.bucket_profile()`` records per-bucket dispatch cost
  and the coalescer derives its target from it;
- ``streaming_score`` reuses ONE plan across the batches of a run
  (``plan_compiles()`` flat after the first batch).

Everything here must stay tier-1-safe on a 1-CPU container: one small
trained model per module, short waits, sub-second fault drills.
"""
import asyncio
import json
import time

import numpy as np
import pytest

from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.models import LogisticRegression
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.runtime import FaultInjector, telemetry
from transmogrifai_tpu.serving import (CircuitBreaker, PlanCache,
                                       ScoringPlan, ServeConfig,
                                       ServeRejected, ServingServer,
                                       plan_compiles, serve_in_process)
from transmogrifai_tpu.types import PickList, Real, RealNN
from transmogrifai_tpu.workflow import Workflow
from transmogrifai_tpu.workflow.runner import WorkflowRunner

@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


def _records(n=160, seed=5):
    rng = np.random.default_rng(seed)
    cats = ["a", "b", "c"]
    recs = []
    for _ in range(n):
        x = float(rng.normal())
        z = float(rng.uniform(0, 4))
        recs.append({"x": x, "z": z,
                     "cat": cats[int(rng.integers(0, len(cats)))],
                     "label": float(x + 0.5 * rng.normal() > 0)})
    return recs


@pytest.fixture(scope="module")
def trained():
    recs = _records()
    x = FeatureBuilder.of("x", Real).extract(
        lambda r: r.get("x")).as_predictor()
    z = FeatureBuilder.of("z", RealNN).extract(
        lambda r: r.get("z")).as_predictor()
    cat = FeatureBuilder.of("cat", PickList).extract(
        lambda r: r.get("cat")).as_predictor()
    label = FeatureBuilder.of("label", RealNN).extract(
        lambda r: r.get("label")).as_response()
    pred = LogisticRegression(reg_param=0.01).set_input(
        label, transmogrify([x, z, cat])).get_output()
    model = (Workflow().set_result_features(pred)
             .set_input_records(recs).train(validate="off"))
    return model, recs, pred.name


def _warm_buckets(server, name, recs, up_to=128):
    """Pre-compile every bucket program a <=up_to-row batch can hit,
    through the server's own resident plan (so any coalescing split
    the loop picks lands on a warm shape)."""
    entry = server.plans.get(name)
    size = 1
    while size <= up_to:
        entry.plan.score(recs[:size])
        size *= 2
    return entry


# ---------------------------------------------------------------------------
# the tier-1 smoke: concurrency, zero recompiles, bitwise parity
# ---------------------------------------------------------------------------

class TestServerSmoke:
    def test_100_concurrent_requests_bitwise_parity_zero_recompiles(
            self, trained):
        model, recs, pred = trained
        batch = [dict(r) for r in (recs * 2)[:100]]
        offline = (ScoringPlan(model).compile()
                   .with_guardrails(sentinel=False)
                   .score_guarded(batch).scored[pred])
        server, client = serve_in_process(
            {"m": model}, ServeConfig(max_wait_ms=10.0, sentinel=False))
        try:
            _warm_buckets(server, "m", batch)
            client.score_many(batch[:16])          # warm the loop path
            c0 = plan_compiles()
            rows = client.score_many(batch)
            assert plan_compiles() == c0           # zero new programs
            n_prob = offline.probability.shape[1]
            for i, row in enumerate(rows):
                v = row[pred]
                assert v["prediction"] == offline.data[i]
                probs = np.array([v[f"probability_{j}"]
                                  for j in range(n_prob)])
                assert np.array_equal(probs, offline.probability[i])
            d = server.describe()
            assert d["requests"] == 116 and d["rows"] == 116
            # concurrent submits coalesced into shared dispatches
            assert d["mean_batch_occupancy"] > 2.0
            assert 0.0 <= d["dispatch_saturation"] <= 1.0
        finally:
            server.stop()

    def test_unlabelled_record_scores_like_score_function(self, trained):
        """A serving request carries no label. With guardrails on (the
        CLI default) the response column is a placeholder, and the
        answer equals ``ScoreFunction`` on the same record."""
        from transmogrifai_tpu.local import ScoreFunction
        model, recs, pred = trained
        record = {k: v for k, v in recs[0].items() if k != "label"}
        want = ScoreFunction(model)(dict(record))
        labelled = ScoreFunction(model)(dict(recs[0]))
        server, client = serve_in_process(
            {"m": model}, ServeConfig(max_wait_ms=5.0))
        try:
            assert server.config.guardrails
            row = client.score(dict(record))
        finally:
            server.stop()
        assert "_guard" not in row
        assert set(row) == set(want)
        assert row[pred].keys() == want[pred].keys()
        for k, v in want[pred].items():
            assert row[pred][k] == pytest.approx(v, abs=1e-9)
            # ... and the label plays no part in the score
            assert labelled[pred][k] == pytest.approx(v, abs=1e-9)

    def test_deadline_or_full(self, trained):
        model, recs, _ = trained
        server, client = serve_in_process(
            {"m": model},
            ServeConfig(max_wait_ms=60.0, target_batch=4,
                        sentinel=False))
        try:
            _warm_buckets(server, "m", recs, up_to=8)
            # 2 requests < target 4: the batch waits the full deadline
            t0 = time.perf_counter()
            client.score_many(recs[:2])
            waited = time.perf_counter() - t0
            assert waited >= 0.055
            assert server.stats["deadline_dispatches"] >= 1
            full0 = server.stats["full_dispatches"]
            # 8 requests: the bucket fills and fires WITHOUT the wait
            t0 = time.perf_counter()
            client.score_many(recs[:8])
            assert server.stats["full_dispatches"] > full0
            assert time.perf_counter() - t0 < 0.5
        finally:
            server.stop()

    def test_quarantine_reasons_per_request(self, trained):
        model, recs, pred = trained
        bad = {"x": "not-a-number", "z": None, "cat": "a"}
        batch = [dict(r) for r in recs[:6]] + [bad]
        server, client = serve_in_process(
            {"m": model}, ServeConfig(max_wait_ms=10.0, sentinel=False))
        try:
            rows = client.score_many(batch)
            assert all("_guard" not in r for r in rows[:6])
            assert all(r[pred]["prediction"] in (0.0, 1.0)
                       for r in rows[:6])
            guard = rows[6]["_guard"]
            assert rows[6][pred] is None
            assert {g["code"] for g in guard} >= {"missing_field"}
            assert telemetry.counters()["serving_rows_quarantined"] == 1
        finally:
            server.stop()

    def test_queue_backpressure_rejects(self, trained):
        model, recs, _ = trained
        server, client = serve_in_process(
            {"m": model},
            ServeConfig(max_wait_ms=250.0, target_batch=64,
                        queue_limit=1, sentinel=False))
        try:
            futs = [client.submit(dict(recs[i])) for i in range(4)]
            outcomes = []
            for f in futs:
                try:
                    f.result(timeout=10)
                    outcomes.append("ok")
                except ServeRejected:
                    outcomes.append("rejected")
            assert outcomes[0] == "ok"
            assert outcomes.count("rejected") == 3
            assert telemetry.counters()["serve_queue_rejections"] == 3
        finally:
            server.stop()

    def test_sentinel_fed_from_live_stream(self, trained):
        model, recs, _ = trained
        server, client = serve_in_process(
            {"m": model}, ServeConfig(max_wait_ms=10.0))  # sentinel ON
        try:
            client.score_many([dict(r) for r in recs[:80]])
            guards = server.plans.get("m").guards["default"]
            assert guards.sentinel is not None
            report = guards.sentinel.drift_report()
            # every served (non-quarantined) row reached the sketches
            assert report["rowsSeen"] == 80
            assert report["status"] == "ok"
        finally:
            server.stop()

    def test_multi_tenant_sentinel_isolation(self, trained):
        """One drifted tenant escalates; a second tenant on the SAME
        model keeps its own healthy sentinel and bitwise-stable
        results (docs/self_healing.md — the detection contract the
        lifecycle manager arms on)."""
        from transmogrifai_tpu.serving import DriftThresholds
        model, recs, pred = trained
        server, client = serve_in_process(
            {"m": model},
            ServeConfig(max_wait_ms=10.0,
                        drift_thresholds=DriftThresholds(
                            warn=0.3, degrade=0.5, min_rows=24)))
        try:
            _warm_buckets(server, "m", recs, up_to=64)
            normal = [dict(r) for r in recs[:32]]
            rng = np.random.default_rng(11)
            drifted = [{"x": float(rng.normal() + 5.0),
                        "z": float(rng.uniform(0, 4)),
                        "cat": "a", "label": 1.0} for _ in range(64)]
            base_b = client.score_many(normal, tenant="b")
            client.score_many(drifted, tenant="a")
            again_b = client.score_many(normal, tenant="b")
            guards = server.plans.get("m").guards
            assert guards["a"].sentinel.drift_report()["status"] \
                == "degrade"
            assert guards["b"].sentinel.drift_report()["status"] == "ok"
            # the healthy tenant's results never moved
            for r0, r1 in zip(base_b, again_b):
                assert r0[pred] == r1[pred]
            # the metrics endpoint splits the two lanes
            snap = server.metrics_snapshot()
            assert snap["sentinels"]["m/a"]["status"] == "degrade"
            assert snap["sentinels"]["m/b"]["status"] == "ok"
            assert snap["sentinels"]["m/a"]["features"]["x"][
                "status"] == "degrade"
        finally:
            server.stop()

    def test_unknown_model_rejected(self, trained):
        model, recs, _ = trained
        server, client = serve_in_process({"m": model}, ServeConfig())
        try:
            with pytest.raises(ServeRejected, match="unknown model"):
                client.score(dict(recs[0]), model="nope")
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# breaker mid-stream + per-tenant isolation + the hang drill
# ---------------------------------------------------------------------------

class TestBreakerMidStream:
    def test_trip_fallback_halfopen_recovery_tenant_isolated(
            self, trained, monkeypatch):
        monkeypatch.setenv("TX_RETRY_MAX_ATTEMPTS", "1")
        model, recs, pred = trained
        clock = {"t": 0.0}
        server, client = serve_in_process(
            {"m": model},
            ServeConfig(
                max_wait_ms=5.0, sentinel=False,
                breaker_factory=lambda: CircuitBreaker(
                    failure_threshold=2, cooldown_seconds=30.0,
                    clock=lambda: clock["t"])))
        try:
            _warm_buckets(server, "m", recs, up_to=8)
            r = dict(recs[0])
            # -- trip tenant A's breaker with persistent device faults
            with FaultInjector.plan("plan:device:dispatch:*=oom"):
                a1 = client.score(r, tenant="A")   # failure 1
                a2 = client.score(r, tenant="A")   # failure 2: OPEN
            assert a1.get("_host_fallback") and a2.get("_host_fallback")
            # host fallback still served REAL scores
            assert a1[pred]["prediction"] in (0.0, 1.0)

            # -- mid-stream: A short-circuits to the fallback pool,
            #    tenant B's queue keeps dispatching to the device lane
            fa = client.submit(r, tenant="A")
            fb = [client.submit(dict(recs[i]), tenant="B")
                  for i in range(4)]
            a3 = fa.result(timeout=30)
            b_rows = [f.result(timeout=30) for f in fb]
            assert a3.get("_host_fallback")        # breaker open
            assert all("_host_fallback" not in b for b in b_rows)
            counters = telemetry.counters()
            assert counters["breaker_trips"] == 1
            assert counters["serving_breaker_short_circuits"] >= 1
            assert counters["serving_device_failures"] == 2

            # -- cooldown elapses: half-open probe recovers tenant A
            clock["t"] = 31.0
            a4 = client.score(r, tenant="A")
            assert "_host_fallback" not in a4
            counters = telemetry.counters()
            assert counters["breaker_recoveries"] == 1
            assert counters["breaker_half_open"] == 1
        finally:
            server.stop()

    def test_hang_drill_deadline_orphans_without_wedging(
            self, trained, monkeypatch):
        monkeypatch.setenv("TX_RETRY_MAX_ATTEMPTS", "1")
        model, recs, pred = trained
        server, client = serve_in_process(
            {"m": model},
            ServeConfig(max_wait_ms=5.0, sentinel=False,
                        deadline_seconds=0.25))
        try:
            _warm_buckets(server, "m", recs, up_to=8)
            t0 = time.perf_counter()
            with FaultInjector.plan("plan:device:dispatch:1=hang:1.2"):
                row = client.score(dict(recs[0]))
            elapsed = time.perf_counter() - t0
            # the batch fell back at the deadline — it did NOT wait
            # out the 1.2s hang
            assert row.get("_host_fallback")
            assert row[pred]["prediction"] in (0.0, 1.0)
            assert elapsed < 1.0
            assert server.stats["orphaned_dispatches"] == 1
            assert telemetry.counters()["serving_deadline_exceeded"] == 1
            # the loop is NOT wedged behind the orphaned thread: the
            # next batch dispatches on a fresh device lane
            row2 = client.score(dict(recs[1]))
            assert "_host_fallback" not in row2
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# multi-model plan cache
# ---------------------------------------------------------------------------

class TestPlanCache:
    def test_lru_eviction_counted_and_recompiles(self, trained):
        model, recs, pred = trained
        cache = PlanCache(budget=1)
        cache.register("a", model)
        cache.register("b", model)
        ea = cache.get("a")
        assert cache.get("a") is ea                # hit, no eviction
        assert cache.evictions == 0
        cache.get("b")                             # evicts "a"
        assert cache.evictions == 1
        ea2 = cache.get("a")                       # miss: recompiled
        assert ea2 is not ea and cache.evictions == 2
        counters = telemetry.counters()
        assert counters["serve_plan_cache_evictions"] == 2
        assert counters["serve_plan_cache_misses"] == 3
        assert counters["serve_plan_cache_hits"] == 1
        # the recompiled plan still scores correctly
        scored = ea2.plan.score(recs[:4])
        assert np.isfinite(scored[pred].data).all()

    def test_server_serves_a_model_zoo(self, trained):
        model, recs, pred = trained
        server, client = serve_in_process(
            {"one": model, "two": model},
            ServeConfig(max_wait_ms=10.0, sentinel=False,
                        plan_budget=2))
        try:
            r1 = client.score(dict(recs[0]), model="one")
            r2 = client.score(dict(recs[0]), model="two")
            assert r1[pred] == r2[pred]            # same fitted model
            assert server.plans.evictions == 0
            assert sorted(server.describe()["models"]) == ["one", "two"]
        finally:
            server.stop()

    def test_budget_validated(self):
        with pytest.raises(ValueError):
            PlanCache(budget=0)


# ---------------------------------------------------------------------------
# bucket profile -> coalescer threshold (satellite 2)
# ---------------------------------------------------------------------------

class TestBucketProfile:
    def test_profile_records_per_bucket_cost(self, trained):
        model, recs, _ = trained
        plan = ScoringPlan(model).compile()
        plan.score(recs[:5])                       # bucket 8
        plan.score(recs[:60])                      # bucket 64
        plan.score(recs[:60])
        prof = plan.bucket_profile()
        assert set(prof) >= {8, 64}
        assert prof[8]["calls"] == 1 and prof[8]["rows"] == 5
        assert prof[64]["calls"] == 2 and prof[64]["rows"] == 120
        for rec in prof.values():
            assert rec["wall_seconds"] >= 0.0
            assert rec["execute_seconds"] <= rec["wall_seconds"] + 1e-9

    def test_coalescer_target_derived_from_profile(self, trained):
        model, recs, _ = trained
        server = ServingServer(ServeConfig(max_wait_ms=50.0))
        server.add_model("m", model)
        entry = server.plans.get("m")
        entry.plan.score(recs[:60])                # cold: compile-heavy
        entry.plan.score(recs[:60])                # warm call
        target = server._target_batch(entry.plan)
        # a recorded warm bucket whose dispatch fits the wait budget
        # becomes the threshold; with no profile it falls back to 64
        assert target >= 8
        explicit = ServingServer(ServeConfig(target_batch=16))
        assert explicit._target_batch(entry.plan) == 16


# ---------------------------------------------------------------------------
# streaming_score plan reuse (satellite 1)
# ---------------------------------------------------------------------------

class TestStreamingPlanReuse:
    def test_plan_compiles_flat_across_stream(self, trained):
        model, recs, pred = trained
        runner = WorkflowRunner()
        runner.model = model
        batches = [recs[i * 16:(i + 1) * 16] for i in range(5)]
        gen = runner.streaming_score(batches)
        first = next(gen)                          # warm: bucket 16
        assert "prediction" in first[0][pred]
        c0 = plan_compiles()
        rest = list(gen)
        assert plan_compiles() == c0               # ONE plan, reused
        assert [len(b) for b in rest] == [16, 16, 16, 16]

    def test_guarded_stream_reuses_one_plan_and_sentinel(self, trained):
        model, recs, pred = trained
        runner = WorkflowRunner()
        runner.model = model
        batches = [recs[i * 16:(i + 1) * 16] for i in range(4)]
        gen = runner.streaming_score(batches, guardrails=True)
        next(gen)
        c0 = plan_compiles()
        list(gen)
        assert plan_compiles() == c0
        # guardrail state persisted across batches: one ledger object,
        # counters accumulated over the whole stream
        assert telemetry.counters()["serving_rows_scored"] == 64


# ---------------------------------------------------------------------------
# the CLI TCP front end (cli/serve.py), driven in-process
# ---------------------------------------------------------------------------

class TestServeTcp:
    def test_json_lines_roundtrip(self, trained, capsys):
        model, recs, pred = trained
        from transmogrifai_tpu.cli.serve import serve_forever

        async def drive():
            server = ServingServer(
                ServeConfig(max_wait_ms=5.0, sentinel=False))
            server.add_model("m", model)
            port_box = {}
            task = asyncio.ensure_future(serve_forever(
                server, "127.0.0.1", 0, max_requests=3,
                ready_cb=lambda p: port_box.setdefault("p", p)))
            while "p" not in port_box:
                await asyncio.sleep(0.005)
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port_box["p"])
            for i in range(2):
                writer.write((json.dumps(
                    {"record": recs[i], "model": "m"}) + "\n").encode())
            writer.write(b'{"record": {}, "model": "nope"}\n')
            await writer.drain()
            outs = [json.loads(await reader.readline())
                    for _ in range(3)]
            writer.close()
            await task
            return outs

        outs = asyncio.run(drive())
        assert outs[0]["ok"] and outs[1]["ok"]
        assert "prediction" in outs[0]["result"][pred]
        assert not outs[2]["ok"] and "unknown model" in outs[2]["error"]


class TestTcpClient:
    """serving/client.py: the reconnecting line-JSON client — bounded
    exponential backoff via runtime RetryPolicy, resend on transport
    failure, no retry of application errors."""

    RETRY = None  # set in _retry() to avoid import-time work

    def _retry(self):
        from transmogrifai_tpu.runtime.retry import RetryPolicy
        return RetryPolicy(max_attempts=3, base_delay=0.01,
                           max_delay=0.02)

    def test_unreachable_raises_serving_unavailable(self):
        import socket
        from transmogrifai_tpu.serving import (ServingUnavailable,
                                               TcpServingClient)
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()                      # nothing listens here now
        client = TcpServingClient("127.0.0.1", port,
                                  retry=self._retry(), timeout=0.5)
        with pytest.raises(ServingUnavailable, match="unreachable"):
            client.connect()

    def test_reconnects_and_resends_after_server_drop(self):
        import socket
        import threading
        from transmogrifai_tpu.serving import TcpServingClient
        srv = socket.socket()
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("127.0.0.1", 0))
        srv.listen(2)
        port = srv.getsockname()[1]
        seen = []

        def run():
            # connection 1: read the request, then DROP it (restart)
            conn, _ = srv.accept()
            seen.append(conn.makefile("r").readline())
            conn.close()
            # connection 2: answer properly
            conn, _ = srv.accept()
            fh = conn.makefile("rw")
            seen.append(fh.readline())
            fh.write(json.dumps({"ok": True, "result": {"y": 1}})
                     + "\n")
            fh.flush()
            conn.close()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        try:
            with TcpServingClient("127.0.0.1", port,
                                  retry=self._retry()) as client:
                out = client.request({"record": {"x": 1.0}})
            assert out == {"ok": True, "result": {"y": 1}}
            t.join(timeout=5)
            # the SAME payload was resent on the fresh connection
            assert len(seen) == 2 and seen[0] == seen[1]
            assert telemetry.counters()[
                "serve_client_reconnects"] >= 1
        finally:
            srv.close()

    def test_late_duplicate_reply_deduped_on_request_id(self):
        # a resend racing a late reply: the stream carries a leftover
        # answer for an EARLIER abandoned request before the real
        # one — the client must surface only the reply echoing its
        # own id, and count the duplicate
        import socket
        import threading
        from transmogrifai_tpu.serving import TcpServingClient
        srv = socket.socket()
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        port = srv.getsockname()[1]

        def run():
            conn, _ = srv.accept()
            fh = conn.makefile("rw")
            fh.readline()
            # the late reply to an abandoned earlier send...
            fh.write(json.dumps({"ok": True, "request_id": "old-7",
                                 "result": {"stale": True}}) + "\n")
            # ...then the real answer
            fh.write(json.dumps({"ok": True, "request_id": "req-1",
                                 "result": {"y": 2}}) + "\n")
            fh.flush()
            conn.close()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        try:
            with TcpServingClient("127.0.0.1", port,
                                  retry=self._retry()) as client:
                out = client.request({"record": {"x": 1.0},
                                      "id": "req-1"})
            assert out["request_id"] == "req-1"
            assert out["result"] == {"y": 2}
            assert telemetry.counters()[
                "serve_client_duplicate_replies"] == 1
            t.join(timeout=5)
        finally:
            srv.close()

    def test_untagged_request_keeps_first_reply(self):
        # without an id there is nothing to dedupe against — the
        # first line is the answer, exactly as before
        import socket
        import threading
        from transmogrifai_tpu.serving import TcpServingClient
        srv = socket.socket()
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        port = srv.getsockname()[1]

        def run():
            conn, _ = srv.accept()
            fh = conn.makefile("rw")
            fh.readline()
            fh.write(json.dumps({"ok": True, "request_id": "srv-1",
                                 "result": {"y": 3}}) + "\n")
            fh.flush()
            conn.close()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        try:
            with TcpServingClient("127.0.0.1", port,
                                  retry=self._retry()) as client:
                out = client.request({"record": {"x": 1.0}})
            assert out["result"] == {"y": 3}
            assert "serve_client_duplicate_replies" not in \
                telemetry.counters()
            t.join(timeout=5)
        finally:
            srv.close()

    def test_scores_against_the_real_loop(self, trained):
        import threading
        from transmogrifai_tpu.cli.serve import serve_forever
        from transmogrifai_tpu.serving import TcpServingClient
        model, recs, pred = trained
        server = ServingServer(
            ServeConfig(max_wait_ms=5.0, sentinel=False))
        server.add_model("m", model)
        port_box = {}

        def run():
            asyncio.run(serve_forever(
                server, "127.0.0.1", 0, max_requests=3,
                ready_cb=lambda p: port_box.setdefault("p", p)))

        t = threading.Thread(target=run, daemon=True)
        t.start()
        while "p" not in port_box:
            time.sleep(0.005)
        with TcpServingClient("127.0.0.1", port_box["p"],
                              retry=self._retry()) as client:
            out = client.score(dict(recs[0]), model="m",
                               request_id="r-1")
            assert out["ok"] and out["request_id"] == "r-1"
            assert "prediction" in out["result"][pred]
            bad = client.score(dict(recs[1]), model="nope")
            # an ANSWERED error is returned, not retried
            assert bad["ok"] is False
            snap = client.metrics()
            assert snap["schema"] >= 2 and snap["answered"] >= 1
        t.join(timeout=10)
