"""Preemption-tolerance tests (serving/state.py + cli/serve.py drain,
resume and supervision — docs/serving_restart.md).

The acceptance contracts, in the ISSUE's words:

- a warm-state snapshot captures the model-zoo manifest, per-bucket
  warm manifest, sentinel sketches, breaker states, plan-cache LRU
  order and telemetry high-water marks, and a ``--resume-state`` boot
  restores it: the recorded buckets score with ZERO new compiles;
- graceful drain: in-flight requests finish, late requests get the
  machine-readable ``draining`` answer, SIGTERM exits 0 with traces,
  profiles and a final snapshot flushed;
- a torn or schema-mismatched snapshot is a loud telemetry marker
  followed by a clean COLD start — never a crash;
- a rolling restart through the reconnecting TCP client is invisible:
  zero caller-observed failures across kill + resume;
- ``tx serve --supervise`` restarts a crashed child under backoff and
  trips a crash-loop breaker after ``--max-restarts`` crashes.

The subprocess drills (one SIGTERM incarnation, one resume incarnation,
two fast-crashing supervised children) are the slowest tests here;
everything else runs in-process against the real loop.
"""
import asyncio
import json
import os
import signal
import socket
import threading
import time

import numpy as np
import pytest

from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.models import LogisticRegression
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.runtime import FaultInjector, telemetry
from transmogrifai_tpu.serving import (SNAPSHOT_SCHEMA, CircuitBreaker,
                                       ServeConfig, ServeDraining,
                                       ServingServer,
                                       ServingStateSnapshot,
                                       StateManager, TcpServingClient,
                                       plan_compiles, serve_in_process)
from transmogrifai_tpu.serving.state import SNAPSHOT_FILE
from transmogrifai_tpu.types import PickList, Real, RealNN
from transmogrifai_tpu.workflow import Workflow


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


@pytest.fixture(scope="module")
def model_dir(trained, tmp_path_factory):
    model, _recs, _pred = trained
    d = str(tmp_path_factory.mktemp("saved") / "model")
    model.save(d)
    return d


# ---------------------------------------------------------------------------
# snapshot capture -> restore round trip (in-process)
# ---------------------------------------------------------------------------

class TestSnapshotRoundTrip:
    def test_warm_restore_zero_new_compiles_and_state_carried(
            self, trained, tmp_path):
        model, recs, _pred = trained
        server, client = serve_in_process(
            {"m": model}, ServeConfig(max_wait_ms=5.0))
        state_dir = str(tmp_path / "state")
        try:
            client.score_many([dict(r) for r in recs[:40]])
            answered = int(server.metrics.answered)
            mgr = StateManager(server, state_dir)
            assert mgr.write(reason="test") is True
            assert server.last_snapshot_at is not None
        finally:
            server.stop()
        with open(os.path.join(state_dir, SNAPSHOT_FILE)) as fh:
            doc = json.load(fh)
        assert doc["schema"] == SNAPSHOT_SCHEMA
        warm = doc["models"]["m"]["warm_buckets"]
        assert warm, "the served buckets must be recorded"
        assert doc["models"]["m"]["samples"], \
            "admitted records must be sampled for prewarm replay"
        assert doc["sentinels"]["m/default"]["rowsSeen"] == 40
        assert doc["counters"]["serving_rows_scored"] == 40

        # -- a fresh incarnation restores the document ----------------------
        telemetry.reset()
        server2 = ServingServer(ServeConfig(max_wait_ms=5.0))
        server2.add_model("m", model)
        out = StateManager(server2, state_dir).restore()
        assert out["mode"] == "warm" and out["restored"] is True
        assert out["warm_buckets"]["m"] == warm
        # every recorded bucket was prewarmed behind the gate: scoring
        # those shapes again compiles NOTHING
        entry = server2.plans.get("m")
        c0 = plan_compiles()
        for bucket in warm:
            entry.plan.score([dict(recs[0])] * bucket)
        assert plan_compiles() == c0
        # sentinel sketches, counters and answered carried over
        report = entry.guards["default"].sentinel.drift_report()
        assert report["rowsSeen"] == 40
        assert telemetry.counters()["serving_rows_scored"] == 40
        assert telemetry.counters()["serve_state_restores"] == 1
        assert server2.metrics.answered == answered
        assert server2.last_snapshot_at == doc["writtenAt"]

    def test_breaker_state_and_lru_order_survive_restart(
            self, trained, tmp_path):
        model, recs, _pred = trained
        clock = {"t": 100.0}
        config = ServeConfig(
            max_wait_ms=5.0, sentinel=False,
            breaker_factory=lambda: CircuitBreaker(
                failure_threshold=1, cooldown_seconds=30.0,
                clock=lambda: clock["t"]))
        server, client = serve_in_process(
            {"a": model, "b": model}, config)
        try:
            client.score(dict(recs[0]), model="a")
            client.score(dict(recs[0]), model="b")
            client.score(dict(recs[1]), model="a")   # LRU: b, then a
            br = server.plans.get("a").guards["default"].breaker
            br.record_failure()                      # threshold 1: OPEN
            assert br.state == br.OPEN
            clock["t"] = 110.0                       # 20s cooldown left
            snap = ServingStateSnapshot.from_json(
                ServingStateSnapshot.capture(server).to_json())
        finally:
            server.stop()
        assert snap.breakers["a/default"]["state"] == "open"
        assert abs(snap.breakers["a/default"]["openRemainingSeconds"]
                   - 20.0) < 0.5
        assert snap.lru == ["b", "a"]

        server2 = ServingServer(config)
        server2.add_model("a", model)
        server2.add_model("b", model)
        clock["t"] = 1000.0                          # a NEW monotonic era
        out = snap.restore(server2)
        assert out["mode"] == "warm"
        br2 = server2.plans.get("a").guards["default"].breaker
        assert br2.state == br2.OPEN
        assert br2.consecutive_failures == 1
        # the remaining cooldown survived the clock discontinuity
        remaining = br2.cooldown_seconds - (clock["t"] - br2.opened_at)
        assert abs(remaining - 20.0) < 0.5
        assert [n for n, _ in server2.plans.lru_order()] == ["b", "a"]

    def test_unregistered_in_memory_model_skipped_not_fatal(
            self, trained, tmp_path):
        model, recs, _pred = trained
        server, client = serve_in_process(
            {"m": model}, ServeConfig(max_wait_ms=5.0, sentinel=False))
        state_dir = str(tmp_path / "state")
        try:
            client.score(dict(recs[0]))
            assert StateManager(server, state_dir).write()
        finally:
            server.stop()
        # the next incarnation does NOT have the in-memory model (and
        # the snapshot has no dir to reload it from): restore skips it
        # loudly instead of crashing
        server2 = ServingServer(ServeConfig(sentinel=False))
        out = StateManager(server2, state_dir).restore()
        assert out["mode"] == "warm"
        assert out["models"] == []
        events = [e for e in telemetry.events_since(0)
                  if e["event"] == "serving_state_model_skipped"]
        assert events and events[0]["model"] == "m"


class TestLifecycleSlice:
    def test_generation_counter_and_history_restored(self, trained):
        from transmogrifai_tpu.serving.lifecycle import (LifecycleConfig,
                                                         ModelLifecycle)
        model, _recs, _pred = trained
        server = ServingServer(ServeConfig(sentinel=False))
        server.add_model("m", model)
        life = ModelLifecycle(server, LifecycleConfig())
        life.last_generation = 3
        life.history.append({"model": "m", "generation": 3,
                             "outcome": "committed"})
        doc = json.loads(json.dumps(life.state_dict()))

        server2 = ServingServer(ServeConfig(sentinel=False))
        life2 = ModelLifecycle(server2, LifecycleConfig())
        life2.load_state(doc)
        assert life2.history[-1]["generation"] == 3
        # the generation counter resumes ABOVE the high-water mark:
        # retrain artifacts of the new incarnation never collide
        assert next(life2._generations) == 4


# ---------------------------------------------------------------------------
# failure modes: torn / mismatched / injected — always a clean cold start
# ---------------------------------------------------------------------------

class TestFailureModes:
    def _manager(self, tmp_path):
        server = ServingServer(ServeConfig(sentinel=False))
        return StateManager(server, str(tmp_path))

    def test_missing_snapshot_is_cold(self, tmp_path):
        out = self._manager(tmp_path).restore()
        assert out == {"mode": "cold", "restored": False,
                       "reason": "no snapshot"}

    def test_torn_snapshot_cold_start_with_marker(self, tmp_path):
        mgr = self._manager(tmp_path)
        with open(mgr.path + ".tmp", "w") as fh:
            fh.write('{"schema": "tx-serving-state/1", "mod')
        os.replace(mgr.path + ".tmp", mgr.path)
        out = mgr.restore()
        assert out["mode"] == "cold" and out["reason"] == "torn snapshot"
        assert telemetry.counters()["serving_state_torn"] == 1

    def test_schema_mismatch_cold_start_with_marker(self, tmp_path):
        mgr = self._manager(tmp_path)
        with open(mgr.path + ".tmp", "w") as fh:
            json.dump({"schema": "tx-serving-state/999"}, fh)
        os.replace(mgr.path + ".tmp", mgr.path)
        out = mgr.restore()
        assert out["mode"] == "cold"
        assert out["reason"] == "schema mismatch"
        assert telemetry.counters()[
            "serving_state_schema_mismatch"] == 1

    def test_injected_restore_fault_degrades_to_cold(self, tmp_path):
        mgr = self._manager(tmp_path)
        assert mgr.write(reason="seed")              # a VALID snapshot
        with FaultInjector.plan("state:server:restore:1=oom"):
            out = mgr.restore()
        assert out["mode"] == "cold"
        assert "restore failed" in out["reason"]
        assert telemetry.counters()[
            "serving_state_restore_failures"] == 1
        # with the fault spent, the same file restores warm
        assert mgr.restore()["mode"] == "warm"

    def test_injected_torn_write_then_cold_restore(self, tmp_path):
        mgr = self._manager(tmp_path)
        with FaultInjector.plan("state:server:snapshot:1=torn"):
            assert mgr.write(reason="drill") is False
        assert telemetry.counters()[
            "serving_state_torn_writes"] == 1
        with open(mgr.path) as fh:                   # truncated on disk
            with pytest.raises(ValueError):
                json.load(fh)
        out = mgr.restore()
        assert out["mode"] == "cold" and out["reason"] == "torn snapshot"


# ---------------------------------------------------------------------------
# artifact-fingerprint drift gates the warm-bucket prewarm replay
# ---------------------------------------------------------------------------

class TestArtifactDriftGate:
    """The model dir was RE-SAVED between snapshot and resume: the
    snapshot's warm buckets describe programs that no longer exist.
    The restore must notice the PR-16 plan-fingerprint mismatch
    (``serving_state_artifact_drift``) and skip the prewarm replay —
    paying compiles to warm a stale lattice is worse than booting
    cold for that model."""

    def _train_and_save(self, path, drop_cat=False, seed=21):
        recs = _records(n=96, seed=seed)
        x = FeatureBuilder.of("x", Real).extract(
            lambda r: r.get("x")).as_predictor()
        z = FeatureBuilder.of("z", RealNN).extract(
            lambda r: r.get("z")).as_predictor()
        cat = FeatureBuilder.of("cat", PickList).extract(
            lambda r: r.get("cat")).as_predictor()
        label = FeatureBuilder.of("label", RealNN).extract(
            lambda r: r.get("label")).as_response()
        feats = [x, z] if drop_cat else [x, z, cat]
        pred = LogisticRegression(reg_param=0.01).set_input(
            label, transmogrify(feats)).get_output()
        model = (Workflow().set_result_features(pred)
                 .set_input_records(recs).train(validate="off"))
        model.save(path)
        return recs

    def test_resaved_model_skips_warm_replay(self, tmp_path,
                                             monkeypatch):
        monkeypatch.setenv("TX_AOT_EXPORT", "on")
        d = str(tmp_path / "model")
        recs = self._train_and_save(d)
        state_dir = str(tmp_path / "state")
        server, client = serve_in_process(
            {"m": d}, ServeConfig(max_wait_ms=5.0, sentinel=False))
        try:
            client.score_many([dict(r) for r in recs[:16]])
            # the incarnation serves from a real artifact store —
            # its fingerprint is what the snapshot records
            entry = server.plans.get("m")
            assert entry.plan.aot_summary() is not None
            assert StateManager(server, state_dir).write()
        finally:
            server.stop()
        # re-save a STRUCTURALLY different model to the same dir
        # (different feature set -> different plan fingerprint)
        self._train_and_save(d, drop_cat=True, seed=22)
        telemetry.reset()
        server2 = ServingServer(
            ServeConfig(max_wait_ms=5.0, sentinel=False))
        server2.add_model("m", d)
        out = StateManager(server2, state_dir).restore()
        try:
            assert out["mode"] == "warm" and out["restored"] is True
            # drift was detected and counted ...
            assert telemetry.counters()[
                "serving_state_artifact_drift"] >= 1
            # ... and the stale warm buckets were NOT replayed
            assert out["warm_buckets"]["m"] == []
        finally:
            server2.stop()

    def test_matching_fingerprint_still_replays(self, tmp_path,
                                                monkeypatch):
        monkeypatch.setenv("TX_AOT_EXPORT", "on")
        d = str(tmp_path / "model")
        recs = self._train_and_save(d)
        state_dir = str(tmp_path / "state")
        server, client = serve_in_process(
            {"m": d}, ServeConfig(max_wait_ms=5.0, sentinel=False))
        try:
            client.score_many([dict(r) for r in recs[:16]])
            assert StateManager(server, state_dir).write()
        finally:
            server.stop()
        telemetry.reset()
        server2 = ServingServer(
            ServeConfig(max_wait_ms=5.0, sentinel=False))
        server2.add_model("m", d)
        out = StateManager(server2, state_dir).restore()
        try:
            assert out["mode"] == "warm"
            assert out["warm_buckets"]["m"], \
                "same fingerprint must keep the warm replay"
            assert "serving_state_artifact_drift" not in \
                telemetry.counters()
        finally:
            server2.stop()


# ---------------------------------------------------------------------------
# graceful drain, in-process under concurrent load
# ---------------------------------------------------------------------------

class TestDrainInProcess:
    def test_inflight_finish_late_requests_refused(self, trained):
        model, recs, pred = trained
        server, client = serve_in_process(
            {"m": model},
            ServeConfig(max_wait_ms=150.0, target_batch=64,
                        sentinel=False))
        try:
            server.plans.get("m").plan.score(recs[:6])  # warm bucket 8
            futs = [client.submit(dict(recs[i])) for i in range(6)]
            deadline = time.monotonic() + 5.0
            while server.inflight < 6:                # all admitted
                assert time.monotonic() < deadline
                time.sleep(0.002)
            summary = asyncio.run_coroutine_threadsafe(
                server.drain(10.0), server.loop).result(timeout=15)
            assert summary["drained"] is True
            assert summary["inflight"] == 0
            # every in-flight request was ANSWERED, not dropped
            rows = [f.result(timeout=1) for f in futs]
            assert all(r[pred]["prediction"] in (0.0, 1.0)
                       for r in rows)
            # a late request gets the machine-readable refusal
            with pytest.raises(ServeDraining):
                client.score(dict(recs[0]))
            counters = telemetry.counters()
            assert counters["serve_drains"] == 1
            assert counters["serve_draining_rejections"] == 1
            assert server.process_block()["draining"] is True
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# metrics: the process block (schema v4), field set pinned
# ---------------------------------------------------------------------------

class TestProcessMetrics:
    def test_process_block_fields_pinned(self, trained):
        model, _recs, _pred = trained
        server = ServingServer(ServeConfig(sentinel=False))
        server.add_model("m", model)
        snap = server.metrics_snapshot()
        # v4 added the "admission" block (docs/admission.md)
        assert snap["schema"] == 5
        assert set(snap["process"]) == {
            "uptime_seconds", "restart_generation", "draining",
            "ready", "inflight", "last_snapshot_age_seconds"}
        assert snap["process"]["ready"] is True
        assert snap["process"]["draining"] is False
        assert snap["process"]["inflight"] == 0
        assert snap["process"]["last_snapshot_age_seconds"] is None
        assert snap["process"]["uptime_seconds"] >= 0.0
        assert isinstance(snap["plan_compiles"], int)

    def test_restart_generation_from_env(self, monkeypatch):
        monkeypatch.setenv("TX_SERVE_GENERATION", "7")
        server = ServingServer(ServeConfig(sentinel=False))
        assert server.process_block()["restart_generation"] == 7

    def test_snapshot_age_tracks_writes(self, trained, tmp_path):
        model, _recs, _pred = trained
        server = ServingServer(ServeConfig(sentinel=False))
        server.add_model("m", model)
        mgr = StateManager(server, str(tmp_path))
        assert mgr.write()
        age = server.process_block()["last_snapshot_age_seconds"]
        assert age is not None and age < 5.0


# ---------------------------------------------------------------------------
# the subprocess drills: SIGTERM flush, rolling restart, supervision
# (spawn/poll/teardown boilerplate lives in the shared fleet harness)
# ---------------------------------------------------------------------------

from fleet_util import (free_port as _free_port,                # noqa: E402
                        patient_retry as _patient_retry,
                        spawn_serve as _spawn_serve,
                        wait_ready as _wait_ready)


class TestRestartDrills:
    def test_sigterm_drains_flushes_and_snapshots(
            self, model_dir, trained, tmp_path):
        _model, recs, pred = trained
        port = _free_port()
        state = tmp_path / "state"
        trace_path = tmp_path / "trace.jsonl"
        store = tmp_path / "profiles.json"
        proc = _spawn_serve(
            model_dir, port, extra=("--state-dir", str(state)),
            env_extra={"TX_TRACE": str(trace_path),
                       "TX_PROFILE_PERSIST": "1",
                       "TX_PROFILE_STORE": str(store)})
        client = TcpServingClient("127.0.0.1", port,
                                  retry=_patient_retry())
        try:
            _wait_ready(port)
            for i in range(8):
                out = client.score(dict(recs[i]), model="m")
                assert out["ok"], out
                assert "prediction" in out["result"][pred]
            # the client is STILL attached: the process must be gone
            # within the drain timeout (default 30 s), not whenever
            # the client chooses to hang up (Server.wait_closed()
            # waits for every accepted connection since Python 3.12)
            proc.send_signal(signal.SIGTERM)
            stdout, _ = proc.communicate(timeout=30)
        finally:
            client.close()
            if proc.poll() is None:
                proc.kill()
                proc.communicate(timeout=30)
        assert proc.returncode == 0, stdout
        # the drain summary reached the final status line
        final = [json.loads(ln) for ln in stdout.splitlines()
                 if ln.startswith("{")]
        assert any("drain" in d for d in final), stdout
        # SIGTERM (not just a clean exit) flushed traces + profiles
        assert trace_path.exists() and trace_path.stat().st_size > 0
        assert store.exists()
        # and wrote the shutdown snapshot
        with open(state / SNAPSHOT_FILE) as fh:
            doc = json.load(fh)
        assert doc["schema"] == SNAPSHOT_SCHEMA
        assert doc["models"]["m"]["dir"] == model_dir
        assert doc["models"]["m"]["warm_buckets"]

    def test_rolling_restart_warm_resume_zero_client_failures(
            self, model_dir, trained, tmp_path):
        _model, recs, _pred = trained
        port = _free_port()
        state = str(tmp_path / "state")
        proc1 = _spawn_serve(model_dir, port,
                             extra=("--state-dir", state))
        failures, answered = [], {"n": 0}
        stop_flag = threading.Event()

        def pump():
            client = TcpServingClient("127.0.0.1", port,
                                      retry=_patient_retry(),
                                      timeout=5.0)
            i = 0
            while not stop_flag.is_set():
                try:
                    out = client.score(dict(recs[i % 64]), model="m")
                    if out.get("ok"):
                        answered["n"] += 1
                    else:
                        failures.append(out)
                except Exception as e:   # noqa: BLE001 - tallied
                    failures.append(repr(e))
            client.close()

        proc2 = None
        thread = threading.Thread(target=pump, daemon=True)
        try:
            _wait_ready(port)
            thread.start()
            deadline = time.monotonic() + 30
            while answered["n"] < 20:        # live traffic flowing
                assert time.monotonic() < deadline
                time.sleep(0.05)
            # -- kill incarnation 1 MID-STREAM --------------------------
            proc1.send_signal(signal.SIGTERM)
            out1, _ = proc1.communicate(timeout=90)
            assert proc1.returncode == 0, out1
            # -- incarnation 2 resumes from the snapshot ----------------
            proc2 = _spawn_serve(
                model_dir, port, extra=("--resume-state", state),
                env_extra={"TX_SERVE_GENERATION": "2"})
            _wait_ready(port)
            n_at_ready = answered["n"]
            deadline = time.monotonic() + 30
            while answered["n"] < n_at_ready + 20:
                assert time.monotonic() < deadline
                time.sleep(0.05)
            # steady state after the warm restart: zero new compiles
            with TcpServingClient("127.0.0.1", port,
                                  retry=_patient_retry()) as probe:
                snap = probe.metrics()
                assert snap["process"]["restart_generation"] == 2
                c0 = snap["plan_compiles"]
                time.sleep(1.0)
                snap2 = probe.metrics()
                assert snap2["plan_compiles"] == c0
            stop_flag.set()
            thread.join(timeout=60)
            proc2.send_signal(signal.SIGTERM)
            out2, _ = proc2.communicate(timeout=90)
        finally:
            stop_flag.set()
            for p in (proc1, proc2):
                if p is not None and p.poll() is None:
                    p.kill()
                    p.communicate(timeout=30)
        # the rolling restart was INVISIBLE to the caller
        assert failures == []
        assert answered["n"] >= 40
        assert proc2.returncode == 0, out2
        resume = [json.loads(ln) for ln in out2.splitlines()
                  if ln.startswith('{"resume"')]
        assert resume and resume[0]["resume"]["mode"] == "warm", out2
        assert resume[0]["resume"]["warm_buckets"]["m"]

    def test_supervisor_crash_loop_breaker_trips(self, model_dir):
        # occupy the port so every supervised child dies at bind
        blocker = socket.socket()
        blocker.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        blocker.bind(("127.0.0.1", 0))
        blocker.listen(1)
        port = blocker.getsockname()[1]
        proc = _spawn_serve(
            model_dir, port,
            extra=("--supervise", "--max-restarts", "2",
                   "--restart-window", "300"),
            env_extra={"TX_RETRY_BASE_DELAY_S": "0.05",
                       "TX_RETRY_MAX_DELAY_S": "0.1"})
        try:
            stdout, _ = proc.communicate(timeout=300)
        finally:
            blocker.close()
            if proc.poll() is None:
                proc.kill()
                proc.communicate(timeout=30)
        assert proc.returncode == 1, stdout
        events = [json.loads(ln) for ln in stdout.splitlines()
                  if ln.startswith('{"supervisor"')]
        kinds = [e["supervisor"] for e in events]
        assert kinds.count("spawned") == 2       # original + 1 restart
        assert kinds.count("crashed") == 2
        assert kinds[-1] == "crash_loop_breaker"
        gens = [e["generation"] for e in events
                if e["supervisor"] == "spawned"]
        assert gens == [1, 2]
