"""Fleet router unit tests — the tier-1 in-process path
(docs/fleet.md).

Everything here runs against FAKE asyncio replicas (a few dozen lines
of JSON-lines server each): no jax, no subprocesses, no model
training — so the full placement / failover / draining / merged-
admission / fault-drill surface stays inside tier-1's time budget.
The real multi-process drills (kill a replica, rolling deploy) live
in test_fleet.py behind the ``slow`` marker.
"""
import asyncio
import json

import pytest

from transmogrifai_tpu.runtime import FaultInjector, telemetry
from transmogrifai_tpu.runtime.retry import RetryPolicy
from transmogrifai_tpu.serving.router import (BackendUnavailable,
                                              FleetRouter,
                                              ReplicaHandle,
                                              RouterConfig,
                                              _BackendLink,
                                              merge_admission)


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


class _NullCostModel:
    """Placement falls back to the config's priors — deterministic."""

    def predict(self, key, bucket=None):
        class _E:
            wall = None
            compile = None
        return _E()


def _fast_retry():
    return RetryPolicy(max_attempts=3, base_delay=0.01,
                       max_delay=0.02)


class FakeReplica:
    """A JSON-lines server that answers like a serve child. ``mode``
    switches the verdict: ok / draining / shed / drop (close the
    connection without answering — the transport-failure drill) /
    flaky (drop the first ``drops_left`` score requests, then answer
    normally — the transient-blip drill) / stale (emit a
    wrong-request_id line before the real answer)."""

    def __init__(self, name, mode="ok", drops_left=1):
        self.name = name
        self.mode = mode
        self.drops_left = drops_left
        self.requests = []
        self.admission = {"enabled": True, "state": "ok",
                          "pressure": 0.1, "drain_rows_per_s": 100.0,
                          "queue_depth": {}, "transitions": 0}
        self.server = None
        self.port = None

    async def start(self):
        self.server = await asyncio.start_server(
            self._handle, "127.0.0.1", 0)
        self.port = self.server.sockets[0].getsockname()[1]
        return self

    async def stop(self):
        # close() only: since Python 3.12 Server.wait_closed() waits for
        # every accepted connection, and the router's links stay open
        if self.server is not None:
            self.server.close()
            self.server = None

    async def _handle(self, reader, writer):
        try:
            while True:
                line = await reader.readline()
                if not line:
                    break
                msg = json.loads(line)
                if msg.get("metrics"):
                    out = {"ok": True, "metrics": {
                        "admission": self.admission,
                        "plan_compiles": 0, "answered": 0}}
                elif msg.get("ready"):
                    out = {"ok": True, "ready": True}
                else:
                    self.requests.append(msg)
                    rid = msg.get("id")
                    if self.mode == "drop":
                        writer.close()
                        return
                    if self.mode == "flaky" and self.drops_left > 0:
                        self.drops_left -= 1
                        writer.close()
                        return
                    if self.mode == "draining":
                        out = {"ok": False, "request_id": rid,
                               "draining": True,
                               "error": "draining for restart",
                               "kind": "transient"}
                    elif self.mode == "shed":
                        out = {"ok": False, "request_id": rid,
                               "shed": True, "retry_after_ms": 7,
                               "error": "overload",
                               "kind": "transient"}
                    else:
                        if self.mode == "stale":
                            stale = {"ok": True,
                                     "request_id": "stale-0",
                                     "result": {"from": "the past"}}
                            writer.write(
                                (json.dumps(stale) + "\n").encode())
                        out = {"ok": True, "request_id": rid,
                               "result": {"replica": self.name},
                               "replica": self.name}
                writer.write((json.dumps(out) + "\n").encode())
                await writer.drain()
        except (OSError, ConnectionError):
            pass
        finally:
            writer.close()


def _router(**cfg):
    config = RouterConfig(**{"admission_poll_s": 0.05,
                             "forward_timeout": 2.0, **cfg})
    r = FleetRouter(config=config, cost_model=_NullCostModel(),
                    retry=_fast_retry())
    r.default_model = "m"
    return r


async def _fleet(router, *replicas):
    out = []
    for rep in replicas:
        await rep.start()
        router.register_replica(rep.name, "127.0.0.1", rep.port)
        out.append(rep)
    return out


# ---------------------------------------------------------------------------
# merged admission math (pure function)
# ---------------------------------------------------------------------------

class TestMergeAdmission:
    def test_worst_state_wins(self):
        merged = merge_admission({
            "r0": {"enabled": True, "state": "ok", "pressure": 0.1,
                   "drain_rows_per_s": 100.0, "queue_depth": {}},
            "r1": {"enabled": True, "state": "brownout",
                   "pressure": 0.8, "drain_rows_per_s": 50.0,
                   "queue_depth": {"t": 10}}})
        assert merged["state"] == "brownout"
        assert merged["pressure"] == 0.8

    def test_drain_rate_sums_and_hint_derives(self):
        merged = merge_admission({
            "r0": {"enabled": True, "state": "shed", "pressure": 1.5,
                   "drain_rows_per_s": 100.0,
                   "queue_depth": {"a": 30, "b": 20}},
            "r1": {"enabled": True, "state": "ok", "pressure": 0.2,
                   "drain_rows_per_s": 150.0, "queue_depth": {}}})
        assert merged["state"] == "shed"
        assert merged["drain_rows_per_s"] == 250.0
        assert merged["queue_rows"] == 50
        # 50 rows / 250 rows/s = 200 ms
        assert merged["retry_after_ms"] == 200

    def test_hint_clamped(self):
        merged = merge_admission({
            "r0": {"enabled": True, "state": "shed", "pressure": 9.0,
                   "drain_rows_per_s": 0.001,
                   "queue_depth": {"t": 100000}}})
        assert merged["retry_after_ms"] == 5000

    def test_disabled_replicas_fold_to_disabled(self):
        merged = merge_admission({"r0": {"enabled": False},
                                  "r1": None})
        assert merged["enabled"] is False
        assert merged["state"] == "ok"

    def test_per_replica_states_echoed(self):
        merged = merge_admission({
            "r0": {"enabled": True, "state": "shed", "pressure": 2.0,
                   "drain_rows_per_s": 10.0, "queue_depth": {}},
            "r1": {"enabled": True, "state": "ok", "pressure": 0.0,
                   "drain_rows_per_s": 10.0, "queue_depth": {}}})
        assert merged["replicas"]["r0"]["state"] == "shed"
        assert merged["replicas"]["r1"]["state"] == "ok"


# ---------------------------------------------------------------------------
# placement: cost-model driven, not round-robin
# ---------------------------------------------------------------------------

class TestPlacement:
    def test_same_model_lanes_colocate_new_models_spread(self):
        async def drive():
            router = _router()
            reps = await _fleet(router, FakeReplica("r0"),
                                FakeReplica("r1"))
            try:
                # two tenants of model A: the second lane lands where
                # A's plan already lives (the wall-cost increment is
                # tiny next to the avoided compile penalty)
                a1 = router.place("A", "t1")
                a2 = router.place("A", "t2")
                assert a1 == a2
                # a NEW model spreads away: its compile penalty on
                # the loaded replica carries the plan-cache pressure
                # surcharge, the empty replica's does not
                b1 = router.place("B", "t1")
                assert b1 != a1
                # round-robin would have alternated a1 -> a2
            finally:
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())

    def test_lane_sticky_until_replica_dies(self):
        async def drive():
            router = _router()
            reps = await _fleet(router, FakeReplica("r0"),
                                FakeReplica("r1"))
            try:
                first = router.place("A", "t1")
                for _ in range(5):
                    assert router.place("A", "t1") == first
                router.unregister_replica(first, "test kill")
                moved = router.place("A", "t1")
                assert moved != first
            finally:
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())

    def test_no_usable_replica_raises(self):
        router = _router()
        with pytest.raises(BackendUnavailable):
            router.place("A", "t1")


# ---------------------------------------------------------------------------
# forwarding: failover, draining re-place, dedupe
# ---------------------------------------------------------------------------

class TestForwarding:
    def test_answers_route_to_placed_replica(self):
        async def drive():
            router = _router()
            reps = await _fleet(router, FakeReplica("r0"),
                                FakeReplica("r1"))
            try:
                out = await router.score({"record": {"x": 1},
                                          "model": "m",
                                          "tenant": "t"})
                assert out["ok"], out
                assert out["replica"] in ("r0", "r1")
                # the SAME lane keeps hitting the same replica
                again = await router.score({"record": {"x": 2},
                                            "model": "m",
                                            "tenant": "t"})
                assert again["replica"] == out["replica"]
            finally:
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())

    def test_dead_replica_fails_over_zero_failures(self):
        async def drive():
            router = _router()
            dead = FakeReplica("r0", mode="drop")
            live = FakeReplica("r1")
            reps = await _fleet(router, dead, live)
            try:
                for i in range(4):
                    out = await router.score({"record": {"x": i},
                                              "model": "m",
                                              "tenant": f"t{i}"})
                    assert out["ok"], out
                    assert out["replica"] == "r1"
                # the drop replica was marked down after its failure
                assert router.replicas["r0"].state == "dead"
                assert router.stats["failovers"] >= 1
                # its lanes moved — nothing still points at r0
                assert all(r != "r0"
                           for r in router._lanes.values())
            finally:
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())

    def test_draining_answer_replaces_lane_and_resends(self):
        async def drive():
            router = _router()
            draining = FakeReplica("r0", mode="draining")
            live = FakeReplica("r1")
            reps = await _fleet(router, draining, live)
            try:
                router._lanes[("m", "t")] = "r0"   # pin, then drain
                out = await router.score({"record": {"x": 1},
                                          "model": "m",
                                          "tenant": "t"})
                # caller sees ONE good answer — the draining verdict
                # was consumed as a re-place signal
                assert out["ok"], out
                assert out["replica"] == "r1"
                assert router.replicas["r0"].state == "draining"
                assert router._lanes[("m", "t")] == "r1"
                assert draining.requests   # it did reach r0 first
            finally:
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())

    def test_stale_reply_deduped(self):
        async def drive():
            router = _router()
            reps = await _fleet(router,
                                FakeReplica("r0", mode="stale"))
            try:
                out = await router.score({"record": {"x": 1},
                                          "model": "m",
                                          "tenant": "t"})
                assert out["ok"], out
                assert out["result"] == {"replica": "r0"}
                assert telemetry.counters().get(
                    "fleet_backend_duplicate_replies", 0) >= 1
            finally:
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())

    def test_transport_blip_resends_on_same_link(self):
        """A replica that drops ONE connection mid-request and then
        answers must be healed by the in-link reconnect+resend: the
        resend carries the same request id, and its genuine reply
        must NOT be discarded as a stale duplicate (the regression:
        marking the rid stale per-attempt made every post-blip retry
        burn the full forward timeout)."""
        async def drive():
            router = _router()
            flaky = FakeReplica("r0", mode="flaky", drops_left=1)
            reps = await _fleet(router, flaky)
            try:
                out = await asyncio.wait_for(
                    router.score({"record": {"x": 1}, "model": "m",
                                  "tenant": "t"}), timeout=5)
                assert out["ok"], out
                assert out["replica"] == "r0"
                # the reconnect's reply was surfaced, not deduped
                assert telemetry.counters().get(
                    "fleet_backend_duplicate_replies", 0) == 0
                assert telemetry.counters().get(
                    "fleet_backend_reconnects", 0) == 1
                # the lone replica survived its blip
                assert router.replicas["r0"].state == "ok"
                assert len(flaky.requests) == 2   # original + resend
            finally:
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())

    def test_abandoned_rid_joins_stale_ring_and_is_skipped(self):
        """Only a rid ABANDONED on a link (every attempt failed) joins
        the stale ring — and a late reply carrying it is then skipped
        by a later expect-less round trip (the probe path)."""
        async def drive():
            state = {"conns": 0}

            async def handle(reader, writer):
                state["conns"] += 1
                line = await reader.readline()
                if not line:
                    writer.close()
                    return
                if state["conns"] <= 3:
                    # swallow the request: the link retries, then
                    # abandons the rid after its final attempt
                    writer.close()
                    return
                # replay the abandoned request's late reply, then
                # answer the probe for real
                late = {"ok": True, "request_id": "abandoned-1",
                        "result": "from the past"}
                real = {"ok": True, "metrics": {"admission": None}}
                writer.write((json.dumps(late) + "\n").encode())
                writer.write((json.dumps(real) + "\n").encode())
                await writer.drain()

            server = await asyncio.start_server(handle, "127.0.0.1",
                                                0)
            port = server.sockets[0].getsockname()[1]
            link = _BackendLink(ReplicaHandle("r0", "127.0.0.1",
                                              port),
                                _fast_retry(), timeout=2.0)
            try:
                with pytest.raises(BackendUnavailable):
                    await link.request({"record": {},
                                        "id": "abandoned-1"})
                assert "abandoned-1" in link._stale_rids
                out = await link.probe()
                assert "metrics" in out   # the late reply was skipped
                assert telemetry.counters().get(
                    "fleet_backend_duplicate_replies", 0) >= 1
            finally:
                await link.close()
                server.close()
        asyncio.run(drive())

    def test_all_replicas_dead_is_answered_error(self):
        async def drive():
            router = _router()
            reps = await _fleet(router,
                                FakeReplica("r0", mode="drop"),
                                FakeReplica("r1", mode="drop"))
            try:
                out = await router.score({"record": {"x": 1},
                                          "model": "m",
                                          "tenant": "t"})
                assert out["ok"] is False
                assert out["kind"] == "transient"
                assert out.get("unavailable")
            finally:
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())


# ---------------------------------------------------------------------------
# fleet-coherent admission
# ---------------------------------------------------------------------------

class TestFleetAdmission:
    def test_one_shedding_replica_sheds_the_whole_fleet(self):
        async def drive():
            router = _router()
            hot = FakeReplica("r0")
            hot.admission = {"enabled": True, "state": "shed",
                             "pressure": 1.9,
                             "drain_rows_per_s": 50.0,
                             "queue_depth": {"t": 25}}
            cold = FakeReplica("r1")
            reps = await _fleet(router, hot, cold)
            try:
                merged = await router.poll_admission_once()
                assert merged["state"] == "shed"
                # a lane that WOULD have routed to the cold replica
                # is shed at the router door anyway — that is the
                # coherence contract: no replica serves full rate
                # while its neighbor drowns
                out = await router.score({"record": {"x": 1},
                                          "model": "m",
                                          "tenant": "cold-lane"})
                assert out["ok"] is False and out["shed"], out
                assert out["fleet"] is True
                # hint derives from the MERGED drain rate:
                # 25 rows / 150 rows/s ≈ 166 ms
                assert out["retry_after_ms"] == merged[
                    "retry_after_ms"] == 166
                assert cold.requests == []   # never forwarded
            finally:
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())

    def test_dead_replica_recovers_via_poll_probe(self):
        """A transient blip must not shrink the fleet permanently:
        the admission poll keeps re-probing a dead-but-registered
        replica and restores it to ok on a successful round trip
        (the manager only re-announces a replica after a respawn, so
        without this the router would never use it again)."""
        async def drive():
            router = _router()
            blip = FakeReplica("r0", mode="drop")
            reps = await _fleet(router, blip)
            try:
                out = await router.score({"record": {"x": 1},
                                          "model": "m",
                                          "tenant": "t"})
                assert out["ok"] is False and out.get("unavailable")
                assert router.replicas["r0"].state == "dead"
                # while the replica stays unreachable the probe fails
                # and it stays dead
                await blip.stop()
                await router.poll_admission_once()
                assert router.replicas["r0"].state == "dead"
                # the replica comes back healthy on the SAME port:
                # one poll restores it without any re-registration
                blip.mode = "ok"
                blip.server = await asyncio.start_server(
                    blip._handle, "127.0.0.1", blip.port)
                await router.poll_admission_once()
                assert router.replicas["r0"].state == "ok"
                assert router.stats["recoveries"] == 1
                assert telemetry.counters().get(
                    "fleet_replica_recoveries", 0) == 1
                out = await router.score({"record": {"x": 2},
                                          "model": "m",
                                          "tenant": "t"})
                assert out["ok"], out
                assert out["replica"] == "r0"
            finally:
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())

    def test_ok_fleet_forwards_normally(self):
        async def drive():
            router = _router()
            reps = await _fleet(router, FakeReplica("r0"),
                                FakeReplica("r1"))
            try:
                merged = await router.poll_admission_once()
                assert merged["state"] == "ok"
                out = await router.score({"record": {"x": 1},
                                          "model": "m",
                                          "tenant": "t"})
                assert out["ok"], out
            finally:
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())

    def test_metrics_snapshot_carries_merged_admission(self):
        async def drive():
            router = _router()
            reps = await _fleet(router, FakeReplica("r0"))
            try:
                await router.poll_admission_once()
                snap = router.metrics_snapshot()
                assert snap["schema"] == "tx-fleet-metrics/1"
                assert snap["admission"]["enabled"] is True
                assert "r0" in snap["replicas"]
                assert snap["replicas"]["r0"]["state"] == "ok"
            finally:
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())


# ---------------------------------------------------------------------------
# deterministic fault drills (TX_FAULT_PLAN fleet scope)
# ---------------------------------------------------------------------------

class TestFaultDrills:
    def test_partition_fault_fails_over(self):
        async def drive():
            router = _router()
            reps = await _fleet(router, FakeReplica("r0"),
                                FakeReplica("r1"))
            try:
                target = router.place("m", "t")
                other = "r1" if target == "r0" else "r0"
                with FaultInjector.plan(
                        f"fleet:{target}:partition:*=preempt"):
                    out = await router.score({"record": {"x": 1},
                                              "model": "m",
                                              "tenant": "t"})
                assert out["ok"], out
                assert out["replica"] == other
                assert router.replicas[target].state == "dead"
            finally:
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())

    def test_hang_fault_times_out_and_fails_over(self):
        async def drive():
            router = _router(forward_timeout=0.3)
            reps = await _fleet(router, FakeReplica("r0"),
                                FakeReplica("r1"))
            try:
                target = router.place("m", "t")
                other = "r1" if target == "r0" else "r0"
                # every forward to the target hangs past the
                # forward_timeout; the lane must fail over
                with FaultInjector.plan(
                        f"fleet:{target}:hang:*=hang:5"):
                    out = await asyncio.wait_for(
                        router.score({"record": {"x": 1},
                                      "model": "m", "tenant": "t"}),
                        timeout=10)
                assert out["ok"], out
                assert out["replica"] == other
            finally:
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())


# ---------------------------------------------------------------------------
# the front end: protocol + writer hygiene (the TX-R07 contract, live)
# ---------------------------------------------------------------------------

class TestFrontEnd:
    def test_handle_speaks_protocol_and_releases_writers(self):
        async def drive():
            router = _router()
            reps = await _fleet(router, FakeReplica("r0"))
            front = await asyncio.start_server(
                router.handle, "127.0.0.1", 0)
            port = front.sockets[0].getsockname()[1]
            try:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", port)
                writer.write(b'{"ready": true}\n')
                ready = json.loads(await reader.readline())
                assert ready["ok"] and ready["ready"]
                assert ready["fleet"] == {"r0": "ok"}
                writer.write(json.dumps(
                    {"record": {"x": 1}, "model": "m",
                     "tenant": "t"}).encode() + b"\n")
                out = json.loads(await reader.readline())
                assert out["ok"], out
                writer.write(b'{"metrics": true}\n')
                met = json.loads(await reader.readline())
                assert met["metrics"]["schema"] == "tx-fleet-metrics/1"
                assert len(router._client_writers) == 1
                writer.close()
                await writer.wait_closed()
                # the disconnect released the writer entry (TX-R07)
                for _ in range(100):
                    if not router._client_writers:
                        break
                    await asyncio.sleep(0.01)
                assert router._client_writers == {}
            finally:
                front.close()
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())

    def test_malformed_line_answers_error(self):
        async def drive():
            router = _router()
            reps = await _fleet(router, FakeReplica("r0"))
            front = await asyncio.start_server(
                router.handle, "127.0.0.1", 0)
            port = front.sockets[0].getsockname()[1]
            try:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", port)
                writer.write(b"this is not json\n")
                out = json.loads(await reader.readline())
                assert out["ok"] is False
                writer.close()
            finally:
                front.close()
                for rep in reps:
                    await rep.stop()
        asyncio.run(drive())
