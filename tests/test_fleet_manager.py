"""ReplicaManager unit tests — the fast, no-subprocess slice
(serving/fleet.py, docs/fleet.md).

``_boot`` is monkeypatched so no serve children ever spawn: what is
under test here is the manager's own arithmetic and threading — heal
runs OFF the watch thread (so concurrent crashes heal in parallel and
the watch loop keeps ticking), the crash-loop breaker trips only
after MORE than ``max_restarts`` crashes in the window, and shutdown
aborts a heal waiting out its backoff. The real spawn/kill/deploy
drills live in test_fleet.py behind the ``slow`` marker.
"""
import threading
import time

import pytest

from transmogrifai_tpu.runtime import telemetry
from transmogrifai_tpu.runtime.retry import RetryPolicy
from transmogrifai_tpu.serving.fleet import ReplicaManager


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


class _FakeProc:
    """Just enough Popen surface for _tick/shutdown."""

    def __init__(self, rc):
        self.returncode = rc

    def poll(self):
        return self.returncode

    def wait(self, timeout=None):
        return self.returncode

    def terminate(self):
        pass

    def kill(self):
        pass


class _FakeReplicaProcess:
    def __init__(self, rc=1, generation=1):
        self.proc = _FakeProc(rc)
        self.generation = generation

    def alive(self):
        return self.proc.poll() is None


def _manager(tmp_path, replicas=2, retry=None, **kw):
    return ReplicaManager(
        models=["m=/nonexistent"], replicas=replicas,
        state_root=str(tmp_path / "state"),
        retry=retry or RetryPolicy(max_attempts=3, base_delay=0.01,
                                   max_delay=0.02),
        **kw)


class TestHealThreading:
    def test_heals_run_off_the_tick_thread_and_in_parallel(
            self, tmp_path, monkeypatch):
        """Two crashed replicas: both ticks return immediately (the
        watch loop keeps ticking while _boot blocks on readiness),
        and both heals reach _boot CONCURRENTLY — serial healing was
        the review finding this guards against."""
        mgr = _manager(tmp_path, replicas=2)
        gate = threading.Event()
        booted = []

        def fake_boot(name, resume):
            booted.append((name, resume))
            gate.wait(5.0)
            with mgr._lock:
                mgr.states[name] = "ok"

        monkeypatch.setattr(mgr, "_boot", fake_boot)
        for name in ("r0", "r1"):
            mgr.states[name] = "ok"
            mgr.procs[name] = _FakeReplicaProcess(rc=1)
        t0 = time.monotonic()
        mgr._tick("r0")
        mgr._tick("r1")
        # neither tick waited for a boot (the gate is still closed)
        assert time.monotonic() - t0 < 1.0
        assert mgr.states["r0"] == "healing"
        assert mgr.states["r1"] == "healing"
        deadline = time.monotonic() + 5.0
        while len(booted) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        # both heals are inside the (blocked) boot at the same time
        assert len(booted) == 2
        gate.set()
        for t in mgr._heals.values():
            t.join(5.0)
        assert mgr.states == {"r0": "ok", "r1": "ok"}
        assert all(resume for _, resume in booted)   # warm takeover

    def test_healing_state_blocks_a_second_heal(self, tmp_path,
                                                monkeypatch):
        """The watch loop keeps ticking a crashed replica while its
        heal is in flight — exactly one heal must run."""
        mgr = _manager(tmp_path, replicas=1)
        gate = threading.Event()
        boots = []

        def fake_boot(name, resume):
            boots.append(name)
            gate.wait(5.0)
            with mgr._lock:
                mgr.states[name] = "ok"

        monkeypatch.setattr(mgr, "_boot", fake_boot)
        mgr.states["r0"] = "ok"
        mgr.procs["r0"] = _FakeReplicaProcess(rc=1)
        mgr._tick("r0")
        for _ in range(10):
            mgr._tick("r0")   # all no-ops: state is "healing"
        deadline = time.monotonic() + 5.0
        while not boots and time.monotonic() < deadline:
            time.sleep(0.01)
        gate.set()
        mgr._heals["r0"].join(5.0)
        assert boots == ["r0"]
        assert len(mgr._crashes["r0"]) == 1

    def test_shutdown_aborts_heal_backoff(self, tmp_path,
                                          monkeypatch):
        """A heal sitting in its backoff sleep must notice shutdown
        and abandon the respawn instead of spawning into a stopping
        manager."""
        mgr = _manager(tmp_path, replicas=1,
                       retry=RetryPolicy(max_attempts=3,
                                         base_delay=5.0,
                                         max_delay=5.0, jitter=0.0))
        boots = []
        monkeypatch.setattr(mgr, "_boot",
                            lambda name, resume: boots.append(name))
        mgr.states["r0"] = "ok"
        mgr.procs["r0"] = _FakeReplicaProcess(rc=1)
        t0 = time.monotonic()
        mgr._tick("r0")
        time.sleep(0.05)   # let the heal thread enter its backoff
        mgr.shutdown(timeout=1.0)
        # shutdown did NOT ride out the 5s backoff
        assert time.monotonic() - t0 < 4.0
        assert boots == []


class TestCrashLoopBreaker:
    def test_breaker_trips_after_more_than_max_restarts(
            self, tmp_path, monkeypatch):
        """Crashes 1..max_restarts each earn a respawn; crash
        max_restarts+1 inside the window trips the breaker — 'more
        than max_restarts crashes', as documented."""
        mgr = _manager(tmp_path, replicas=1, max_restarts=2,
                       restart_window=60.0)
        boots = []
        monkeypatch.setattr(mgr, "_boot",
                            lambda name, resume: boots.append(name))
        mgr._heal("r0", rc=1)
        mgr._heal("r0", rc=1)
        assert boots == ["r0", "r0"]
        assert mgr.states["r0"] != "failed"
        mgr._heal("r0", rc=1)   # the (max_restarts+1)th crash
        assert mgr.states["r0"] == "failed"
        assert boots == ["r0", "r0"]   # no further respawn
        assert telemetry.counters().get(
            "fleet_crash_loop_breakers", 0) == 1

    def test_crashes_outside_the_window_age_out(self, tmp_path,
                                                monkeypatch):
        """Only crashes inside restart_window count toward the
        breaker."""
        mgr = _manager(tmp_path, replicas=1, max_restarts=1,
                       restart_window=0.05)
        boots = []
        monkeypatch.setattr(mgr, "_boot",
                            lambda name, resume: boots.append(name))
        mgr._heal("r0", rc=1)
        time.sleep(0.1)         # the first crash leaves the window
        mgr._heal("r0", rc=1)
        assert mgr.states["r0"] != "failed"
        assert boots == ["r0", "r0"]


class TestBootFailuresAreVisible:
    """One process holds an accelerator, so of N replicas started with
    one environment only one can get it (docs/fleet.md): the others
    must fail the fleet's start at once — not be waited on, skipped or
    healed in a loop."""

    def test_child_that_exits_before_its_banner_fails_at_once(self):
        import subprocess
        import sys

        from transmogrifai_tpu.serving.fleet import (ReplicaProcess,
                                                     ReplicaSpec)
        proc = subprocess.Popen(
            [sys.executable, "-c",
             "import sys; print('Unable to initialize backend'); "
             "sys.exit(3)"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        rp = ReplicaProcess(ReplicaSpec("r1", ("m=/x",), "/tmp"), proc, 1)
        t0 = time.monotonic()
        with pytest.raises(RuntimeError) as err:
            rp.wait_port(timeout=120.0)
        assert time.monotonic() - t0 < 30.0      # not the 120 s timeout
        assert "exited 3" in str(err.value)
        assert "Unable to initialize backend" in str(err.value)

    def test_partial_boot_does_not_start_the_fleet(self, tmp_path,
                                                   monkeypatch, capsys):
        mgr = _manager(tmp_path, replicas=2)

        def boot(name, resume):
            if name == "r1":
                raise RuntimeError("replica r1 exited 1 before its "
                                   "serving banner")
            mgr.states[name] = "ok"
        monkeypatch.setattr(mgr, "_boot", boot)
        with pytest.raises(RuntimeError, match="1 of 2 replicas failed "
                                               "to boot: r1"):
            mgr.start()
        assert mgr.states == {"r0": "ok", "r1": "failed"}
        assert mgr._watch is None                # nothing to heal
        assert '"fleet": "boot_failed"' in capsys.readouterr().out
