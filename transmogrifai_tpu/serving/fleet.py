"""Replica manager: N supervised serve children behind one router
(docs/fleet.md).

This generalizes the single-child ``tx serve --supervise`` supervisor
(cli/serve.py, docs/serving_restart.md) to a SET of child serving
processes. Each replica gets its own ``--state-dir`` (so warm-state
snapshots are per-incarnation), its own ephemeral port (``--port 0``,
bound port read back from the child's JSON banner), and — when the
model dir carries AOT artifacts (docs/aot_artifacts.md) — a
compile-free boot, which is what makes rolling deploys cheap.

The robustness contract, per replica:

- **Crash → warm takeover.** A child that dies with a non-zero exit
  is respawned with ``--resume-state <its state dir>`` and a bumped
  ``TX_SERVE_GENERATION``: the new incarnation replays the dead one's
  last warm-state snapshot (bucket prewarm, tenant guards — see
  docs/serving_restart.md), so takeover is WARM, not a cold start.
  While the replacement boots, the router has already re-placed the
  dead replica's lanes onto survivors — clients never see the gap.
  Each heal runs on its own thread: the watch loop keeps ticking the
  other replicas, so near-simultaneous crashes heal in parallel.
- **Crash-loop breaker.** Per-replica sliding-window crash counting,
  exactly like the PR-12 supervisor: more than ``max_restarts``
  crashes inside ``restart_window`` seconds marks the replica
  ``failed`` and stops respawning it (restarting is making it worse);
  the rest of the fleet keeps serving.
- **Rolling deploy.** :meth:`ReplicaManager.rolling_deploy` drains
  ONE replica at a time: tell the router to stop placing lanes there,
  SIGTERM the child (graceful drain + final snapshot), respawn with
  ``--resume-state``, wait for ``{"ready": true}``, then move on.
  At every instant N-1 replicas serve.

Deterministic drills: the watch loop probes
``maybe_inject("fleet", <replica>, "kill")`` each tick — a ``kill``
fault in ``TX_FAULT_PLAN`` (e.g. ``fleet:r1:kill:1=kill``) SIGKILLs
that child, turning the warm-takeover path into a reproducible test
(runtime/faults.py).

Everything here is plain threads + subprocesses — no coroutines. The
router runs the event loop; the manager talks to it only through its
``*_threadsafe`` entry points.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

from ..runtime import telemetry as _telemetry
from ..runtime.faults import KillPoint, maybe_inject
from ..runtime.retry import RetryPolicy

__all__ = ["ReplicaManager", "ReplicaSpec", "ReplicaProcess",
           "wait_port_ready"]


def wait_port_ready(host: str, port: int, timeout: float = 120.0,
                    require_ready: bool = True) -> dict:
    """Poll a serving port with ``{"ready": true}`` probes until the
    server answers ready (readiness barrier for replica boots and the
    test harness). Returns the final readiness answer."""
    deadline = time.monotonic() + timeout
    last_err: Optional[Exception] = None
    while time.monotonic() < deadline:
        try:
            with socket.create_connection((host, port),
                                          timeout=2.0) as sock:
                sock.sendall(b'{"ready": true}\n')
                buf = b""
                while not buf.endswith(b"\n"):
                    chunk = sock.recv(4096)
                    if not chunk:
                        raise ConnectionError("closed during probe")
                    buf += chunk
            doc = json.loads(buf)
            if not require_ready or doc.get("ready"):
                return doc
        except (OSError, ConnectionError,
                json.JSONDecodeError) as e:
            last_err = e
        time.sleep(0.05)
    raise TimeoutError(
        f"serving port {host}:{port} not ready within {timeout}s "
        f"(last error: {last_err})")


@dataclass
class ReplicaSpec:
    """Launch recipe for one replica."""
    name: str
    models: Sequence[str]          # "name=/model/dir" pairs
    state_dir: str
    host: str = "127.0.0.1"
    extra_args: Sequence[str] = field(default_factory=tuple)
    env: Dict[str, str] = field(default_factory=dict)


class ReplicaProcess:
    """One live child incarnation: the Popen handle, the bound port
    parsed from the child's banner line, and a stdout-pump thread that
    keeps the pipe drained (a full pipe would wedge the child's
    drain/final-snapshot prints)."""

    def __init__(self, spec: ReplicaSpec, proc: subprocess.Popen,
                 generation: int):
        self.spec = spec
        self.proc = proc
        self.generation = generation
        self.port: Optional[int] = None
        self.port_event = threading.Event()
        self.output: List[str] = []
        self._pump = threading.Thread(target=self._drain_stdout,
                                      daemon=True)
        self._pump.start()

    def _drain_stdout(self) -> None:
        for line in self.proc.stdout:
            self.output.append(line)
            if self.port is None:
                try:
                    doc = json.loads(line)
                except (ValueError, TypeError):
                    doc = None   # non-JSON child chatter, not a banner
                if isinstance(doc, dict) and doc.get("serving"):
                    self.port = int(doc.get("port", 0)) or None
                    if self.port:
                        self.port_event.set()

    def wait_port(self, timeout: float = 120.0) -> int:
        """The bound port from the child's banner. A child that EXITS
        before printing one (it could not load its model, or could not
        get its device) fails here at once, with its last output,
        instead of being waited on for the whole timeout."""
        deadline = time.monotonic() + timeout
        while not self.port_event.wait(0.1):
            rc = self.proc.poll()
            if rc is None and time.monotonic() < deadline:
                continue
            self._pump.join(1.0)        # collect the child's last words
            if self.port_event.is_set():
                break
            tail = "".join(self.output[-20:])
            if rc is None:
                raise TimeoutError(
                    f"replica {self.spec.name} printed no serving "
                    f"banner within {timeout}s\n{tail}")
            raise RuntimeError(
                f"replica {self.spec.name} exited {rc} before its "
                f"serving banner\n{tail}")
        return int(self.port)

    def alive(self) -> bool:
        return self.proc.poll() is None


class ReplicaManager:
    """Spawns, watches, heals and drains the replica set.

    Callbacks wire the manager to the router (all invoked from the
    manager's threads; the router marshals them onto its loop):

    - ``on_up(name, host, port, generation)`` — replica answered
      ready (first boot or a takeover respawn).
    - ``on_down(name, reason)`` — replica died; the router re-places
      its lanes NOW, before the replacement exists.
    - ``on_draining(name)`` — a drain is about to start; stop placing
      lanes there.
    """

    def __init__(self, models: Sequence[str], replicas: int,
                 state_root: str, host: str = "127.0.0.1",
                 serve_args: Sequence[str] = (),
                 env: Optional[Dict[str, str]] = None,
                 retry: Optional[RetryPolicy] = None,
                 max_restarts: int = 5,
                 restart_window: float = 60.0,
                 ready_timeout: float = 180.0,
                 on_up: Optional[Callable] = None,
                 on_down: Optional[Callable] = None,
                 on_draining: Optional[Callable] = None):
        self.host = host
        self.retry = retry or RetryPolicy.from_env()
        self.max_restarts = max(int(max_restarts), 1)
        self.restart_window = max(float(restart_window), 0.001)
        self.ready_timeout = float(ready_timeout)
        self.on_up = on_up
        self.on_down = on_down
        self.on_draining = on_draining
        self.specs: Dict[str, ReplicaSpec] = {}
        for i in range(int(replicas)):
            name = f"r{i}"
            state_dir = os.path.join(state_root, name)
            os.makedirs(state_dir, exist_ok=True)
            self.specs[name] = ReplicaSpec(
                name=name, models=tuple(models),
                state_dir=state_dir, host=host,
                extra_args=tuple(serve_args),
                env=dict(env or {}))
        self.procs: Dict[str, ReplicaProcess] = {}
        #: "starting" | "ok" | "healing" | "draining" | "failed"
        #: | "stopped"
        self.states: Dict[str, str] = {n: "starting"
                                       for n in self.specs}
        self._crashes: Dict[str, deque] = {n: deque()
                                           for n in self.specs}
        self._generations: Dict[str, int] = {n: 0 for n in self.specs}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._watch: Optional[threading.Thread] = None
        self._heals: Dict[str, threading.Thread] = {}
        self.kill_drills = 0

    # -- spawning ----------------------------------------------------------
    def _spawn(self, name: str, resume: bool) -> ReplicaProcess:
        spec = self.specs[name]
        self._generations[name] += 1
        generation = self._generations[name]
        cmd = [sys.executable, "-m", "transmogrifai_tpu.cli", "serve",
               "--host", spec.host, "--port", "0",
               "--state-dir", spec.state_dir]
        for m in spec.models:
            cmd += ["--model", m]
        if resume:
            cmd += ["--resume-state", spec.state_dir]
        cmd += list(spec.extra_args)
        env = dict(os.environ, **spec.env,
                   TX_SERVE_GENERATION=str(generation))
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True,
                                env=env)
        rp = ReplicaProcess(spec, proc, generation)
        self.procs[name] = rp
        _telemetry.event("fleet_replica_spawned", replica=name,
                         generation=generation, pid=proc.pid,
                         resume=resume)
        print(json.dumps({"fleet": "spawned", "replica": name,
                          "generation": generation,
                          "pid": proc.pid, "resume": resume}),
              flush=True)
        return rp

    def _boot(self, name: str, resume: bool) -> None:
        rp = self._spawn(name, resume=resume)
        port = rp.wait_port(self.ready_timeout)
        wait_port_ready(rp.spec.host, port, self.ready_timeout)
        with self._lock:
            self.states[name] = "ok"
        print(json.dumps({"fleet": "ready", "replica": name,
                          "port": port,
                          "generation": rp.generation}), flush=True)
        if self.on_up is not None:
            self.on_up(name, rp.spec.host, port, rp.generation)

    def _first_boot(self, name: str, errors: Dict[str, str]) -> None:
        try:
            self._boot(name, resume=False)
        except (OSError, TimeoutError, RuntimeError) as e:
            errors[name] = str(e)
            with self._lock:
                self.states[name] = "failed"
            _telemetry.event("fleet_boot_failed", replica=name,
                             error=str(e)[:200])
            print(json.dumps({"fleet": "boot_failed", "replica": name,
                              "error": str(e)[:2000]}), flush=True)

    def start(self) -> None:
        """Boot every replica in parallel, barrier on readiness, then
        start the watch thread. A fleet that cannot boot EVERY replica
        it was asked for does not start: the caller's ``shutdown()``
        stops the ones that did. (Every replica inherits this process's
        environment, so on an accelerator they all reach for the same
        device and only one gets it — docs/fleet.md.)"""
        errors: Dict[str, str] = {}
        threads = [threading.Thread(target=self._first_boot,
                                    args=(name, errors))
                   for name in self.specs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise RuntimeError(
                f"{len(errors)} of {len(self.specs)} replicas failed "
                f"to boot: " + "; ".join(
                    f"{n}: {e.splitlines()[0]}"
                    for n, e in sorted(errors.items())))
        self._watch = threading.Thread(target=self._watch_loop,
                                       daemon=True)
        self._watch.start()

    # -- the watch loop ----------------------------------------------------
    def _watch_loop(self) -> None:
        while not self._stop.wait(0.1):
            for name in list(self.specs):
                self._tick(name)

    def _tick(self, name: str) -> None:
        with self._lock:
            state = self.states.get(name)
        if state not in ("ok", "draining"):
            return
        rp = self.procs.get(name)
        if rp is None:
            return
        if rp.alive():
            try:
                # fleet:<name>:kill — the deterministic kill drill:
                # SIGKILL this child as a real OOM-killer would
                maybe_inject("fleet", name, "kill")
            except KillPoint:
                self.kill_drills += 1
                _telemetry.count("fleet_kill_drills")
                print(json.dumps({"fleet": "kill_drill",
                                  "replica": name,
                                  "generation": rp.generation}),
                      flush=True)
                rp.proc.kill()
            return
        rc = rp.proc.returncode
        if state == "draining" or rc == 0:
            # graceful exits end the incarnation without healing;
            # rolling_deploy owns the respawn
            return
        # heal on a dedicated thread: _heal blocks on the backoff
        # sleep and then on the replacement's readiness gate (up to
        # ready_timeout), and the watch loop must keep ticking the
        # OTHER replicas meanwhile — near-simultaneous crashes heal
        # in parallel and kill drills keep firing. The "healing"
        # state keeps this tick from starting a second heal.
        with self._lock:
            self.states[name] = "healing"
        t = threading.Thread(target=self._heal, args=(name, rc),
                             daemon=True)
        self._heals[name] = t
        t.start()

    def _heal(self, name: str, rc: int) -> None:
        """Crash detected: count it against the sliding window, then
        either trip the per-replica crash-loop breaker or respawn
        with ``--resume-state`` (the warm takeover). Runs on its own
        thread, one per healing replica."""
        now = time.monotonic()
        crashes = self._crashes[name]
        crashes.append(now)
        while crashes and now - crashes[0] > self.restart_window:
            crashes.popleft()
        _telemetry.count("fleet_replica_crashes")
        print(json.dumps({"fleet": "crashed", "replica": name,
                          "code": rc,
                          "crashes_in_window": len(crashes)}),
              flush=True)
        if self.on_down is not None:
            self.on_down(name, f"exit {rc}")
        if len(crashes) > self.max_restarts:
            with self._lock:
                self.states[name] = "failed"
            _telemetry.count("fleet_crash_loop_breakers")
            print(json.dumps({"fleet": "crash_loop_breaker",
                              "replica": name,
                              "crashes": len(crashes),
                              "window_seconds": self.restart_window}),
                  flush=True)
            return
        if self._stop.wait(self.retry.delay_for(
                len(crashes), f"fleet-restart:{name}")):
            return   # manager is shutting down — no respawn
        try:
            self._boot(name, resume=True)
        except (OSError, TimeoutError, RuntimeError) as e:
            # respawn failed outright — harsher than another crash:
            # a replacement that cannot even reach ready has nothing
            # a restart window could ride out, so the replica is
            # marked failed immediately instead of looping forever
            _telemetry.event("fleet_respawn_failed", replica=name,
                             error=str(e)[:200])
            with self._lock:
                self.states[name] = "failed"
            print(json.dumps({"fleet": "respawn_failed",
                              "replica": name,
                              "error": str(e)[:200]}), flush=True)

    # -- drain / rolling deploy -------------------------------------------
    def drain_replica(self, name: str,
                      timeout: float = 60.0) -> int:
        """Gracefully stop one replica: router stops placing lanes
        there, then SIGTERM → drain → final snapshot → exit 0."""
        rp = self.procs.get(name)
        with self._lock:
            self.states[name] = "draining"
        if self.on_draining is not None:
            self.on_draining(name)
        if rp is None or not rp.alive():
            return 0
        rp.proc.terminate()
        try:
            rc = rp.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            rp.proc.kill()
            rc = rp.proc.wait(10)
        print(json.dumps({"fleet": "drained", "replica": name,
                          "code": rc}), flush=True)
        return rc

    def rolling_deploy(self) -> None:
        """Drain + respawn each replica sequentially — the zero-
        downtime deploy: at every instant all OTHER replicas serve,
        and each respawn resumes from its own final snapshot."""
        for name in sorted(self.specs):
            with self._lock:
                if self.states.get(name) not in ("ok", "draining"):
                    continue
            _telemetry.count("fleet_rolling_deploys")
            self.drain_replica(name)
            self._boot(name, resume=True)

    # -- teardown ----------------------------------------------------------
    def shutdown(self, timeout: float = 30.0) -> None:
        self._stop.set()
        if self._watch is not None:
            self._watch.join(5.0)
        for t in list(self._heals.values()):
            t.join(2.0)
        for name, rp in list(self.procs.items()):
            with self._lock:
                self.states[name] = "stopped"
            if rp.alive():
                rp.proc.terminate()
        deadline = time.monotonic() + timeout
        for rp in list(self.procs.values()):
            remaining = max(deadline - time.monotonic(), 0.1)
            try:
                rp.proc.wait(remaining)
            except subprocess.TimeoutExpired:
                rp.proc.kill()
                rp.proc.wait(10)
        # a heal thread that out-waited the joins above may have
        # slipped a fresh spawn past the terminate sweep — reap it
        for rp in list(self.procs.values()):
            if rp.alive():
                rp.proc.kill()

    def snapshot(self) -> dict:
        """Manager-side view for the fleet metrics document."""
        with self._lock:
            states = dict(self.states)
        return {
            "replicas": {
                name: {"state": states.get(name),
                       "generation": self._generations[name],
                       "port": (self.procs[name].port
                                if name in self.procs else None),
                       "alive": (self.procs[name].alive()
                                 if name in self.procs else False)}
                for name in sorted(self.specs)},
            "kill_drills": self.kill_drills,
        }
