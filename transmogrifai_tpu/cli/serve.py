"""``python -m transmogrifai_tpu.cli serve`` — the async micro-batching
scoring server (docs/serving_loop.md) over a JSON-lines TCP front end.

Protocol: one JSON object per line on the socket; the server answers
one JSON line per request, in order::

    -> {"record": {"age": 31.0, ...}, "model": "titanic", "tenant": "a"}
    <- {"ok": true, "request_id": "req-1a2b-3", "result": {...}}
    <- {"ok": false, "request_id": "...", "error": "...",
        "kind": "transient"}
    <- {"ok": false, "request_id": "...", "shed": true,
        "retry_after_ms": 12, "error": "...", "kind": "transient"}

The ``"shed"`` answer is the overload admission controller refusing a
request at the door (docs/admission.md): bounded lane queues, a
cost-model deadline budget, per-tenant fair-queuing quotas and the
brownout state machine all shed through it, the hint derived from
predicted queue drain time. ``--admission=off`` removes the
controller entirely (the pre-admission enqueue edge, byte-for-byte);
``--tenant-weight``/``--tenant-deadline-ms`` shape it.

Every response echoes a ``request_id`` — generated at admission, or
the client's own ``"id"`` field when supplied — the same id that keys
the request's span tree when tracing is on (``TX_TRACE``,
docs/observability.md). A ``{"metrics": true}`` line is a CONTROL
request: it answers the live metrics snapshot instead of scoring, and
``--metrics-port`` serves the same JSON over HTTP (``GET /``) for
scrapers that should not touch the scoring socket.

``--auto-retrain`` (off by default) arms the self-healing lifecycle:
drift-triggered background retraining with canary validation, atomic
hot-swap between batches, and instant rollback on a post-swap breaker
trip or drift regression (docs/self_healing.md). ``--retrain-budget``,
``--canary-rows`` and ``--swap-policy`` tune it.

Start one process serving a model zoo::

    python -m transmogrifai_tpu.cli serve \\
        --model titanic=/models/titanic --model churn=/models/churn \\
        --port 8765 --max-wait-ms 5 --plan-cache 4

The hot path is the :class:`~transmogrifai_tpu.serving.ServingServer`
coalescing loop: deadline-or-full bucket batching, double-buffered
encode vs dispatch, per-tenant guardrails + breaker + sentinel, LRU
plan cache. ``--max-requests`` exits after N answered requests (CI
smoke); ``--port 0`` binds an ephemeral port (printed on stdout).

Preemption tolerance (docs/serving_restart.md): SIGTERM/SIGINT flips
the loop to DRAINING — new requests get a machine-readable
``{"ok": false, "draining": true}`` answer (the reconnecting client
retries against the next incarnation), queued + in-flight requests
finish under ``--drain-timeout``, traces/metrics/profiles flush, the
warm-state snapshot is written, and the process exits 0.
``--resume-state DIR`` restores that snapshot on boot — recompiling +
prewarming exactly the recorded buckets BEHIND the readiness gate
(``{"ready": true}`` control request + the metrics ``process`` block)
before the port binds. ``--supervise`` runs a parent that restarts a
crashed loop under ``RetryPolicy`` backoff with a crash-loop breaker,
handing the snapshot dir to each incarnation."""
from __future__ import annotations

import asyncio
import json
import os
import sys
from typing import List, Optional

__all__ = ["add_serve_parser", "run_serve", "run_supervised",
           "serve_forever"]

#: seconds a connection that is mid-answer at shutdown gets to finish
#: writing before its transport is aborted
_CLOSE_GRACE_S = 5.0


def add_serve_parser(sub) -> None:
    sv = sub.add_parser(
        "serve",
        help="async micro-batching scoring server (JSON lines over "
             "TCP; docs/serving_loop.md)")
    sv.add_argument("--model", action="append", required=True,
                    metavar="[NAME=]DIR",
                    help="saved model directory, optionally named "
                         "(repeatable; the first is the default model)")
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument("--port", type=int, default=8765,
                    help="TCP port (0 = ephemeral, printed on stdout)")
    sv.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="deadline half of deadline-or-full coalescing")
    sv.add_argument("--target-batch", type=int, default=None,
                    help="coalescer target batch (default: derived "
                         "from the plan's recorded bucket profile)")
    sv.add_argument("--max-batch", type=int, default=256,
                    help="hard cap on rows per dispatch")
    sv.add_argument("--plan-cache", type=int, default=4,
                    help="LRU budget of resident compiled plans")
    sv.add_argument("--deadline-seconds", type=float, default=None,
                    help="per-batch device dispatch deadline (a hung "
                         "dispatch is orphaned, the batch falls back "
                         "to the host path)")
    sv.add_argument("--no-guardrails", action="store_true",
                    help="disable per-tenant admission/output/breaker "
                         "guardrails (docs/serving_guardrails.md)")
    sv.add_argument("--no-sentinel", action="store_true",
                    help="disable the per-tenant drift sentinel")
    sv.add_argument("--admission", choices=["on", "off"], default="on",
                    help="overload admission control "
                         "(docs/admission.md): bounded lane queues "
                         "with retry_after_ms shed answers, cost-model "
                         "deadline admission, per-tenant DRR fair "
                         "queuing, brownout load shedding. "
                         "--admission=off restores the pre-admission "
                         "enqueue edge byte-for-byte")
    sv.add_argument("--tenant-weight", action="append", default=None,
                    metavar="NAME=W",
                    help="fair-queuing weight / quota share for a "
                         "tenant (repeatable; unlisted tenants weigh "
                         "1.0; brownout sheds lower-weight tenants "
                         "first)")
    sv.add_argument("--tenant-deadline-ms", action="append",
                    default=None, metavar="[NAME=]MS",
                    help="per-request completion budget: a request "
                         "whose predicted completion (queue wait + "
                         "encode + dispatch) exceeds it is shed at "
                         "the door (repeatable; a bare MS applies to "
                         "every tenant)")
    sv.add_argument("--max-requests", type=int, default=None,
                    help="exit after answering N requests (smoke/CI)")
    sv.add_argument("--auto-retrain", action="store_true",
                    help="enable the self-healing lifecycle: on a "
                         "tenant's drift sentinel reaching DEGRADE, "
                         "retrain in the background, canary-validate, "
                         "and atomically hot-swap the compiled plan "
                         "(docs/self_healing.md). OFF by default — "
                         "without it serving behavior is unchanged")
    sv.add_argument("--retrain-budget", type=float, default=120.0,
                    help="wall-clock seconds a background retrain may "
                         "take before it is abandoned (with "
                         "--auto-retrain)")
    sv.add_argument("--canary-rows", type=int, default=64,
                    help="retained ring of recent admitted requests "
                         "used to shadow-score candidates (with "
                         "--auto-retrain)")
    sv.add_argument("--swap-policy", choices=["tenant", "model"],
                    default="tenant",
                    help="hot-swap scope: 'tenant' replaces the plan "
                         "only for the drifted tenant (others keep the "
                         "original entry, bitwise unaffected); 'model' "
                         "replaces the shared cache entry")
    sv.add_argument("--metrics-port", type=int, default=None,
                    help="also serve the live metrics JSON over HTTP "
                         "on this port (GET /; 0 = ephemeral, printed "
                         "on stdout; docs/observability.md)")
    sv.add_argument("--drain-timeout", type=float, default=30.0,
                    help="seconds a SIGTERM/SIGINT drain waits for "
                         "queued + in-flight requests before shutdown "
                         "(docs/serving_restart.md)")
    sv.add_argument("--artifacts", choices=["auto", "require", "off"],
                    default=None,
                    help="AOT artifact loading (docs/aot_artifacts.md):"
                         " auto loads each saved model's exported "
                         "executables (zero serve-process compiles) "
                         "with loud fallback to live compile; require "
                         "refuses to boot a model without valid "
                         "artifacts (fleet replicas); off always "
                         "live-compiles (default: TX_AOT_ARTIFACTS "
                         "env, else auto)")
    sv.add_argument("--state-dir", default=None, metavar="DIR",
                    help="write the warm-state snapshot here "
                         "(periodically, at lifecycle commits, and on "
                         "shutdown); defaults to --resume-state's DIR")
    sv.add_argument("--resume-state", default=None, metavar="DIR",
                    help="restore the warm-state snapshot from DIR on "
                         "boot: recompile + prewarm the recorded "
                         "buckets behind the readiness gate, restore "
                         "sentinels/breakers/lifecycle. A torn or "
                         "mismatched snapshot cold-starts loudly")
    sv.add_argument("--snapshot-interval", type=float, default=30.0,
                    help="seconds between periodic snapshot writes "
                         "(with --state-dir/--resume-state; 0 = only "
                         "at lifecycle commits and shutdown)")
    sv.add_argument("--supervise", action="store_true",
                    help="run a supervisor parent that restarts the "
                         "serving child on crash with RetryPolicy "
                         "backoff and a crash-loop breaker")
    sv.add_argument("--max-restarts", type=int, default=5,
                    help="crash-loop breaker: give up after this many "
                         "crashes inside --restart-window seconds")
    sv.add_argument("--restart-window", type=float, default=60.0,
                    help="sliding window (seconds) the crash-loop "
                         "breaker counts crashes over")


def _parse_models(specs: List[str]) -> List[tuple]:
    out = []
    for spec in specs:
        if "=" in spec:
            name, path = spec.split("=", 1)
        else:
            path = spec
            name = os.path.basename(os.path.normpath(path)) or "model"
        out.append((name, path))
    return out


async def serve_forever(server, host: str, port: int,
                        max_requests: Optional[int] = None,
                        ready_cb=None,
                        metrics_port: Optional[int] = None,
                        metrics_ready_cb=None,
                        drain_timeout: float = 30.0,
                        state_manager=None,
                        snapshot_interval: Optional[float] = None,
                        banner_extra: Optional[dict] = None) -> int:
    """Run ``server``'s loop behind a JSON-lines TCP front end until
    cancelled (or ``max_requests`` answers, or a SIGTERM/SIGINT
    drain). Importable so tests drive the exact CLI path in-process
    with in-memory models. ``metrics_port`` additionally serves the
    live ``server.metrics_snapshot()`` JSON over HTTP;
    ``state_manager`` (serving/state.StateManager) arms snapshot
    writes — every ``snapshot_interval`` seconds and at shutdown."""
    from ..runtime.errors import classify_error
    from ..serving.server import ServeDraining, ServeShed
    await server.start()
    answered = {"n": 0}
    done = asyncio.Event()
    stop = asyncio.Event()

    def _draining_answer(rid):
        return {"ok": False, "request_id": rid, "draining": True,
                "error": "ServeDraining: serving loop is draining "
                         "for shutdown; retry against the next "
                         "incarnation",
                "kind": "transient"}

    #: open client connections -> "an answer is being produced". At
    #: shutdown idle ones are closed under their reader; a busy one
    #: finishes its answer first (Server.wait_closed() waits for every
    #: accepted connection, so an attached client must not be able to
    #: keep a stopped server — and the device it owns — alive)
    conns: dict = {}
    closing = [False]

    async def handle(reader, writer):
        try:
            while not closing[0]:
                conns[writer] = False
                line = await reader.readline()
                if not line:
                    break
                conns[writer] = True
                if server.draining:
                    # refuse the connection with the machine-readable
                    # answer (the reconnecting client backs off and
                    # resends to the next incarnation), then close it
                    writer.write((json.dumps(_draining_answer(None))
                                  + "\n").encode())
                    await writer.drain()
                    break
                rid = None
                try:
                    msg = json.loads(line)
                    if isinstance(msg, dict) and msg.get("metrics"):
                        # control request: live metrics, no scoring,
                        # does not consume the --max-requests budget
                        out = {"ok": True,
                               "metrics": server.metrics_snapshot()}
                        writer.write((json.dumps(out, default=float)
                                      + "\n").encode())
                        await writer.drain()
                        continue
                    if isinstance(msg, dict) and msg.get("ready"):
                        # readiness-gate control request
                        # (docs/serving_restart.md)
                        out = {"ok": True, "ready": bool(server.ready),
                               "draining": server.draining,
                               "generation": server.restart_generation}
                        writer.write((json.dumps(out) + "\n").encode())
                        await writer.drain()
                        continue
                    if isinstance(msg, dict) and "id" in msg:
                        rid = str(msg["id"])
                    rid, row = await server.score_with_id(
                        msg.get("record", msg), model=msg.get("model"),
                        tenant=msg.get("tenant", "default"), rid=rid)
                    out = {"ok": True, "request_id": rid, "result": row}
                except asyncio.CancelledError:
                    raise
                except ServeDraining:
                    writer.write((json.dumps(_draining_answer(rid))
                                  + "\n").encode())
                    await writer.drain()
                    break
                except ServeShed as e:
                    # overload shed (docs/admission.md): unlike
                    # draining, the server is healthy and the
                    # connection STAYS OPEN — the client honors the
                    # retry hint and resends on the same socket
                    out = {"ok": False, "request_id": rid,
                           "shed": True,
                           "retry_after_ms": e.retry_after_ms,
                           "error": f"{type(e).__name__}: {e}",
                           "kind": classify_error(e)}
                except Exception as e:
                    # a bad request/record answers with the classified
                    # error instead of dropping the connection
                    out = {"ok": False, "request_id": rid,
                           "error": f"{type(e).__name__}: {e}",
                           "kind": classify_error(e)}
                writer.write((json.dumps(out, default=float) + "\n")
                             .encode())
                await writer.drain()
                answered["n"] += 1
                if max_requests and answered["n"] >= max_requests:
                    done.set()
                    break
        finally:
            conns.pop(writer, None)
            writer.close()

    async def handle_metrics(reader, writer):
        # minimal HTTP/1.1 responder: whatever the request line says,
        # answer the metrics snapshot (a scrape endpoint, not a router)
        try:
            await asyncio.wait_for(reader.readuntil(b"\r\n\r\n"), 5.0)
        except (asyncio.IncompleteReadError, asyncio.TimeoutError,
                asyncio.LimitOverrunError):
            pass
        body = json.dumps(server.metrics_snapshot(),
                          default=float).encode()
        writer.write(b"HTTP/1.1 200 OK\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: " + str(len(body)).encode() +
                     b"\r\nConnection: close\r\n\r\n" + body)
        await writer.drain()
        writer.close()

    tcp = await asyncio.start_server(handle, host, port)
    bound = tcp.sockets[0].getsockname()[1]
    http = None
    banner = {"serving": True, "host": host, "port": bound,
              "models": server.plans.names()}
    if banner_extra:
        banner.update(banner_extra)
    if metrics_port is not None:
        http = await asyncio.start_server(handle_metrics, host,
                                          metrics_port)
        banner["metrics_port"] = http.sockets[0].getsockname()[1]
        if metrics_ready_cb is not None:
            metrics_ready_cb(banner["metrics_port"])
    print(json.dumps(banner), flush=True)
    if ready_cb is not None:
        ready_cb(bound)

    # graceful drain on SIGTERM/SIGINT (docs/serving_restart.md) —
    # only installable on a main-thread loop; in-process test loops
    # (background threads) skip the handlers and use cancellation
    loop = asyncio.get_running_loop()
    sig_installed = []
    try:
        import signal as _signal
        for sig in (_signal.SIGTERM, _signal.SIGINT):
            loop.add_signal_handler(sig, stop.set)
            sig_installed.append(sig)
    except (ValueError, OSError, RuntimeError, NotImplementedError):
        pass

    snap_task = None
    if state_manager is not None and snapshot_interval:
        async def _periodic_snapshots():
            while True:
                await asyncio.sleep(snapshot_interval)
                await loop.run_in_executor(None, state_manager.write)
        snap_task = asyncio.create_task(_periodic_snapshots())

    cancelled = False
    waiters = [asyncio.ensure_future(stop.wait())]
    if max_requests:
        waiters.append(asyncio.ensure_future(done.wait()))
    try:
        await asyncio.wait(waiters,
                           return_when=asyncio.FIRST_COMPLETED)
    except asyncio.CancelledError:
        cancelled = True
    finally:
        for w in waiters:
            w.cancel()
    drain_summary = None
    if not cancelled and stop.is_set():
        # queued + in-flight requests finish (new ones get the
        # draining answer) before anything is torn down
        drain_summary = await server.drain(drain_timeout)
    try:
        if state_manager is not None and not cancelled:
            # final snapshot AFTER the drain: sketches, breakers and
            # counters include every answered request
            await loop.run_in_executor(
                None, lambda: state_manager.write(reason="shutdown"))
    finally:
        for sig in sig_installed:
            try:
                loop.remove_signal_handler(sig)
            except (ValueError, RuntimeError):  # pragma: no cover
                pass
        if snap_task is not None:
            snap_task.cancel()
        tcp.close()
        closing[0] = True
        for writer, busy in list(conns.items()):
            if not busy:
                writer.close()
        try:
            await asyncio.wait_for(tcp.wait_closed(), _CLOSE_GRACE_S)
        except asyncio.TimeoutError:
            # a client that stopped reading its answers: cut it off
            for writer in list(conns):
                writer.transport.abort()
        if http is not None:
            http.close()
        await server.shutdown()
    final = {"served": answered["n"], **server.describe()}
    if drain_summary is not None:
        final["drain"] = drain_summary
    print(json.dumps(final, default=float), flush=True)
    return 0


def run_serve(args) -> int:
    if getattr(args, "supervise", False):
        return run_supervised(args)
    from ..observability import persist_process_profiles, trace
    from ..serving.server import ServeConfig, ServingServer
    from ..utils.jax_setup import backend_block, enable_compilation_cache
    enable_compilation_cache()
    trace.configure_from_env()
    lifecycle = None
    if getattr(args, "auto_retrain", False):
        # the lifecycle is opt-in: without --auto-retrain the config
        # stays None and the loop behaves exactly as before
        from ..serving.lifecycle import LifecycleConfig
        lifecycle = LifecycleConfig(
            retrain_budget_seconds=args.retrain_budget,
            canary_rows=args.canary_rows,
            swap_policy=args.swap_policy)
    admission_control = None
    if getattr(args, "admission", "on") != "off":
        # overload admission (docs/admission.md); --admission=off
        # leaves this None -> the enqueue edge is byte-identical to
        # a build without the controller
        from ..serving.admission import AdmissionConfig
        weights = {}
        for spec in (getattr(args, "tenant_weight", None) or []):
            name, _, w = spec.partition("=")
            weights[name] = float(w or 1.0)
        deadline = None
        d = {}
        for spec in (getattr(args, "tenant_deadline_ms", None) or []):
            name, sep, ms = spec.partition("=")
            if sep:
                d[name] = float(ms)
            else:
                d["default"] = float(name)
        if d:
            deadline = (d["default"] if set(d) == {"default"}
                        else d)
        admission_control = AdmissionConfig(
            tenant_weights=weights, tenant_deadline_ms=deadline)
    config = ServeConfig(
        max_wait_ms=args.max_wait_ms,
        target_batch=args.target_batch,
        max_batch=args.max_batch,
        plan_budget=args.plan_cache,
        deadline_seconds=args.deadline_seconds,
        guardrails=not args.no_guardrails,
        sentinel=not args.no_sentinel,
        lifecycle=lifecycle,
        admission_control=admission_control)
    if getattr(args, "artifacts", None):
        # the flag wins over the env; set BEFORE any plan resolves so
        # PlanCache.get / prewarm / state restore all see one mode
        os.environ["TX_AOT_ARTIFACTS"] = args.artifacts
    server = ServingServer(config)
    for name, path in _parse_models(args.model):
        server.add_model(name, path)
    # warm-restart wiring (docs/serving_restart.md). Both flags off =
    # no StateManager, no snapshot task: behavior identical to before
    resume_dir = getattr(args, "resume_state", None)
    write_dir = getattr(args, "state_dir", None) or resume_dir
    banner_extra = {}
    if resume_dir:
        from ..serving.state import StateManager
        server.ready = False
        summary = StateManager(server, resume_dir).restore()
        server.ready = True
        print(json.dumps({"resume": summary}, default=float),
              flush=True)
        banner_extra["resume"] = summary.get("mode", "cold")
    state_manager = None
    if write_dir:
        from ..serving.state import StateManager
        state_manager = StateManager(server, write_dir)
        banner_extra["generation"] = server.restart_generation
    # autotuned prewarm (docs/autotuning.md): compile the buckets the
    # cost model says this zoo will hit BEFORE the port binds, so the
    # first live batches skip their compile stall. Cold store or
    # TX_TUNE=off -> empty set -> no-op, boot time unchanged.
    from ..artifacts.store import load_mode
    if load_mode() == "require":
        # fleet-replica contract: resolve every registered model NOW —
        # a model without valid artifacts refuses to boot instead of
        # silently compiling in-band
        from ..artifacts.loader import ArtifactsRequired
        try:
            for name in server.plans.names():
                server.plans.get(name, server.plan_buckets,
                                 server.plan_lattice)
        except ArtifactsRequired as e:
            print(f"tx-serve: {e}", file=sys.stderr)
            return 2
    warmed = server.prewarm()
    if warmed:
        banner_extra["prewarmed"] = warmed
    # which resident models serve from deserialized AOT executables
    # (the boot-visible zero-compile signal, docs/aot_artifacts.md)
    aot_models = sorted(
        key[0] for key, entry in server.plans.resident_entries()
        if getattr(entry.plan, "aot_active", lambda: False)())
    if aot_models:
        banner_extra["artifacts"] = aot_models
    if server._target_decision.tuned() or any(
            d.tuned() for d in server._bucket_decisions):
        banner_extra["tuned"] = {
            "target_batch": server._target_decision.chosen,
            "buckets": [d.chosen for d in server._bucket_decisions]}
    if admission_control is not None:
        banner_extra["admission"] = "on"
    # the device this process actually holds, and what its boot took
    # from the compile cache — so whoever started it can tell a server
    # on the chip from one that is not
    banner_extra["backend"] = backend_block()
    try:
        return asyncio.run(serve_forever(
            server, args.host, args.port,
            max_requests=args.max_requests,
            metrics_port=args.metrics_port,
            drain_timeout=getattr(args, "drain_timeout", 30.0),
            state_manager=state_manager,
            snapshot_interval=getattr(args, "snapshot_interval", None),
            banner_extra=banner_extra))
    finally:
        # the finally (not the happy path) flushes: a SIGTERM drain,
        # a crash, and a clean --max-requests exit all persist the
        # session's traces and measured costs
        trace.flush()
        if os.environ.get("TX_PROFILE_PERSIST") == "1":
            # fold this session's measured section/bucket costs into
            # the persisted profile store (docs/observability.md)
            persist_process_profiles()


def run_supervised(args) -> int:
    """``tx serve --supervise``: a parent that keeps one serving child
    alive across crashes. Child exit 0 (graceful drain, --max-requests)
    ends supervision; a crash restarts the child under
    ``RetryPolicy`` backoff, with ``TX_SERVE_GENERATION`` bumped per
    incarnation (the metrics ``process.restart_generation``) and the
    same argv — so ``--resume-state`` hands the snapshot to each new
    child. A crash-loop breaker gives up after ``--max-restarts``
    crashes inside ``--restart-window`` seconds (exit 1)."""
    import collections
    import signal
    import subprocess
    import sys
    import time as _time
    from ..runtime.retry import RetryPolicy
    cmd = [sys.executable, "-m", "transmogrifai_tpu.cli"] + \
        [a for a in sys.argv[1:] if a != "--supervise"]
    policy = RetryPolicy.from_env()
    window = max(float(getattr(args, "restart_window", 60.0)), 0.001)
    max_restarts = max(int(getattr(args, "max_restarts", 5)), 1)
    crashes = collections.deque()
    state = {"child": None, "stopping": False}

    def _forward(signum, _frame):
        state["stopping"] = True
        child = state["child"]
        if child is not None and child.poll() is None:
            child.send_signal(signum)

    prev = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            prev[sig] = signal.signal(sig, _forward)
        except ValueError:  # pragma: no cover - non-main thread
            pass
    generation = 0
    try:
        while True:
            generation += 1
            env = dict(os.environ,
                       TX_SERVE_GENERATION=str(generation))
            child = subprocess.Popen(cmd, env=env)
            state["child"] = child
            print(json.dumps({"supervisor": "spawned",
                              "generation": generation,
                              "pid": child.pid}), flush=True)
            try:
                rc = child.wait()
            except KeyboardInterrupt:  # pragma: no cover
                state["stopping"] = True
                rc = child.wait()
            if state["stopping"] or rc == 0:
                print(json.dumps({"supervisor": "exit", "code": rc,
                                  "generation": generation}),
                      flush=True)
                return 0 if rc == 0 else rc
            now = _time.monotonic()
            crashes.append(now)
            while crashes and now - crashes[0] > window:
                crashes.popleft()
            print(json.dumps({"supervisor": "crashed", "code": rc,
                              "generation": generation,
                              "crashes_in_window": len(crashes)}),
                  flush=True)
            if len(crashes) >= max_restarts:
                # crash-loop breaker: restarting is making it worse
                print(json.dumps({"supervisor": "crash_loop_breaker",
                                  "crashes": len(crashes),
                                  "window_seconds": window}),
                      flush=True)
                return 1
            delay = policy.delay_for(len(crashes),
                                     f"serve-restart:{generation}")
            _time.sleep(delay)
    finally:
        for sig, handler in prev.items():
            signal.signal(sig, handler)
