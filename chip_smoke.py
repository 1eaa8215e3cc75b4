#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the flagship path once through the entry points a user calls:

1. a TRAINER process: ``Workflow.train()`` cold, warm, and once more after
   ``jax.clear_caches()`` (the persistent compile cache must serve the
   recompiles) on the seeded synthetic Titanic with the whole default binary pool (LR 8, RF 18,
   GBT 18, SVC 4 grid points x 3 folds = 144 models x folds, float32),
   ``score_and_evaluate`` on the holdout, ``model.save()`` with AOT export;
2. a SERVER process: ``python -m transmogrifai_tpu.cli serve`` with its
   defaults, a ``{"ready": true}`` barrier, a few UNLABELLED records through
   ``TcpServingClient``, one ``{"metrics": true}``, then SIGTERM with the
   client still connected.

One process holds a chip at a time: this parent never initializes a JAX
backend (``serving.client`` imports jax but opens none); the trainer is one
child, the server the next, and the parent only speaks TCP.

It fails — exit code non-zero, no result line — when JAX finds no TPU, when
any phase fails, when any answer is ``ok: false``, when a fallback counter
listed in ``TRAIN_ZERO`` / ``SERVE_ZERO`` is non-zero, when the holdout AuPR
leaves ``AUPR_TOLERANCE`` of the CPU reference, or when a served probability
differs from ``model.score`` on the same record. The last line of a
successful run is ``{"ok": true, "device": {...}}`` with the device as the
trainer's JAX reported it.

    python3 chip_smoke.py                    # on the chip, full width
    python3 chip_smoke.py --cpu-dry-run      # CPU, tiny size: control flow only
    python3 chip_smoke.py --cpu-dry-run full # CPU, full width: re-derives
                                             # CPU_REFERENCE_AUPR

The dry run is the only way to run it off the chip; it labels every line
``platform: cpu``. Every time it prints is set-up information, not a speed.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
#: everything the run writes (model dir, profile store, audit cache, logs);
#: listed in .gitignore, wiped at start
WORK = os.path.join(ROOT, ".chip_smoke")
SEED = 42
ROWS = 1309
MODELS_X_FOLDS = 144

#: holdout AuPR of this exact run (seed 42, 1309 rows, default pool, float32)
#: under JAX_PLATFORMS=cpu, jax 0.9.0: ``chip_smoke.py --cpu-dry-run full``.
CPU_REFERENCE_AUPR = 0.6547
#: |chip AuPR - CPU reference| allowed. The holdout is 327 rows, so one
#: swapped pair of neighbours moves AuPR by ~0.003; the chip runs the tree
#: histograms and the linear solvers as default-precision (bf16-pass) MXU
#: matmuls over float32 storage, which can move a near-tied split and with
#: it the winning grid point. 0.03 admits that and still rejects a wrong
#: program (a constant or shuffled scorer sits near the 0.38 base rate).
AUPR_TOLERANCE = 0.03
#: ``prepare_fit_fallbacks`` per train on this DAG, by design: the two
#: RealVectorizers (fill-with-mean) and the ModelSelector have no
#: ``fit_device`` kernel, so ``plans/prepare.py`` fits them from the host
#: over device-resident columns (the selector's search itself runs on the
#: device). Asserted exactly, so a new one shows.
EXPECTED_PREPARE_FIT_FALLBACKS = 3
#: |served probability - model.score probability| allowed: the same float32
#: program on the same device, but the server pads each request to its own
#: bucket, and XLA may tile a different batch shape differently.
SERVED_PROB_TOLERANCE = 1e-5

#: trainer telemetry counters that must be zero after the run
TRAIN_ZERO = ("quarantines", "retries", "prepare_plan_fallbacks",
              "prepare_fallbacks", "plan_fallbacks",
              "serve_aot_export_errors")
#: server counters (metrics["counters"]) that must be zero
SERVE_ZERO = ("serve_aot_fallbacks", "serve_aot_dispatch_errors",
              "breaker_trips", "serving_host_fallback_batches",
              "serving_breaker_short_circuits", "serving_device_failures",
              "serve_batch_failures", "serving_deadline_exceeded",
              "serving_rows_quarantined", "serving_rows_invalidated",
              "plan_fallbacks")

_LABEL = {"prefix": ""}


def say(msg: str) -> None:
    print(f"chip_smoke: {_LABEL['prefix']}{msg}", flush=True)


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# the trainer child (the only code here that touches a JAX backend)
# ---------------------------------------------------------------------------

def _tiny_pool():
    """The dry run's pool: every family of the default pool, cut to a
    few shallow grid points so the CPU finishes in about a minute."""
    from transmogrifai_tpu.models import (GBTClassifier, LinearSVC,
                                          LogisticRegression,
                                          RandomForestClassifier)
    return [
        (LogisticRegression(max_iter=20),
         [{"reg_param": r, "elastic_net_param": 0.1} for r in (0.01, 0.1)]),
        (RandomForestClassifier(num_trees=4),
         [{"max_depth": d, "min_instances_per_node": 10,
           "min_info_gain": 0.001} for d in (2, 3)]),
        (GBTClassifier(num_rounds=3),
         [{"max_depth": d, "min_child_weight": 1.0, "gamma": 0.001}
          for d in (2, 3)]),
        (LinearSVC(max_iter=20), [{"reg_param": 0.01}]),
    ]


def _peak_hbm(jax) -> list:
    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def _feature_width(model, pred_name: str) -> int:
    """Width of the feature vector the selected model consumes."""
    stage = next(s for s in model.stages()
                 if s.get_output().name == pred_name)
    vec = stage.input_features[-1].name
    return model.train_dataset[vec].data.shape[1]


def phase_train(mode: str) -> int:
    """Train twice, evaluate, save, score the served sample in-process
    and write ``expected.json`` for the parent. ``mode``: "chip" |
    "tiny" | "full" (the last two are the CPU dry run)."""
    sys.path.insert(0, ROOT)
    import jax

    from transmogrifai_tpu.utils.jax_setup import (backend_block,
                                                   enable_compilation_cache)
    enable_compilation_cache()
    dev = backend_block()
    say(f"platform: {dev['platform']}  device_kind: {dev['device_kind']}  "
        f"device_count: {dev['device_count']}  jax: {dev['jax']}  "
        f"x64: {dev['x64']}")
    want = "tpu" if mode == "chip" else "cpu"
    if dev["platform"] != want:
        say(f"FAIL: this run needs platform {want!r}, JAX reports "
            f"{dev['platform']!r}")
        return 4
    check(not dev["x64"], "the chip path is float32: x64 must be off")
    say(f"compile cache: {dev['compile_cache']['dir']}  profile store: "
        f"{os.environ['TX_PROFILE_STORE']} (starts empty)")

    import numpy as np

    from examples.titanic import (SAMPLE_PASSENGER, build_features,
                                  default_selector, stratified_split,
                                  synthetic_titanic)
    from transmogrifai_tpu.artifacts.store import read_manifest
    from transmogrifai_tpu.evaluators import BinaryClassificationEvaluator
    from transmogrifai_tpu.models.trees import tree_kernel_compiles
    from transmogrifai_tpu.models.trees.forms import _depth_mode, _hist_mode
    from transmogrifai_tpu.parallel.cv import resolve_search_mesh
    from transmogrifai_tpu.runtime import telemetry
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            SelectedModel)
    from transmogrifai_tpu.selector.selector import models_x_folds
    from transmogrifai_tpu.utils import compile_time
    from transmogrifai_tpu.utils.uid import reset as reset_uids
    from transmogrifai_tpu.workflow import Workflow

    tiny = mode == "tiny"
    records = synthetic_titanic(240 if tiny else ROWS, seed=SEED)
    train, test = stratified_split(records, seed=SEED)

    def train_once():
        reset_uids(deterministic=True)   # same feature names both times
        survived, features = build_features()
        selector = (BinaryClassificationModelSelector.with_cross_validation(
            num_folds=3, seed=SEED, stratify=True, models=_tiny_pool())
            if tiny else default_selector(seed=SEED))
        pred = selector.set_input(survived, features).get_output()
        wf = (Workflow().set_result_features(survived, pred)
              .set_input_records(train))
        c0, k0 = compile_time.compile_seconds(), compile_time.cache_counts()
        p0, t0 = tree_kernel_compiles(), time.perf_counter()
        model = wf.train()
        seconds = time.perf_counter() - t0
        k1 = compile_time.cache_counts()
        return model, pred.name, wf, {
            "train_seconds": round(seconds, 2),
            "compile_seconds": round(
                compile_time.compile_seconds() - c0, 2),
            "tree_program_compiles": tree_kernel_compiles() - p0,
            "cache_hits": k1["hits"] - k0["hits"],
            "cache_misses": k1["misses"] - k0["misses"]}

    _, _, _, cold = train_once()
    say(f"set-up, cold train: {json.dumps(cold)}")
    _, _, _, warm = train_once()
    say(f"set-up, warm train: {json.dumps(warm)}")
    # what the first train of the NEXT process from this checkout sees:
    # nothing compiled in memory, the persistent cache warm
    jax.clear_caches()
    model, pred_name, wf, restart = train_once()
    say(f"set-up, train after jax.clear_caches(): {json.dumps(restart)}")
    # every entry the cold train found or wrote must now be found again
    # (JAX counts a "miss" only when it writes an entry, i.e. for compiles
    # of at least 0.5 s, so a program near that line may be written late)
    check(0 < cold["cache_hits"] + cold["cache_misses"]
          <= restart["cache_hits"],
          f"the persistent compile cache at {dev['compile_cache']['dir']} "
          f"did not serve the recompiles: cold {cold}, now {restart}")
    mesh = resolve_search_mesh("auto")
    peaks = _peak_hbm(jax)
    # the tree kernels' paths as the package's resolvers choose them for
    # this table (32 bins a column bounds the packed bin count)
    hist_mode = _hist_mode(len(train), 32 * _feature_width(model, pred_name))
    say(f"hist_mode: {hist_mode}  depth_mode: {_depth_mode()}  "
        f"search mesh: {None if mesh is None else dict(mesh.shape)}  "
        f"peak HBM bytes per device: {peaks}")
    check(all(b is None or b > 0 for b in peaks),
          "a visible device was never used by the search")

    # what came out, by the repo's own means
    selected = [s for s in model.stages() if isinstance(s, SelectedModel)]
    check(len(selected) == 1 and selected[0].summary is not None,
          "no selector summary on the trained model")
    summary = selected[0].summary
    mxf = models_x_folds(model)
    # winner + every CV metric vector, bit for bit: equal digests on one
    # chip and on four is the sharded search's invariance contract
    digest = hashlib.sha256(json.dumps(
        [summary.best_model_name, summary.best_model_params,
         [[r.model_name, r.params, [float(v).hex() for v in r.metric_values]]
          for r in summary.validation_results]],
        sort_keys=True).encode()).hexdigest()[:16]
    say(f"winner: {summary.best_model_name} {summary.best_model_params} "
        f"cv {summary.evaluation_metric}="
        f"{summary.best_validation_metric:.4f}  models_x_folds: {mxf}  "
        f"search digest: {digest}")
    check(not summary.quarantined,
          f"selector quarantined families: {summary.quarantined}")
    if not tiny:
        check(mxf == MODELS_X_FOLDS,
              f"search ran {mxf} models x folds, expected {MODELS_X_FOLDS}")
    for r in summary.validation_results:
        check(bool(np.isfinite(r.metric_values).all()),
              f"non-finite CV metric for {r.model_name} {r.params}")
    plan = wf.last_prepare_plan
    check(plan is not None, "train fell back from the compiled prepare plan")
    host_fits = [p for p in plan.fit_placements
                 if p[1] == "host" and "device columns" in p[2]]
    for label, _where, why in host_fits:
        say(f"prepare fit on host: {label}: {why}")

    evaluator = BinaryClassificationEvaluator(
        label_col="survived", prediction_col=pred_name)
    _, metrics = model.score_and_evaluate(test, evaluator)
    aupr = float(metrics.AuPR)
    say(f"holdout rows: {len(test)}  AuPR: {aupr:.4f}  AuROC: "
        f"{float(metrics.AuROC):.4f}  (CPU reference AuPR "
        f"{CPU_REFERENCE_AUPR}, tolerance {AUPR_TOLERANCE})")
    check(np.isfinite(aupr), "holdout AuPR is not finite")
    if mode == "chip":
        check(abs(aupr - CPU_REFERENCE_AUPR) <= AUPR_TOLERANCE,
              f"holdout AuPR {aupr:.4f} is not within {AUPR_TOLERANCE} of "
              f"the CPU reference {CPU_REFERENCE_AUPR}")

    model_dir = os.path.join(WORK, "model")
    t0 = time.perf_counter()
    model.save(model_dir)
    manifest, state = read_manifest(model_dir)
    check(manifest is not None, f"save wrote no AOT artifact store ({state})")
    say(f"set-up, save with AOT export: {time.perf_counter() - t0:.2f} s  "
        f"scoring buckets: {len(manifest.get('score') or {})}  "
        f"prepare segments: {len(manifest.get('prepare') or {})}  "
        f"platform stamp: {manifest.get('platform')}")
    check(len(manifest.get("score") or {}) == len(manifest["buckets"]),
          "AOT export skipped a scoring bucket")

    # the records the parent will send to the server, unlabelled
    sample = [{k: v for k, v in r.items() if k != "survived"}
              for r in test[:5]] + [dict(SAMPLE_PASSENGER)]
    scored = model.score(sample)
    probs = np.asarray(scored[pred_name].probability, dtype=np.float64)
    check(probs.shape == (len(sample), 2) and np.isfinite(probs).all()
          and np.allclose(probs.sum(axis=1), 1.0, atol=1e-5),
          f"model.score probabilities malformed: {probs!r}")

    counters = telemetry.counters()
    say("trainer counters: " + json.dumps(
        {k: counters.get(k, 0) for k in TRAIN_ZERO
         + ("prepare_fit_fallbacks",)}))
    for k in TRAIN_ZERO:
        check(counters.get(k, 0) == 0,
              f"trainer counter {k} = {counters.get(k)} (must be 0)")
    check(counters.get("prepare_fit_fallbacks", 0)
          == 3 * EXPECTED_PREPARE_FIT_FALLBACKS,
          f"prepare_fit_fallbacks = {counters.get('prepare_fit_fallbacks', 0)}"
          f" over three trains, expected 3 x {EXPECTED_PREPARE_FIT_FALLBACKS}")

    with open(os.path.join(WORK, "expected.json"), "w") as fh:
        json.dump({"device": dev, "pred_name": pred_name, "records": sample,
                   "probabilities": probs.tolist(), "aupr": aupr,
                   "model_dir": model_dir}, fh)
    say(f"peak HBM bytes per device at trainer exit: {_peak_hbm(jax)}")
    return 0


# ---------------------------------------------------------------------------
# the parent: spawns one child at a time, speaks TCP, never opens a backend
# ---------------------------------------------------------------------------

def _child_env(mode: str) -> dict:
    env = dict(os.environ,
               TX_PROFILE_STORE=os.path.join(WORK, "profile_store.json"),
               TX_AUDIT_CACHE=os.path.join(WORK, "audit_cache.json"))
    env.pop("JAX_ENABLE_X64", None)
    if mode != "chip":
        env["JAX_PLATFORMS"] = "cpu"
    return env


def run_trainer(mode: str) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", "train",
           "--mode", mode]
    rc = subprocess.run(cmd, cwd=ROOT, env=_child_env(mode),
                        timeout=1000).returncode
    if rc != 0:
        raise SmokeFailure(f"trainer exited {rc}")
    with open(os.path.join(WORK, "expected.json")) as fh:
        return json.load(fh)


class Server:
    """``python -m transmogrifai_tpu.cli serve`` as a child, its stdout
    read on a thread (one JSON object per line)."""

    def __init__(self, model_dir: str, mode: str):
        self.stderr = open(os.path.join(WORK, "serve.stderr"), "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "transmogrifai_tpu.cli", "serve",
             "--model", f"titanic={model_dir}", "--port", "0"],
            cwd=ROOT, env=_child_env(mode), stdout=subprocess.PIPE,
            stderr=self.stderr, text=True)
        self.lines: list = []
        self._banner = threading.Event()
        self._pump = threading.Thread(target=self._read, daemon=True)
        self._pump.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            if isinstance(doc, dict):
                self.lines.append(doc)
                if doc.get("serving"):
                    self._banner.set()

    def banner(self, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while not self._banner.wait(0.2):
            check(self.proc.poll() is None,
                  f"server exited {self.proc.returncode} before its banner"
                  f"\n{self.stderr_tail()}")
            check(time.monotonic() < deadline,
                  f"server printed no banner within {timeout:.0f} s")
        return next(d for d in self.lines if d.get("serving"))

    def stderr_tail(self) -> str:
        self.stderr.flush()
        with open(self.stderr.name) as fh:
            return fh.read()[-4000:]

    def wait_exit(self, timeout: float) -> int:
        """Exit code once the process is gone and its stdout is read."""
        rc = self.proc.wait(timeout)
        self._pump.join(5)
        return rc

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(30)
        self._pump.join(5)
        self.stderr.close()


def run_server(expected: dict, mode: str) -> None:
    from transmogrifai_tpu.runtime.retry import RetryPolicy
    from transmogrifai_tpu.serving.client import TcpServingClient
    dev, pred = expected["device"], expected["pred_name"]
    t0 = time.perf_counter()
    server = Server(expected["model_dir"], mode)
    client = None
    try:
        banner = server.banner(timeout=600)
        backend = banner.get("backend") or {}
        say(f"set-up, server start to banner: "
            f"{time.perf_counter() - t0:.2f} s  backend: "
            f"{json.dumps(backend)}")
        for key in ("platform", "device_kind", "device_count"):
            check(backend.get(key) == dev[key],
                  f"server runs on {key}={backend.get(key)!r}, the trainer "
                  f"on {dev[key]!r}")

        client = TcpServingClient(
            "127.0.0.1", int(banner["port"]), timeout=120.0,
            retry=RetryPolicy(max_attempts=2, base_delay=0.2, max_delay=0.5))
        ready = client.request({"ready": True})
        check(ready.get("ok") and ready.get("ready"),
              f"server not ready after its banner: {ready}")

        worst = 0.0
        for i, (record, want) in enumerate(
                zip(expected["records"], expected["probabilities"])):
            answer = client.score(record, model="titanic",
                                  request_id=f"smoke-{i}")
            check(answer.get("ok") is True, f"answer {i} not ok: {answer}")
            row = answer["result"][pred]
            got = [row["probability_0"], row["probability_1"]]
            check(all(isinstance(p, float) and 0.0 <= p <= 1.0 for p in got),
                  f"answer {i} probabilities malformed: {row}")
            worst = max(worst, abs(got[0] - want[0]), abs(got[1] - want[1]))
        say(f"served {len(expected['records'])} unlabelled records, all ok; "
            f"max |served - model.score| probability = {worst:.3g} "
            f"(tolerance {SERVED_PROB_TOLERANCE})")
        check(worst <= SERVED_PROB_TOLERANCE,
              f"served probabilities differ from model.score by {worst:.3g}")

        m = client.metrics()
        counters = m.get("counters") or {}
        say("server metrics: " + json.dumps({
            "backend": m.get("backend"), "answered": m.get("answered"),
            "failed_batches": m.get("failed_batches"),
            "plan_compiles": m.get("plan_compiles"), "aot": m.get("aot"),
            "breakers": m.get("breakers"),
            "counters": {k: counters.get(k, 0) for k in SERVE_ZERO
                         + ("serve_aot_dispatches", "serve_aot_loads")}}))
        check((m.get("backend") or {}).get("platform") == dev["platform"],
              f"server metrics report backend {m.get('backend')}")
        check(m.get("answered") == len(expected["records"]),
              f"server answered {m.get('answered')} requests")
        check(m.get("failed_batches") == 0,
              f"failed_batches = {m.get('failed_batches')}")
        # with its defaults the server loads a model's plan, and with it
        # the AOT executables, at the first request for it
        check(((m.get("aot") or {}).get("titanic") or {})
              .get("loadedBuckets"),
              f"server did not load the AOT artifacts: aot = {m.get('aot')}")
        check(m.get("plan_compiles") == 0,
              f"serve process compiled {m.get('plan_compiles')} plan "
              f"programs after the AOT load")
        check(counters.get("serve_aot_dispatches", 0) > 0,
              "no request was dispatched through an AOT executable")
        for k in SERVE_ZERO:
            check(counters.get(k, 0) == 0,
                  f"server counter {k} = {counters.get(k)} (must be 0)")
        check(all(s == "closed" for s in (m.get("breakers") or {}).values()),
              f"a breaker is not closed: {m.get('breakers')}")

        # SIGTERM with this client still connected: the process must be
        # gone within the drain timeout (cli serve default: 30 s)
        t0 = time.perf_counter()
        server.proc.send_signal(signal.SIGTERM)
        try:
            rc = server.wait_exit(30)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("server still alive 30 s after SIGTERM with "
                               "a client attached") from None
        say(f"set-up, SIGTERM to exit with the client attached: "
            f"{time.perf_counter() - t0:.2f} s  exit code {rc}")
        check(rc == 0, f"server exited {rc} after SIGTERM\n"
              f"{server.stderr_tail()}")
        final = server.lines[-1]
        check(final.get("served") == len(expected["records"]),
              f"server's final line: {final}")
    finally:
        if client is not None:
            client.close()
        server.stop()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-dry-run", nargs="?", const="tiny",
                    choices=["tiny", "full"], default=None,
                    help="run on the CPU backend instead of failing "
                         "without a TPU (tiny: control flow; full: the "
                         "flagship width, prints the CPU reference AuPR)")
    ap.add_argument("--phase", choices=["train"], help=argparse.SUPPRESS)
    ap.add_argument("--mode", choices=["chip", "tiny", "full"],
                    default="chip", help=argparse.SUPPRESS)
    args = ap.parse_args()
    mode = args.mode if args.phase else (args.cpu_dry_run or "chip")
    if mode != "chip":
        _LABEL["prefix"] = "platform: cpu | "
    try:
        if args.phase == "train":
            return phase_train(mode)
        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(WORK)
        t0 = time.perf_counter()
        expected = run_trainer(mode)
        run_server(expected, mode)
        say(f"all phases passed in {time.perf_counter() - t0:.0f} s "
            f"(set-up, compilation included)")
    except (SmokeFailure, OSError, subprocess.SubprocessError) as e:
        # a failed check, a server that went away (ServingUnavailable is
        # a ConnectionError), a child that outlived its time limit
        say(f"FAIL: {type(e).__name__}: {e}")
        return 1
    dev = expected["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
