"""Set-up by program (ISSUE 36): the compile log of ``utils/compile_time``
(one record a program: trace, lower, load or compile, by program and thread),
the tracer's ``compile.program`` spans, ``tx trace``'s compile share, and the
benchmark's ``setup_*`` readers on a recorded log."""
import json
import os
import sys
import threading
import time
from collections import deque

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:                 # ``benchmark`` is a root package
    sys.path.insert(0, ROOT)

import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402

from benchmark.layer_metrics import (                 # noqa: E402
    _setup_log, setup_cache_load_s, setup_compile_overlap, setup_compiled_s,
    setup_programs, setup_trace_lower_s)
from transmogrifai_tpu.cli.trace import summarize_trace        # noqa: E402
from transmogrifai_tpu.observability import trace     # noqa: E402
from transmogrifai_tpu.utils import compile_time      # noqa: E402

SAVED = "/jax/compilation_cache/compile_time_saved_sec"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
SPENT = ("trace_s", "lower_s", "backend_s")
READERS = {"setup_trace_lower_s": setup_trace_lower_s,
           "setup_cache_load_s": setup_cache_load_s,
           "setup_compiled_s": setup_compiled_s,
           "setup_programs": setup_programs,
           "setup_compile_overlap": setup_compile_overlap}
with open(os.path.join(os.path.dirname(__file__),
                       "compile_log_recorded.json")) as _fh:
    RECORDED = json.load(_fh)


@pytest.fixture(autouse=True)
def _installed():
    compile_time.install()
    trace.configure(False)
    trace.reset()
    yield
    trace.configure(False)
    trace.reset()


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compile cache in a directory of the test's own, every
    program written to it however quick its compile and small its entry."""
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {name: getattr(jax.config, name) for name in names}
    for name, value in zip(names, (str(tmp_path / "cache"), 0.0, -1)):
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    yield
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def _program(tag):
    """A jitted function of a name no other test compiles, with a jitted
    function inside it: the nested one raises its own trace event first."""
    @jax.jit
    def nested(x):
        return jnp.tanh(x) @ x

    def outer(x):
        return nested(x) * 3.0 + nested(x.T)
    outer.__name__ = outer.__qualname__ = f"clog_{tag}"
    return jax.jit(outer)


def _call(tag, fn=None):
    """(the records the call added, its compile seconds, its wall seconds)"""
    x = jnp.ones((6, 6))
    fn = fn or _program(tag)
    n0, c0 = len(compile_time.compile_log()), compile_time.compile_seconds()
    t0 = time.monotonic()
    fn(x).block_until_ready()
    wall = time.monotonic() - t0
    added = [r for r in compile_time.compile_log()[n0:]
             if r["program"] == f"jit_clog_{tag}"]
    return added, compile_time.compile_seconds() - c0, wall


class TestCompileLog:
    def test_nested_jit_is_one_record_with_its_outermost_trace(self):
        t_before = time.monotonic()
        (rec,), spent, wall = _call("nested")
        assert rec["thread"] == threading.current_thread().name
        assert rec["cache"] in ("compiled", "hit")
        assert rec["trace_s"] > 0 and rec["lower_s"] > 0 \
            and rec["backend_s"] > 0
        assert t_before <= rec["t0"] < rec["t1"] <= time.monotonic()
        # the record's own seconds fit inside its interval, the interval
        # inside the call; the nested function's trace is in trace_s and in
        # the total once, not beside it
        own = sum(rec[k] for k in SPENT)
        assert own <= rec["t1"] - rec["t0"] + 1e-3 <= wall + 1e-3
        assert own - 1e-6 <= spent <= wall

    def test_first_compile_then_hit(self, persistent_cache):
        fn = _program("cached")
        (first,), _, _ = _call("cached", fn)
        assert first["cache"] == "compiled"
        assert first["retrieval_s"] == 0.0 and first["saved_s"] == 0.0
        jax.clear_caches()
        (hit,), spent, wall = _call("cached", fn)
        assert hit["cache"] == "hit" and hit["retrieval_s"] > 0
        assert hit["retrieval_s"] <= hit["backend_s"]
        # the total grew by the trace + lower + backend seconds, no more
        assert sum(hit[k] for k in SPENT) - 1e-6 <= spent <= wall

    def test_cache_detail_is_in_no_total(self):
        import jax.monitoring as monitoring
        with compile_time.section("clog-test:detail"):
            c0 = compile_time.compile_seconds()
            by0 = compile_time.compile_seconds_by_thread()
            monitoring.record_event_duration_secs(SAVED, 7.0)
            monitoring.record_event_duration_secs(RETRIEVAL, 2.0)
            monitoring.record_event_duration_secs("/jax/other/compile", 5.0)
            assert compile_time.compile_seconds() == c0
            assert compile_time.compile_seconds_by_thread() == by0
        sec = compile_time.seconds_by_section("clog-test:")
        assert sec["clog-test:detail"]["compile"] == 0.0
        compile_time.reset_sections("clog-test:")

    def test_thread_name_is_the_familys(self):
        out = []
        th = threading.Thread(target=lambda: out.append(_call("thread")),
                              name="tx-family-X")
        th.start()
        th.join(timeout=120)
        assert not th.is_alive()
        (rec,), spent, _ = out[0]
        assert rec["thread"] == "tx-family-X"
        assert compile_time.compile_seconds_by_thread("tx-family-X")[
            "tx-family-X"] >= sum(rec[k] for k in SPENT) - 1e-6

    def test_log_is_bounded_and_counts_its_drops(self, monkeypatch):
        monkeypatch.setattr(compile_time, "_LOG", deque(maxlen=2))
        dropped0 = compile_time.compile_log_dropped()
        for tag in ("b0", "b1", "b2"):
            _call(tag)
        log = compile_time.compile_log()
        assert len(log) == 2 and log[-1]["program"] == "jit_clog_b2"
        assert compile_time.compile_log_dropped() - dropped0 >= 1
        log[0]["program"] = "edited"          # a copy
        assert compile_time.compile_log()[0]["program"] != "edited"

    def test_threads_share_the_log_without_losing_a_record(self, monkeypatch):
        """More threads than cores feed the listener at a shortened switch
        interval: every record is in the log or counted as dropped, and the
        total holds every thread's seconds."""
        monkeypatch.setattr(compile_time, "_LOG", deque(maxlen=64))
        events = [e for e, f in compile_time._DURATION_EVENTS.items()
                  if f in SPENT]           # trace, lower, backend: in order
        workers, programs = (os.cpu_count() or 4) + 4, 40
        c0, d0 = (compile_time.compile_seconds(),
                  compile_time.compile_log_dropped())

        def feed(i):
            for j in range(programs):
                for event in events:
                    compile_time._on_event_duration(
                        event, 1e-3, fun_name="stress" if event == events[0]
                        else "jit(stress)")
                    time.sleep(2e-3)     # events in turn, not nested

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=feed, args=(i,),
                                        name=f"tx-family-S{i}")
                       for i in range(workers)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=120)
        finally:
            sys.setswitchinterval(interval)
        assert not any(th.is_alive() for th in threads)
        log = compile_time.compile_log()
        assert len(log) == 64 and all(
            r["program"] == "jit_stress" and r["trace_s"] == 1e-3
            and r["lower_s"] == 1e-3 for r in log)
        assert len(log) + compile_time.compile_log_dropped() - d0 \
            == workers * programs
        assert compile_time.compile_seconds() - c0 == pytest.approx(
            workers * programs * 3e-3, rel=1e-6)
        by_thread = compile_time.compile_seconds_by_thread("tx-family-S")
        assert len(by_thread) == workers and all(
            v == pytest.approx(programs * 3e-3) for v in by_thread.values())

    def test_stage_and_family_compile_seconds_stay_under_their_wall(
            self, persistent_cache):
        """On a cached start (every program a hit) the listener's
        ``StageMetric.compile_seconds`` and the family profile's compile
        seconds are at most the wall seconds beside them: before ISSUE 36
        they held the seconds the cache SAVED and every nested trace twice."""
        from transmogrifai_tpu.features.builder import FeatureBuilder
        from transmogrifai_tpu.models import LogisticRegression
        from transmogrifai_tpu.ops import transmogrify
        from transmogrifai_tpu.selector import (
            BinaryClassificationModelSelector, validator)
        from transmogrifai_tpu.utils.listener import WorkflowListener
        from transmogrifai_tpu.workflow import Workflow
        import numpy as np
        rng = np.random.default_rng(7)
        records = [{"x": float(v), "label": float(v + rng.normal() > 0)}
                   for v in rng.normal(size=120)]

        def train():
            label = FeatureBuilder.real_nn("label").extract(
                lambda r: r["label"]).as_response()
            x = FeatureBuilder.real("x").extract(
                lambda r: r["x"]).as_predictor()
            selector = BinaryClassificationModelSelector \
                .with_cross_validation(
                    num_folds=3, splitter=None,
                    models=[(LogisticRegression(max_iter=20),
                             [{"reg_param": r} for r in (0.01, 0.1)])])
            pred = selector.set_input(label, transmogrify([x])).get_output()
            listener = WorkflowListener()
            (Workflow().set_result_features(label, pred)
             .set_input_records(records).with_listener(listener).train())
            return listener.metrics.stage_metrics

        train()
        jax.clear_caches()
        validator.reset_family_profile()
        hits0 = compile_time.cache_counts()["hits"]
        metrics = train()
        assert compile_time.cache_counts()["hits"] > hits0
        assert any(m.compile_seconds > 0 for m in metrics)
        for m in metrics:
            assert m.compile_seconds <= m.seconds + 1e-3, m
        profile = validator._FAMILY_PROFILE
        assert profile and all(
            v["compile"] <= v["seconds"] + 1e-3 for v in profile.values())


class TestCompileProgramSpan:
    def test_child_of_the_span_open_on_the_calling_thread(self):
        trace.configure(True)
        with trace.span("train"):
            ref = trace.current_ref()

            def work():
                with trace.span("search.fetch", parent=ref, family="X"):
                    _call("span")
            th = threading.Thread(target=work, name="tx-family-X")
            th.start()
            th.join(timeout=120)
            assert not th.is_alive()
        spans = trace.spans()
        fetch = next(s for s in spans if s["name"] == "search.fetch")
        (prog,) = [s for s in spans if s["name"] == "compile.program"
                   and s["attrs"]["program"] == "jit_clog_span"]
        rec = next(r for r in compile_time.compile_log()
                   if r["program"] == "jit_clog_span")
        assert prog["parent"] == fetch["sid"]
        assert prog["trace"] == fetch["trace"]
        assert prog["t0"] == rec["t0"]
        assert prog["dur"] == pytest.approx(rec["t1"] - rec["t0"])
        assert prog["attrs"] == {k: v for k, v in rec.items()
                                 if k not in ("t0", "t1")}
        assert fetch["t0"] <= prog["t0"] \
            and prog["t0"] + prog["dur"] <= fetch["t0"] + fetch["dur"]

    def test_outside_any_span_it_is_dropped_and_the_log_has_it(self):
        trace.configure(True)
        (rec,), _, _ = _call("orphan")
        assert rec["program"] == "jit_clog_orphan"
        assert trace.spans() == []

    def test_off_is_the_shared_noop_and_the_log_still_fills(self):
        assert trace.span("search.fetch") is trace._NOOP
        with trace.span("search.fetch"):
            (rec,), _, _ = _call("off")
        assert rec["program"] == "jit_clog_off"
        assert trace.spans() == []
        assert compile_time._PROGRAM_OBSERVER["fn"] is None

    def test_tx_trace_reads_the_compile_share_from_the_programs(self):
        def span(sid, parent, name, dur, **attrs):
            return {"sid": sid, "parent": parent, "trace": "t1",
                    "name": name, "t0": 0.0, "dur": dur, "attrs": attrs,
                    "events": []}
        records = [
            span(1, None, "train", 10.0),
            # a segment's section holds its stage's seconds: 3.0 twice
            span(2, 1, "section:prepare:seg0", 4.0, compile_seconds=3.0),
            span(3, 2, "section:prepare:stage:X", 3.5, compile_seconds=3.0)]
        assert summarize_trace(records)["compile_seconds"] == 6.0
        assert summarize_trace(records)["costliest_programs"] == []
        records += [
            span(10 + i, 1, "compile.program", 1.0 + i, program=f"jit_p{i}",
                 thread="tx-family-X", cache="hit" if i % 2 else "compiled",
                 trace_s=0.25, lower_s=0.25, backend_s=0.5 * i,
                 retrieval_s=0.1, saved_s=40.0)
            for i in range(6)]
        summary = summarize_trace(records)
        assert summary["compile_seconds"] == 6 * 0.5 + 0.5 * 15
        assert summary["compile_share"] == 1.05
        assert [p["program"] for p in summary["costliest_programs"]] == [
            "jit_p5", "jit_p4", "jit_p3", "jit_p2", "jit_p1"]
        assert summary["costliest_programs"][0] == {
            "program": "jit_p5", "thread": "tx-family-X", "cache": "hit",
            "seconds": 3.0}


# ---------------------------------------------------------------------------
# the benchmark's readers
# ---------------------------------------------------------------------------

@pytest.fixture
def recorded_log(monkeypatch):
    """The package's log as one run recorded it: the set-up's records, then
    one that entered after the window opened (the readers must cut it)."""
    later = dict(RECORDED["records"][0], program="jit_after_the_window",
                 t0=9e9, t1=9e9 + 50.0, backend_s=50.0)
    _log_of(monkeypatch, RECORDED["records"] + [later])
    yield {"setup_compiles": list(RECORDED["setup_compiles"])}
    _setup_log.cut.cache_clear()


def _log_of(monkeypatch, records):
    """The package's log replaced by ``records``; the observations of a
    harness that counted every one of them before its window."""
    monkeypatch.setattr(compile_time, "compile_log",
                        lambda: [dict(r) for r in records])
    monkeypatch.setattr(compile_time, "compile_log_dropped", lambda: 0)
    _setup_log.cut.cache_clear()
    return {"setup_compiles": (len(records),
                               sum(r["backend_s"] for r in records))}


def _record(t0, t1, **fields):
    return dict({"program": "jit_p", "thread": "MainThread", "t0": t0,
                 "t1": t1, "trace_s": 0.5, "lower_s": 0.25, "backend_s": 1.0,
                 "cache": "hit", "retrieval_s": 0.75, "saved_s": 3.0},
                **fields)


class TestSetupReaders:
    @pytest.mark.parametrize("name", sorted(READERS))
    def test_reader_on_the_recorded_log(self, name, recorded_log, capsys):
        assert READERS[name].read(recorded_log) == pytest.approx(
            RECORDED["pinned"][name], rel=1e-12)
        out = capsys.readouterr().out
        assert out.count("bench: set-up by program: [program, thread, "
                         "trace_s, lower_s, backend_s, cache]") == 1
        assert "jit_after_the_window" not in out
        READERS[name].read(recorded_log)            # said once a process
        assert capsys.readouterr().out == ""

    def test_pins_are_the_records_arithmetic(self):
        recs, pins = RECORDED["records"], RECORDED["pinned"]
        assert len(recs) == RECORDED["setup_compiles"][0] \
            == pins["setup_programs"]
        assert sum(r["backend_s"] for r in recs) == pytest.approx(
            RECORDED["setup_compiles"][1], rel=1e-9)
        assert pins["setup_cache_load_s"] == pytest.approx(sum(
            r["retrieval_s"] for r in recs if r["cache"] == "hit"))
        assert pins["setup_compiled_s"] == pytest.approx(sum(
            r["backend_s"] for r in recs if r["cache"] == "compiled"))
        assert pins["setup_trace_lower_s"] == pytest.approx(sum(
            r["trace_s"] + r["lower_s"] for r in recs))
        assert pins["setup_compile_overlap"] >= 1.0

    def test_overlap_is_one_for_turns_and_two_for_two_at_once(
            self, monkeypatch):
        turns = [_record(10.0, 12.0), _record(12.0, 15.0),
                 _record(20.0, 21.0, thread="tx-family-X")]
        assert setup_compile_overlap.read(_log_of(monkeypatch, turns)) \
            == pytest.approx(1.0)
        at_once = [_record(10.0, 12.0), _record(10.0, 12.0,
                                                thread="tx-family-X")]
        assert setup_compile_overlap.read(_log_of(monkeypatch, at_once)) \
            == pytest.approx(2.0)
        assert setup_compile_overlap.read(_log_of(monkeypatch, [])) is None

    def test_hits_and_compiles_are_told_apart(self, monkeypatch):
        obs = _log_of(monkeypatch, [
            _record(0.0, 2.0), _record(2.0, 9.0, cache="compiled",
                                       backend_s=6.0, retrieval_s=0.0)])
        assert setup_cache_load_s.read(obs) == 0.75
        assert setup_compiled_s.read(obs) == 6.0
        assert setup_trace_lower_s.read(obs) == 1.5
        assert setup_programs.read(obs) == 2

    @pytest.mark.parametrize("fault", ["seconds", "count", "dropped",
                                       "no_log"])
    def test_every_reader_is_none_where_the_log_cannot_be_told(
            self, fault, recorded_log, monkeypatch, capsys):
        count, seconds = recorded_log["setup_compiles"]
        if fault == "seconds":
            recorded_log["setup_compiles"] = [count, seconds * 1.006]
        elif fault == "count":
            recorded_log["setup_compiles"] = [count + 2, seconds]
        elif fault == "dropped":
            monkeypatch.setattr(compile_time, "compile_log_dropped",
                                lambda: 3)
        else:
            monkeypatch.delattr(compile_time, "compile_log")
        assert [r.read(recorded_log) for r in READERS.values()] == [None] * 5
        said = [line for line in capsys.readouterr().out.splitlines()
                if line.startswith("bench: set-up by program")]
        assert len(said) == 1 and (
            "keeps no compile log" in said[0] if fault == "no_log"
            else "NOT READ" in said[0])

    def test_within_half_a_percent_is_read(self, recorded_log):
        count, seconds = recorded_log["setup_compiles"]
        recorded_log["setup_compiles"] = [count, seconds * 1.004]
        assert setup_programs.read(recorded_log) == count
