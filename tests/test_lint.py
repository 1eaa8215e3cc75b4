"""Pre-flight static analyzer tests: every rule demonstrated by a
failing fixture, a clean negative case, the repo-clean CI gate, and
regression tests for the satellite bugfixes that shipped with `tx lint`.
"""
import os
import sys
import textwrap

import numpy as np
import pytest

from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.features.feature import Feature
from transmogrifai_tpu.lint import (Baseline, LintError, abstract_probe,
                                    lint_dag, lint_model, lint_paths,
                                    lint_source, lint_workflow)
from transmogrifai_tpu.models import LogisticRegression
from transmogrifai_tpu.models.linear import LogisticRegressionModel
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.stages.base import UnaryTransformer
from transmogrifai_tpu.types import OPVector, Real, RealNN, Text
from transmogrifai_tpu.workflow import Workflow

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO_ROOT, "transmogrifai_tpu")


def _rules(findings):
    return {f.rule_id for f in findings}


# ---------------------------------------------------------------------------
# DAG fixtures
# ---------------------------------------------------------------------------

def _basic_pipeline():
    label = FeatureBuilder.real_nn("label").extract(
        lambda r: r["label"]).as_response()
    x = FeatureBuilder.real("x").extract(lambda r: r["x"]).as_predictor()
    cat = FeatureBuilder.pick_list("cat").extract(
        lambda r: r["cat"]).as_predictor()
    fv = transmogrify([x, cat])
    pred = LogisticRegression().set_input(label, fv).get_output()
    return label, fv, pred


class TestDagRules:
    def test_clean_dag_has_no_findings(self):
        label, fv, pred = _basic_pipeline()
        assert lint_dag([pred]) == []

    def test_d01_leakage_path(self):
        # a manually built feature hides its response ancestry (the
        # is_response flag the set_input guard relies on is wrong)
        label, fv, pred = _basic_pipeline()
        leaky = Feature("leaky", OPVector, is_response=False,
                        origin_stage=fv.origin_stage, parents=(label, fv))
        pred2 = LogisticRegression().set_input(label, leaky).get_output()
        findings = lint_dag([pred2])
        assert "TX-D01" in _rules(findings)
        (f,) = [f for f in findings if f.rule_id == "TX-D01"]
        assert f.severity == "error" and "leak" in f.message.lower()

    def test_d01_matrix_is_response(self):
        label, fv, pred = _basic_pipeline()
        resp_vec = Feature("resp_vec", OPVector, is_response=True,
                           origin_stage=fv.origin_stage,
                           parents=fv.parents)
        lr = LogisticRegression()
        lr.input_features = (label, resp_vec)   # bypass set_input guard
        out = Feature("p", lr.output_type, origin_stage=lr,
                      parents=(label, resp_vec))
        assert "TX-D01" in _rules(lint_dag([out]))

    def test_d01_sanity_checked_path_is_legit(self):
        # label flowing through an AllowLabelAsInput stage is NOT leakage
        label, fv, pred = _basic_pipeline()
        checked = fv.sanity_check(label)
        pred2 = LogisticRegression().set_input(label, checked).get_output()
        assert "TX-D01" not in _rules(lint_dag([pred2]))

    def test_d02_cycle(self):
        a = Feature("a", Real)
        st = UnaryTransformer()
        st.input_features = (a,)
        b = Feature("b", Real, origin_stage=st, parents=(a,))
        a.parents = (b,)          # close the loop
        findings = lint_dag([b])
        assert "TX-D02" in _rules(findings)

    def test_d03_dead_stage(self):
        label, fv, pred = _basic_pipeline()
        checked = fv.sanity_check(label)   # built but never wired in
        findings = lint_dag([pred], extra_features=[checked])
        dead = [f for f in findings if f.rule_id == "TX-D03"]
        assert len(dead) == 1 and dead[0].severity == "warning"
        assert checked.name in dead[0].message

    def test_d04_type_mismatch_with_converter_hint(self):
        class WantsReal(UnaryTransformer):
            input_types = (Real,)
            output_type = Real

        txt = Feature("txt", Text)
        st = WantsReal()
        st.input_features = (txt,)       # bypass the set_input guard
        out = Feature("out", Real, origin_stage=st, parents=(txt,))
        findings = lint_dag([out])
        (f,) = [f for f in findings if f.rule_id == "TX-D04"]
        assert "Real" in f.message and "Text" in f.message
        assert "to_real" in (f.hint or "")

    def test_d05_untrained_estimator_in_scoring_dag(self):
        from transmogrifai_tpu.workflow.workflow import WorkflowModel
        label, fv, pred = _basic_pipeline()
        model = WorkflowModel(result_features=(pred,))
        findings = lint_model(model)
        assert "TX-D05" in _rules(findings)
        # the same DAG is fine pre-train
        assert "TX-D05" not in _rules(lint_workflow(
            Workflow().set_result_features(pred)))

    def test_d06_duplicate_stage_uid(self):
        class T(UnaryTransformer):
            output_type = Real

        x1, x2 = Feature("x1", Real), Feature("x2", Real)
        s1, s2 = T(), T()
        s2.uid = s1.uid
        s1.input_features, s2.input_features = (x1,), (x2,)
        o1 = Feature("o1", Real, origin_stage=s1, parents=(x1,))
        o2 = Feature("o2", Real, origin_stage=s2, parents=(x2,))
        assert "TX-D06" in _rules(lint_dag([o1, o2]))

    def test_d07_vector_metadata_mismatch(self):
        from transmogrifai_tpu.utils.vector_meta import (
            VectorColumnMetadata, VectorMetadata)
        label = Feature("label", RealNN, is_response=True)
        fv = Feature("fv", OPVector)
        m = LogisticRegressionModel(coefficients=np.zeros(3),
                                    intercept=0.0)
        m.vector_metadata = VectorMetadata("fv", tuple(
            VectorColumnMetadata(parent_feature_name="x",
                                 parent_feature_type="Real")
            for _ in range(5)))
        m.input_features = (label, fv)
        out = Feature("p", m.output_type, origin_stage=m,
                      parents=(label, fv))
        (f,) = [f for f in lint_dag([out]) if f.rule_id == "TX-D07"]
        assert "3" in f.message and "5" in f.message


# ---------------------------------------------------------------------------
# JAX / AST rules
# ---------------------------------------------------------------------------

def _src(code):
    return lint_source(textwrap.dedent(code), "<fixture>")


class TestJaxAstRules:
    def test_j01_np_call_in_jit(self):
        findings = _src("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                return np.sum(x)
        """)
        assert _rules(findings) == {"TX-J01"}
        assert "jnp.sum" in findings[0].hint

    def test_j01_item_and_float(self):
        findings = _src("""
            import jax

            @jax.jit
            def f(x):
                return float(x) + x.item()
        """)
        assert [f.rule_id for f in findings] == ["TX-J01", "TX-J01"]

    def test_j01_host_code_untouched(self):
        # numpy OUTSIDE jit is host orchestration — no findings
        assert _src("""
            import numpy as np

            def host(x):
                return np.sum(np.asarray(x, dtype=np.float64)).item()
        """) == []

    def test_j02_jit_per_call_and_in_loop(self):
        findings = _src("""
            import jax

            def per_call(f, x):
                return jax.jit(f)(x)

            def in_loop(fs, x):
                return [jax.jit(f)(x) for f in fs or ()] or [
                    jax.jit(f)(x) for f in fs]
        """)
        assert "TX-J02" in _rules(findings)
        findings2 = _src("""
            import jax

            def in_loop(fs, x):
                out = []
                for f in fs:
                    out.append(jax.jit(f)(x))
                return out
        """)
        errs = [f for f in findings2 if f.rule_id == "TX-J02"]
        assert errs and errs[0].severity == "error"

    def test_j02_memoized_builder_is_blessed(self):
        assert _src("""
            import functools
            import jax

            @functools.lru_cache(maxsize=8)
            def builder(depth):
                def body(x):
                    return x * depth
                return jax.jit(body)
        """) == []

    def test_j03_nonhashable_static(self):
        findings = _src("""
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("ks",))
            def f(x, ks):
                return x

            def caller(x):
                return f(x, ks=[1, 2])
        """)
        (f,) = [f for f in findings if f.rule_id == "TX-J03"]
        assert "ks" in f.message and f.severity == "error"

    def test_j04_float64_creep(self):
        findings = _src("""
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                return x.astype(jnp.float64) + jnp.zeros(
                    3, dtype=jnp.float64)
        """)
        assert [f.rule_id for f in findings] == ["TX-J04", "TX-J04"]

    def test_j04_dtype_guard_is_not_creep(self):
        assert _src("""
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                return x if x.dtype == jnp.float64 else x * 2
        """) == []

    def test_j05_traced_control_flow(self):
        findings = _src("""
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("k",))
            def f(x, n, k):
                if k:           # static: fine
                    x = x * 2
                if n > 0:       # traced: concretization error
                    x = x + 1
                while x > 0:    # traced: concretization error
                    x = x - 1
                if x is None:   # identity check: fine
                    return x
                return x
        """)
        assert [f.rule_id for f in findings] == ["TX-J05", "TX-J05"]

    def test_j06_serving_per_call_jit(self):
        code = textwrap.dedent("""
            import jax

            def handle_request(f, x):
                return jax.jit(f)(x)
        """)
        findings = lint_source(code, "transmogrifai_tpu/serving/api.py")
        assert [f.rule_id for f in findings] == ["TX-J06"]
        assert findings[0].severity == "error"
        # the SAME source outside serving/ is the milder TX-J02 warning
        assert _rules(lint_source(code, "pkg/models/api.py")) == {"TX-J02"}

    def test_j06_serving_transform_value_loop(self):
        code = textwrap.dedent("""
            def score_batch(stages, rows):
                out = []
                for r in rows:
                    out.append(stages[0].transform_value(r))
                return out + [s.transform_value(rows[0]) for s in stages]
        """)
        findings = lint_source(code, "x/serving/loop.py")
        assert [f.rule_id for f in findings] == ["TX-J06", "TX-J06"]
        # batched columnar code in serving/ is clean
        assert lint_source(textwrap.dedent("""
            def score_batch(stage, ds):
                return stage.transform_dataset(ds)
        """), "x/serving/ok.py") == []
        # and transform_value loops OUTSIDE serving/ are not its business
        assert lint_source(code, "x/local/loop.py") == []

    def test_j09_train_path_transform_columns_walk(self):
        code = textwrap.dedent("""
            def fit_layer(model, ds, names):
                return model.transform_columns([ds[n] for n in names])
        """)
        findings = lint_source(
            code, "transmogrifai_tpu/workflow/workflow.py")
        assert [f.rule_id for f in findings] == ["TX-J09"]
        assert findings[0].severity == "warning"
        assert "prepare" in (findings[0].hint or "")
        # transform_dataset is the same host walk
        findings = lint_source(textwrap.dedent("""
            def fit_layer(stage, ds):
                return stage.transform_dataset(ds)
        """), "x/workflow/runner.py")
        assert [f.rule_id for f in findings] == ["TX-J09"]
        # the SAME source outside workflow/ is not its business (the
        # prepare plan's own recorded host fallbacks live in plans/)
        assert lint_source(code,
                           "transmogrifai_tpu/plans/prepare.py") == []

    def test_j09_train_path_transform_value_loop(self):
        code = textwrap.dedent("""
            def prepare(stage, rows):
                return [stage.transform_value(r) for r in rows]
        """)
        findings = lint_source(code, "x/workflow/exec.py")
        assert [f.rule_id for f in findings] == ["TX-J09"]
        assert findings[0].severity == "error"

    def test_j09_escape_hatch_suppression(self, tmp_path):
        # the blessed TX_PREPARE=host walk carries an inline disable —
        # visible, reviewable, and honored by the engine
        d = tmp_path / "workflow"
        d.mkdir()
        p = d / "mod.py"
        p.write_text(
            "def f(model, cols):\n"
            "    return model.transform_columns(cols)"
            "  # tx-lint: disable=TX-J09\n")
        findings, _ = lint_paths([str(p)])
        assert findings == []

    def test_j10_time_sleep_in_serving_async_handler(self):
        code = textwrap.dedent("""
            import time

            async def handle(queue):
                time.sleep(0.01)
                return queue.popleft()
        """)
        findings = lint_source(code, "transmogrifai_tpu/serving/server.py")
        assert [f.rule_id for f in findings] == ["TX-J10"]
        assert findings[0].severity == "error"
        assert "asyncio.sleep" in (findings[0].hint or "")
        # the same call in a SYNC serving function is not its business
        assert lint_source(textwrap.dedent("""
            import time

            def worker():
                time.sleep(0.01)
        """), "x/serving/server.py") == []
        # nor is an async handler OUTSIDE serving/
        assert lint_source(code, "x/workers/pool.py") == []

    def test_j10_device_sync_and_materialization(self):
        findings = lint_source(textwrap.dedent("""
            import numpy as np

            async def handle(out):
                out.block_until_ready()
                return np.asarray(out)
        """), "x/serving/loop.py")
        assert [f.rule_id for f in findings] == ["TX-J10", "TX-J10"]

    def test_j10_file_io_and_bare_sleep(self):
        findings = lint_source(textwrap.dedent("""
            from time import sleep

            async def handle(path):
                sleep(0.5)
                with open(path) as fh:
                    return fh.read()
        """), "x/serving/io.py")
        assert [f.rule_id for f in findings] == ["TX-J10", "TX-J10"]

    def test_j10_awaited_sleep_and_executor_idiom_clean(self):
        # `await asyncio.sleep` and blocking work pushed into a NESTED
        # sync function (the run_in_executor idiom) are the blessed
        # patterns and stay clean
        assert lint_source(textwrap.dedent("""
            import asyncio
            import time
            import numpy as np

            async def handle(loop, pool, out):
                await asyncio.sleep(0.001)

                def materialize():
                    time.sleep(0.0)
                    return np.asarray(out)

                return await loop.run_in_executor(pool, materialize)
        """), "x/serving/server.py") == []

    def test_j07_grid_value_into_static_argname(self):
        findings = _src("""
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("depth",))
            def kern(x, depth):
                return x * depth

            def fit_fold_grid_arrays(X, grid):
                return [kern(X, depth=p["max_depth"]) for p in grid]
        """)
        (f,) = [f for f in findings if f.rule_id == "TX-J07"]
        assert "depth" in f.message and f.severity == "warning"
        assert "fit_fold_grid_arrays" in f.message

    def test_j07_grid_value_keys_memoized_builder(self):
        findings = _src("""
            import functools
            import jax

            @functools.lru_cache(maxsize=None)
            def make_kernel(depth):
                def body(x):
                    return x * depth
                return jax.jit(body)

            def fit_fold_grid_arrays(X, grid):
                out = []
                for gi, p in enumerate(list(grid)):
                    depth = p["max_depth"]
                    out.append(make_kernel(depth)(X))
                return out
        """)
        (f,) = [f for f in findings if f.rule_id == "TX-J07"]
        assert "make_kernel" in f.message

    def test_j07_aggregate_statics_are_blessed(self):
        # whole-grid aggregates (one value per SEARCH, not per point)
        # may shape statics — the repo's grouped-statics idiom
        assert _src("""
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("use_l1",))
            def kern(x, use_l1):
                return x

            def fit_fold_grid_arrays(X, grid):
                use_l1 = any(p.get("l1") for p in grid)
                return kern(X, use_l1=bool(use_l1))
        """) == []

    def test_j07_taint_stops_at_nontrivial_calls(self):
        # grid -> group_grid(...) -> groups: the grouped-statics path
        # compiles once per GROUP, so the taint deliberately stops
        assert _src("""
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("shape_key",))
            def kern(x, shape_key):
                return x

            def group_grid(grid):
                return {}

            def fit_fold_grid_arrays(X, grid):
                groups = group_grid(grid)
                return [kern(X, shape_key=k) for k in groups]
        """) == []

    def test_j07_outside_grid_kernel_is_silent(self):
        assert _src("""
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("depth",))
            def kern(x, depth):
                return x * depth

            def plain_fit(X, params):
                return kern(X, depth=params["max_depth"])
        """) == []

    def test_e00_parse_error(self):
        findings = lint_source("def broken(:\n", "bad.py")
        assert _rules(findings) == {"TX-E00"}

    def test_shape_reads_are_static(self):
        assert _src("""
            import jax

            @jax.jit
            def f(x):
                if x.shape[0] > 4:
                    return x[:4]
                if len(x) > 2:
                    return x
                return x * x.ndim
        """) == []


class TestAbstractProbe:
    def test_probe_catches_host_transfer(self):
        import jax
        import numpy as np

        def bad(x):
            return np.asarray(x) + 1
        findings = abstract_probe(
            bad, jax.ShapeDtypeStruct((4,), "float32"))
        assert _rules(findings) == {"TX-J01"}

    def test_probe_catches_concretization(self):
        import jax

        def bad(x):
            if x[0] > 0:
                return x
            return -x
        findings = abstract_probe(
            bad, jax.ShapeDtypeStruct((4,), "float32"))
        assert _rules(findings) == {"TX-J05"}

    def test_probe_clean_fn_and_no_device_exec(self):
        import jax
        import jax.numpy as jnp

        calls = []

        def good(x):
            calls.append(1)     # tracing runs the python body once
            return jnp.tanh(x) * 2
        assert abstract_probe(
            good, jax.ShapeDtypeStruct((8, 3), "float32")) == []
        assert calls == [1]     # traced abstractly, never executed again


# ---------------------------------------------------------------------------
# suppression + baseline
# ---------------------------------------------------------------------------

class TestSuppression:
    BAD = ("import jax\nimport numpy as np\n\n"
           "@jax.jit\ndef f(x):\n    return np.sum(x)")

    def test_inline_disable(self, tmp_path):
        p = tmp_path / "mod.py"
        p.write_text(self.BAD.replace(
            "return np.sum(x)",
            "return np.sum(x)  # tx-lint: disable=TX-J01"))
        findings, _ = lint_paths([str(p)])
        assert findings == []

    def test_inline_disable_all(self, tmp_path):
        p = tmp_path / "mod.py"
        p.write_text(self.BAD.replace(
            "return np.sum(x)", "return np.sum(x)  # tx-lint: disable"))
        assert lint_paths([str(p)])[0] == []

    def test_baseline_roundtrip(self, tmp_path):
        p = tmp_path / "mod.py"
        p.write_text(self.BAD)
        findings, _ = lint_paths([str(p)])
        assert len(findings) == 1
        bl_path = str(tmp_path / "baseline.json")
        Baseline.write(bl_path, findings)
        fresh, stale = lint_paths([str(p)], Baseline.load(bl_path))
        assert fresh == [] and stale == []
        # fixing the file makes the baseline entry stale
        p.write_text("import numpy as np\n")
        fresh, stale = lint_paths([str(p)], Baseline.load(bl_path))
        assert fresh == [] and len(stale) == 1


# ---------------------------------------------------------------------------
# workflow integration + the repo gate
# ---------------------------------------------------------------------------

class _UntouchableData:
    """train() must fail validation BEFORE reading any data."""

    def __iter__(self):
        raise AssertionError("input data was touched during pre-flight")


class TestWorkflowValidate:
    def _leaky_workflow(self):
        label, fv, pred = _basic_pipeline()
        leaky = Feature("leaky", OPVector, is_response=False,
                        origin_stage=fv.origin_stage, parents=(label, fv))
        pred2 = LogisticRegression().set_input(label, leaky).get_output()
        wf = Workflow().set_result_features(pred2)
        wf._input_data = _UntouchableData()
        return wf

    def test_strict_raises_before_touching_data(self):
        wf = self._leaky_workflow()
        with pytest.raises(LintError, match="TX-D01"):
            wf.train(validate="strict")

    def test_warn_logs_and_proceeds_to_data(self, caplog):
        wf = self._leaky_workflow()
        # warn mode continues past lint - so it MUST hit the data probe
        with caplog.at_level("WARNING"):
            with pytest.raises(AssertionError, match="touched"):
                wf.train(validate="warn")
        assert "TX-D01" in caplog.text

    def test_off_skips_lint(self):
        wf = self._leaky_workflow()
        with pytest.raises(AssertionError, match="touched"):
            wf.train(validate="off")

    def test_bad_validate_value(self):
        wf = self._leaky_workflow()
        with pytest.raises(ValueError, match="validate"):
            wf.train(validate="bogus")

    def test_clean_workflow_trains_strict(self, rng):
        recs = [{"x": float(rng.normal()), "cat": ["a", "b"][i % 2],
                 "label": float(i % 2)} for i in range(60)]
        label, fv, pred = _basic_pipeline()
        model = (Workflow().set_result_features(pred)
                 .set_input_records(recs).train(validate="strict"))
        assert model.score(recs).n_rows == 60


class TestObservabilityRule:
    """TX-O01: telemetry/trace emission inside a jitted body records
    TRACE time, not run time (docs/lint.md, docs/observability.md)."""

    def test_o01_telemetry_event_and_count_in_jit(self):
        findings = _src("""
            import jax
            from transmogrifai_tpu.runtime import telemetry

            @jax.jit
            def kernel(x):
                telemetry.event("dispatched", rows=8)
                telemetry.count("kernel_calls")
                return x * 2
        """)
        assert [f.rule_id for f in findings] == ["TX-O01", "TX-O01"]
        assert all(f.severity == "error" for f in findings)
        assert "COMPILE" in findings[0].message

    def test_o01_wall_clock_read_in_jit(self):
        findings = _src("""
            import jax
            import time

            @jax.jit
            def kernel(x):
                t0 = time.perf_counter()
                y = x * 2
                return y, time.perf_counter() - t0
        """)
        assert [f.rule_id for f in findings] == ["TX-O01", "TX-O01"]
        assert "trace time" in findings[0].message

    def test_o01_tracer_span_in_jit(self):
        findings = _src("""
            import jax
            from transmogrifai_tpu.observability import trace

            @jax.jit
            def kernel(x):
                trace.add_event("inner", n=1)
                return x
        """)
        assert _rules(findings) == {"TX-O01"}

    def test_o01_host_side_emission_is_fine(self):
        # the same calls AROUND the jitted dispatch are the blessed
        # pattern — no findings
        assert _src("""
            import jax
            import time
            from transmogrifai_tpu.runtime import telemetry

            @jax.jit
            def kernel(x):
                return x * 2

            def dispatch(x):
                t0 = time.perf_counter()
                out = kernel(x)
                telemetry.event("dispatched",
                                seconds=time.perf_counter() - t0)
                return out
        """) == []

    def test_o01_compile_time_section_is_exempt(self):
        # measuring trace cost inside a traced body is section()'s
        # documented job (plans/prepare.py per-stage sections)
        assert _src("""
            import jax
            from transmogrifai_tpu.utils import compile_time

            @jax.jit
            def kernel(x):
                with compile_time.section("prepare:stage:X"):
                    y = x * 2
                return y
        """) == []

    def test_o01_inline_suppression(self, tmp_path):
        # suppressions live at the file layer (engine applies them)
        p = tmp_path / "kern.py"
        p.write_text(textwrap.dedent("""
            import jax
            import time

            @jax.jit
            def kernel(x):
                t0 = time.time()  # tx-lint: disable=TX-O01
                return x
        """))
        findings, _ = lint_paths([str(p)])
        assert [f.rule_id for f in findings] == []


class TestRepoGate:
    def test_package_source_is_lint_clean(self):
        """The analyzer gates this repo: any new hot-path defect in
        transmogrifai_tpu/ fails this test (and hence tier-1)."""
        findings, _ = lint_paths([PKG])
        assert findings == [], "\n".join(str(f) for f in findings)


# ---------------------------------------------------------------------------
# satellite bugfix regressions
# ---------------------------------------------------------------------------

class TestResolveImportableFnNoExec:
    def test_main_script_resolved_without_reexecution(
            self, tmp_path, monkeypatch):
        from transmogrifai_tpu.workflow.persistence import \
            resolve_importable_fn
        marker = tmp_path / "executed.marker"
        script = tmp_path / "myscript77.py"
        script.write_text(
            "import pathlib\n"
            f"pathlib.Path({str(marker)!r}).write_text('boom')\n"
            "def extract(r):\n    return r.get('x')\n")
        monkeypatch.syspath_prepend(str(tmp_path))

        def extract(r):
            return r.get("x")
        extract.__module__ = "__main__"
        extract.__qualname__ = "extract"
        import types
        fake_main = types.ModuleType("__main__")
        fake_main.__file__ = str(script)
        monkeypatch.setitem(sys.modules, "__main__", fake_main)

        assert resolve_importable_fn(extract) == "myscript77:extract"
        # find_spec-based resolution must NOT run the script's top level
        assert not marker.exists()

    def test_stem_resolving_elsewhere_is_dropped(
            self, tmp_path, monkeypatch):
        from transmogrifai_tpu.workflow.persistence import \
            resolve_importable_fn
        # __main__ claims to be "json.py" — the stem resolves to the
        # stdlib json, NOT the running script: recording "json:extract"
        # would silently bind a different module's attribute on load
        import types

        def extract(r):
            return r
        extract.__module__ = "__main__"
        extract.__qualname__ = "extract"
        fake_main = types.ModuleType("__main__")
        fake_main.__file__ = str(tmp_path / "json.py")
        monkeypatch.setitem(sys.modules, "__main__", fake_main)
        assert resolve_importable_fn(extract) is None


class TestAsyncDispatchGuard:
    def test_counts_stacked_validation_folds_and_masks(self):
        from transmogrifai_tpu.selector.validator import \
            _async_dispatch_bytes
        X = np.zeros((100, 10))
        masks = np.zeros((5, 100))
        X_val_st = np.zeros((5, 20, 10))
        y_val_st = np.zeros((5, 20))
        total = _async_dispatch_bytes(X, masks, X_val_st, y_val_st)
        assert total == (X.nbytes + masks.nbytes + X_val_st.nbytes
                         + y_val_st.nbytes)
        # the old guard looked at X alone — the under-estimate the fix
        # closes is exactly the masks + stacked-fold contribution
        assert total > X.nbytes

    def test_no_stacked_folds(self):
        from transmogrifai_tpu.selector.validator import \
            _async_dispatch_bytes
        X = np.zeros((10, 4))
        masks = np.zeros((3, 10))
        assert _async_dispatch_bytes(X, masks, None, None) == \
            X.nbytes + masks.nbytes


class TestR01ExceptionSwallow:
    """TX-R01: broad excepts in selector/serving hot paths must
    re-raise, quarantine or record a fallback (docs/lint.md)."""

    SEL = "transmogrifai_tpu/selector/myvalidator.py"

    def _lint(self, code, path=None):
        return lint_source(textwrap.dedent(code), path or self.SEL)

    def test_swallowing_except_exception_flagged(self):
        findings = self._lint("""
            def dispatch(thunk):
                try:
                    return thunk()
                except Exception:
                    return None
        """)
        assert "TX-R01" in _rules(findings)
        f = [x for x in findings if x.rule_id == "TX-R01"][0]
        assert f.severity == "error"
        assert "quarantine" in (f.hint or "")

    def test_bare_except_flagged(self):
        findings = self._lint("""
            def dispatch(thunk):
                try:
                    return thunk()
                except:
                    pass
        """)
        assert "TX-R01" in _rules(findings)

    def test_reraise_is_clean(self):
        findings = self._lint("""
            def dispatch(thunk):
                try:
                    return thunk()
                except Exception as e:
                    if classify_error(e) == "bug":
                        raise
                    return None
        """)
        assert "TX-R01" not in _rules(findings)

    def test_quarantine_routing_is_clean(self):
        findings = self._lint("""
            def dispatch(ctx, name, thunk):
                try:
                    return thunk()
                except Exception as e:
                    ctx.quarantine(name, str(e))
                    return None
        """)
        assert "TX-R01" not in _rules(findings)

    def test_recorded_fallback_is_clean(self):
        findings = self._lint("""
            def encode(stage, col):
                try:
                    return stage.encode(col)
                except Exception as e:
                    reason = _fallback_reason("encode", e)
                    return reason
        """, path="transmogrifai_tpu/serving/myplan.py")
        assert "TX-R01" not in _rules(findings)

    def test_narrow_except_is_clean(self):
        findings = self._lint("""
            def dispatch(thunk):
                try:
                    return thunk()
                except (ValueError, FloatingPointError):
                    return None
        """)
        assert "TX-R01" not in _rules(findings)

    def test_outside_hot_paths_is_silent(self):
        findings = self._lint("""
            def handler(fn):
                try:
                    fn()
                except Exception:
                    pass
        """, path="transmogrifai_tpu/utils/mylistener.py")
        assert "TX-R01" not in _rules(findings)


class TestR02SilentRecordDrop:
    """TX-R02: serving-path code must not drop records on exception
    without recording a reason (docs/serving_guardrails.md)."""

    SRV = "transmogrifai_tpu/serving/myguard.py"

    def _lint(self, code, path=None):
        return lint_source(textwrap.dedent(code), path or self.SRV)

    def test_silent_continue_flagged(self):
        findings = self._lint("""
            def score_all(records, fn):
                out = []
                for r in records:
                    try:
                        out.append(fn(r))
                    except ValueError:
                        continue
                return out
        """)
        assert "TX-R02" in _rules(findings)
        f = [x for x in findings if x.rule_id == "TX-R02"][0]
        assert f.severity == "error"
        assert "quarantine" in (f.hint or "")

    def test_silent_pass_in_loop_flagged(self):
        findings = self._lint("""
            def score_all(records, fn):
                out = []
                for r in records:
                    try:
                        out.append(fn(r))
                    except Exception:
                        pass
                return out
        """)
        assert "TX-R02" in _rules(findings)

    def test_recorded_drop_is_clean(self):
        findings = self._lint("""
            def score_all(records, fn, reasons):
                out = []
                for i, r in enumerate(records):
                    try:
                        out.append(fn(r))
                    except ValueError as e:
                        reasons.append(quarantine_reason(i, e))
                        continue
                return out
        """)
        assert "TX-R02" not in _rules(findings)

    def test_counted_drop_is_clean(self):
        findings = self._lint("""
            def score_all(records, fn, telemetry):
                out = []
                for r in records:
                    try:
                        out.append(fn(r))
                    except ValueError:
                        telemetry.count("rows_dropped")
                        continue
                return out
        """)
        assert "TX-R02" not in _rules(findings)

    def test_logged_drop_is_clean(self):
        findings = self._lint("""
            def score_all(records, fn, log):
                out = []
                for r in records:
                    try:
                        out.append(fn(r))
                    except ValueError:
                        log.warning("dropping record")
                        continue
                return out
        """)
        assert "TX-R02" not in _rules(findings)

    def test_local_scoring_is_in_scope(self):
        findings = self._lint("""
            def extract(records, fn):
                vals = []
                for r in records:
                    try:
                        vals.append(fn(r))
                    except Exception:
                        continue
                return vals
        """, path="transmogrifai_tpu/local/scoring.py")
        assert "TX-R02" in _rules(findings)

    def test_pass_outside_loop_is_silent(self):
        # a pass-only handler NOT in a loop drops no record
        findings = self._lint("""
            def warm_cache():
                try:
                    enable_cache()
                except (OSError, RuntimeError):
                    pass
        """)
        assert "TX-R02" not in _rules(findings)

    def test_outside_serving_paths_is_silent(self):
        findings = self._lint("""
            def drain(batches, fn):
                for b in batches:
                    try:
                        fn(b)
                    except Exception:
                        continue
        """, path="transmogrifai_tpu/utils/mydrain.py")
        assert "TX-R02" not in _rules(findings)


class TestR03LiveSwapMutation:
    """TX-R03: serving-path code must not mutate a live PlanCache entry
    or plan registry in place — hot model changes go through the atomic
    swap_entry/rollback/commit helpers (docs/self_healing.md)."""

    SRV = "transmogrifai_tpu/serving/mylifecycle.py"

    def _lint(self, code, path=None):
        return lint_source(textwrap.dedent(code), path or self.SRV)

    def test_entry_attribute_store_flagged(self):
        findings = self._lint("""
            def hot_patch(cache, name, new_plan):
                entry = cache.get(name)
                entry.plan = new_plan
        """)
        assert "TX-R03" in _rules(findings)
        f = [x for x in findings if x.rule_id == "TX-R03"][0]
        assert f.severity == "error"
        assert "swap_entry" in (f.hint or "")

    def test_entry_model_store_flagged(self):
        findings = self._lint("""
            def hot_patch(entry, candidate):
                entry.model = candidate
        """)
        assert "TX-R03" in _rules(findings)

    def test_registry_subscript_store_flagged(self):
        findings = self._lint("""
            def hot_patch(cache, key, entry):
                cache._entries[key] = entry
        """)
        assert "TX-R03" in _rules(findings)

    def test_registry_subscript_delete_flagged(self):
        findings = self._lint("""
            def evict(cache, key):
                del cache._overrides[key]
        """)
        assert "TX-R03" in _rules(findings)

    def test_self_stores_are_legal(self):
        # the owning object's own methods (PlanCache itself, entry
        # construction) are the blessed implementation
        findings = self._lint("""
            class PlanCache:
                def swap_entry(self, key, entry):
                    self._entries[key] = entry

                def _set(self, plan):
                    self.plan = plan
        """)
        assert "TX-R03" not in _rules(findings)

    def test_atomic_helper_call_is_legal(self):
        findings = self._lint("""
            def heal(server, name, entry, tenant):
                server.plans.swap_entry(name, entry, tenant=tenant)
        """)
        assert "TX-R03" not in _rules(findings)

    def test_outside_serving_is_silent(self):
        findings = self._lint("""
            def rebuild(cache, key, entry):
                cache._entries[key] = entry
                entry.plan = None
        """, path="transmogrifai_tpu/selector/journal.py")
        assert "TX-R03" not in _rules(findings)

    def test_inline_suppression(self, tmp_path):
        # suppression is applied by the engine on real files; the path
        # must have a "serving" segment for the rule to arm at all
        d = tmp_path / "serving"
        d.mkdir()
        p = d / "patch.py"
        p.write_text("def hot_patch(entry, new_plan):\n"
                     "    entry.plan = new_plan"
                     "  # tx-lint: disable=TX-R03\n")
        findings, _ = lint_paths([str(p)])
        assert findings == []


class TestR04TornStateWrite:
    """TX-R04: serving-path state files must be written through the
    shared atomic tmp+os.replace writer (atomic_write_json) — a bare
    write-mode open() to a live path tears the document when the
    process dies mid-write (docs/serving_restart.md)."""

    SRV = "transmogrifai_tpu/serving/mystate.py"

    def _lint(self, code, path=None):
        return lint_source(textwrap.dedent(code), path or self.SRV)

    def test_live_path_write_flagged(self):
        findings = self._lint("""
            import json

            def save(path, doc):
                with open(path, "w") as fh:
                    json.dump(doc, fh)
        """)
        assert "TX-R04" in _rules(findings)
        f = [x for x in findings if x.rule_id == "TX-R04"][0]
        assert f.severity == "error"
        assert "atomic_write_json" in (f.hint or "")

    def test_mode_keyword_flagged(self):
        findings = self._lint("""
            def save(path, text):
                fh = open(path, mode="a")
                fh.write(text)
        """)
        assert "TX-R04" in _rules(findings)

    def test_exclusive_create_flagged(self):
        findings = self._lint("""
            def save(path, text):
                with open(path, "x") as fh:
                    fh.write(text)
        """)
        assert "TX-R04" in _rules(findings)

    def test_tmp_suffix_concat_is_legal(self):
        # the atomic-writer idiom itself: stage to *.tmp, os.replace
        findings = self._lint("""
            import json, os

            def save(path, doc):
                tmp = path + ".tmp"
                with open(tmp, "w") as fh:
                    json.dump(doc, fh)
                os.replace(tmp, path)
        """)
        assert "TX-R04" not in _rules(findings)

    def test_tmp_string_expression_is_legal(self):
        findings = self._lint("""
            def save(path, text):
                with open(path + ".tmp", "w") as fh:
                    fh.write(text)
        """)
        assert "TX-R04" not in _rules(findings)

    def test_read_mode_is_legal(self):
        findings = self._lint("""
            import json

            def load(path):
                with open(path) as fh:
                    return json.load(fh)

            def load_binary(path):
                with open(path, "rb") as fh:
                    return fh.read()
        """)
        assert "TX-R04" not in _rules(findings)

    def test_outside_serving_is_silent(self):
        findings = self._lint("""
            def save(path, text):
                with open(path, "w") as fh:
                    fh.write(text)
        """, path="transmogrifai_tpu/observability/mystore.py")
        assert "TX-R04" not in _rules(findings)

    def test_async_write_reports_both_rules(self):
        # in an async handler the same open() is also a blocking call
        # (TX-J10); the two findings are different defects
        findings = self._lint("""
            async def flush(path, text):
                with open(path, "w") as fh:
                    fh.write(text)
        """)
        assert {"TX-R04", "TX-J10"} <= _rules(findings)

    def test_inline_suppression(self, tmp_path):
        d = tmp_path / "serving"
        d.mkdir()
        p = d / "writer.py"
        p.write_text("def save(path, text):\n"
                     "    fh = open(path, 'w')"
                     "  # tx-lint: disable=TX-R04\n"
                     "    fh.write(text)\n")
        findings, _ = lint_paths([str(p)])
        assert findings == []


class TestR05UnboundedQueue:
    """TX-R05: a bare deque()/asyncio.Queue() bound to a request-queue
    name in serving/ grows without limit under overload — queues must
    be bounded and overflow shed at the admission edge
    (docs/admission.md)."""

    SRV = "transmogrifai_tpu/serving/myqueue.py"

    def _lint(self, code, path=None):
        return lint_source(textwrap.dedent(code), path or self.SRV)

    def test_bare_deque_flagged(self):
        findings = self._lint("""
            import collections

            class Lane:
                def __init__(self):
                    self.queue = collections.deque()
        """)
        assert "TX-R05" in _rules(findings)
        f = [x for x in findings if x.rule_id == "TX-R05"][0]
        assert f.severity == "error"
        assert "admission edge" in (f.hint or "")

    def test_bare_asyncio_queue_flagged(self):
        findings = self._lint("""
            import asyncio

            def make_backlog():
                backlog = asyncio.Queue()
                return backlog
        """)
        assert "TX-R05" in _rules(findings)

    def test_annotated_assign_flagged(self):
        findings = self._lint("""
            from collections import deque

            class Lane:
                def __init__(self):
                    self.pending: deque = deque()
        """)
        assert "TX-R05" in _rules(findings)

    def test_explicit_unbounded_values_flagged(self):
        # maxlen=None and maxsize=0 are the unbounded spellings
        findings = self._lint("""
            import asyncio, collections

            def build():
                queue = collections.deque(maxlen=None)
                pending = asyncio.Queue(maxsize=0)
                return queue, pending
        """)
        assert len([f for f in findings
                    if f.rule_id == "TX-R05"]) == 2

    def test_bounded_constructions_legal(self):
        findings = self._lint("""
            import asyncio, collections

            class Lane:
                def __init__(self, limit):
                    self.queue = collections.deque(maxlen=limit)
                    self.backlog = asyncio.Queue(maxsize=64)
                    self.pending = collections.deque([], 128)
        """)
        assert "TX-R05" not in _rules(findings)

    def test_non_queue_names_legal(self):
        # a deque used as a scratch buffer is not a request queue
        findings = self._lint("""
            import collections

            def window(xs):
                recent = collections.deque()
                for x in xs:
                    recent.append(x)
                return list(recent)
        """)
        assert "TX-R05" not in _rules(findings)

    def test_outside_serving_is_silent(self):
        findings = self._lint("""
            import collections

            class Worker:
                def __init__(self):
                    self.queue = collections.deque()
        """, path="transmogrifai_tpu/selector/pool.py")
        assert "TX-R05" not in _rules(findings)

    def test_inline_suppression(self, tmp_path):
        d = tmp_path / "serving"
        d.mkdir()
        p = d / "lanes.py"
        p.write_text("import collections\n"
                     "queue = collections.deque()"
                     "  # tx-lint: disable=TX-R05\n")
        findings, _ = lint_paths([str(p)])
        assert findings == []


class TestR06ArtifactBypass:
    """TX-R06: serving/ and cli/ code must build compiled plans through
    artifacts.loader.load_or_compile — a direct
    ``ScoringPlan(...).compile()`` ignores a saved model's exported AOT
    executables and pays a cold in-band XLA compile per bucket
    (docs/aot_artifacts.md)."""

    SRV = "transmogrifai_tpu/serving/myserver.py"

    def _lint(self, code, path=None):
        return lint_source(textwrap.dedent(code), path or self.SRV)

    def test_chained_compile_flagged(self):
        findings = self._lint("""
            from .plan import ScoringPlan

            def build(model):
                return ScoringPlan(model).compile()
        """)
        assert "TX-R06" in _rules(findings)
        f = [x for x in findings if x.rule_id == "TX-R06"][0]
        assert f.severity == "error"
        assert "load_or_compile" in (f.hint or "")

    def test_qualified_ctor_flagged(self):
        findings = self._lint("""
            from . import plan as planmod

            def build(model, buckets):
                return planmod.ScoringPlan(
                    model, min_bucket=buckets[0]).compile()
        """)
        assert "TX-R06" in _rules(findings)

    def test_cli_path_flagged(self):
        findings = self._lint("""
            from ..serving import ScoringPlan

            def run_score(args, model):
                plan = ScoringPlan(model).compile()
                return plan
        """, path="transmogrifai_tpu/cli/myscore.py")
        assert "TX-R06" in _rules(findings)

    def test_load_or_compile_legal(self):
        findings = self._lint("""
            from ..artifacts.loader import load_or_compile

            def build(model):
                return load_or_compile(model)
        """)
        assert "TX-R06" not in _rules(findings)

    def test_uncompiled_construction_legal(self):
        # building a plan without .compile() (bucket introspection)
        # is not a bypass — nothing compiles
        findings = self._lint("""
            from .plan import ScoringPlan

            def ladder(model):
                return ScoringPlan(model).buckets()
        """)
        assert "TX-R06" not in _rules(findings)

    def test_outside_serving_and_cli_is_silent(self):
        # the loader itself (artifacts/) and tests build plans directly
        findings = self._lint("""
            from ..serving.plan import ScoringPlan

            def load_or_compile(model):
                return ScoringPlan(model).compile()
        """, path="transmogrifai_tpu/artifacts/myloader.py")
        assert "TX-R06" not in _rules(findings)

    def test_inline_suppression(self, tmp_path):
        d = tmp_path / "serving"
        d.mkdir()
        p = d / "boot.py"
        p.write_text(
            "from .plan import ScoringPlan\n"
            "def build(model):\n"
            "    return ScoringPlan(model).compile()"
            "  # tx-lint: disable=TX-R06\n")
        findings, _ = lint_paths([str(p)])
        assert findings == []


class TestR07LeakedWriter:
    """TX-R07: a socket/stream writer stored in a dict-like container
    in serving/ with no removal path anywhere in the module leaks one
    entry (and one fd) per client disconnect — the router's
    ``finally: writers.pop(key, None)`` is the required shape."""

    SRV = "transmogrifai_tpu/serving/frontend.py"

    def _lint(self, code, path=None):
        return lint_source(textwrap.dedent(code), path or self.SRV)

    def test_writer_store_without_cleanup_flagged(self):
        findings = self._lint("""
            class Frontend:
                def __init__(self):
                    self._writers = {}

                async def handle(self, reader, writer):
                    key = id(writer)
                    self._writers[key] = writer
                    while True:
                        line = await reader.readline()
                        if not line:
                            break
        """)
        assert "TX-R07" in _rules(findings)
        f = [x for x in findings if x.rule_id == "TX-R07"][0]
        assert f.severity == "error"
        assert "pop" in (f.hint or "")

    def test_sock_and_conn_names_flagged(self):
        findings = self._lint("""
            def track(table, registry, sock, conn):
                table[1] = sock
                registry["a"] = conn
        """)
        assert len([f for f in findings
                    if f.rule_id == "TX-R07"]) == 2

    def test_pop_in_finally_is_clean(self):
        # the reference shape: handler's finally evicts the entry
        findings = self._lint("""
            class Frontend:
                def __init__(self):
                    self._writers = {}

                async def handle(self, reader, writer):
                    key = id(writer)
                    self._writers[key] = writer
                    try:
                        await reader.readline()
                    finally:
                        self._writers.pop(key, None)
        """)
        assert "TX-R07" not in _rules(findings)

    def test_cleanup_in_other_method_counts(self):
        # the verdict is module-wide: a disconnect method that dels
        # the entry is a removal path even though the store is
        # elsewhere
        findings = self._lint("""
            class Frontend:
                def __init__(self):
                    self.conns = {}

                def attach(self, key, conn):
                    self.conns[key] = conn

                def detach(self, key):
                    del self.conns[key]
        """)
        assert "TX-R07" not in _rules(findings)

    def test_non_connection_values_legal(self):
        findings = self._lint("""
            class Cache:
                def __init__(self):
                    self.results = {}

                def put(self, key, row):
                    self.results[key] = row
        """)
        assert "TX-R07" not in _rules(findings)

    def test_outside_serving_is_silent(self):
        findings = self._lint("""
            def track(table, writer):
                table[1] = writer
        """, path="transmogrifai_tpu/runtime/pool.py")
        assert "TX-R07" not in _rules(findings)

    def test_inline_suppression(self, tmp_path):
        d = tmp_path / "serving"
        d.mkdir()
        p = d / "front.py"
        p.write_text("def track(table, writer):\n"
                     "    table[1] = writer"
                     "  # tx-lint: disable=TX-R07\n")
        findings, _ = lint_paths([str(p)])
        assert findings == []


class TestJ08ShardClosure:
    """TX-J08: a shard_map/pjit body closing over an array-like value
    gets implicit full replication — arrays must enter through
    in_specs (docs/lint.md, docs/distributed.md)."""

    def _lint(self, code):
        return lint_source(textwrap.dedent(code),
                           "transmogrifai_tpu/parallel/mykernel.py")

    def test_closed_over_arrays_flagged(self):
        findings = self._lint("""
            import jax
            from jax.sharding import PartitionSpec as P
            from jax import shard_map

            def builder(mesh, X, y):
                def body(w_loc):
                    return (w_loc * y) @ X
                return jax.jit(shard_map(
                    body, mesh=mesh, in_specs=(P("models"),),
                    out_specs=P("models")))
        """)
        flagged = [f for f in findings if f.rule_id == "TX-J08"]
        assert len(flagged) == 2
        assert flagged[0].severity == "warning"
        assert "in_specs" in (flagged[0].hint or "")

    def test_lambda_body_flagged(self):
        findings = self._lint("""
            import jax
            from jax.sharding import PartitionSpec as P
            from jax import shard_map

            def builder(mesh, masks):
                return jax.jit(shard_map(
                    lambda w: w * masks, mesh=mesh,
                    in_specs=(P("models"),), out_specs=P("models")))
        """)
        assert "TX-J08" in _rules(findings)

    def test_arrays_through_in_specs_clean(self):
        findings = self._lint("""
            import jax
            from jax.sharding import PartitionSpec as P
            from jax import shard_map

            def builder(cfg, spec, mesh):
                data_ax = "data" if "data" in mesh.axis_names else None

                def body(w_loc, X_loc, y_loc):
                    return fit(cfg, w_loc, X_loc, y_loc,
                               axis_name=data_ax)
                return jax.jit(shard_map(
                    body, mesh=mesh,
                    in_specs=(P("models"), P(data_ax), P(data_ax)),
                    out_specs=P("models")))
        """)
        assert "TX-J08" not in _rules(findings)

    def test_config_closures_clean(self):
        """Kernel config (cfg/spec/statics/axis names/module CONSTANTS)
        closes over shard bodies legitimately throughout the repo."""
        findings = self._lint("""
            import jax
            from jax.sharding import PartitionSpec as P
            from jax import shard_map

            MAX_ITER = 100

            def builder(statics, spec, mesh):
                def body(w_loc):
                    return kernel(statics, spec, w_loc, MAX_ITER)
                return jax.jit(shard_map(
                    body, mesh=mesh, in_specs=(P("models"),),
                    out_specs=P("models")))
        """)
        assert "TX-J08" not in _rules(findings)

    def test_single_capital_x_is_data_not_constant(self):
        findings = self._lint("""
            import jax
            from jax.sharding import PartitionSpec as P
            from jax import shard_map

            def builder(mesh, X):
                def body(w_loc):
                    return w_loc @ X
                return jax.jit(shard_map(
                    body, mesh=mesh, in_specs=(P("models"),),
                    out_specs=P("models")))
        """)
        assert "TX-J08" in _rules(findings)


class TestT01TunableKnobFork:
    """TX-T01: a numeric literal default for a registered tunable knob
    outside ``tuning/`` forks the knob away from the autotuning
    registry (tuning/registry.py STATIC_DEFAULTS) — the policy and
    ``tx tune`` overrides would govern one copy while the literal
    silently rules the hot path (docs/autotuning.md, docs/lint.md)."""

    def test_const_literal_flagged_in_consumer(self):
        findings = lint_source(
            "_DEFAULT_TARGET = 64\n",
            "transmogrifai_tpu/serving/server.py")
        flagged = [f for f in findings if f.rule_id == "TX-T01"]
        assert len(flagged) == 1
        assert flagged[0].severity == "error"
        assert "STATIC_DEFAULTS" in (flagged[0].hint or "")

    def test_annotated_const_literal_flagged(self):
        findings = lint_source(
            "DEFAULT_MIN_BUCKET: int = 8\n",
            "transmogrifai_tpu/plans/common.py")
        assert "TX-T01" in _rules(findings)

    def test_registry_read_is_clean(self):
        findings = lint_source(textwrap.dedent("""
            from ..tuning.registry import STATIC_DEFAULTS as _TUNABLES

            _DEFAULT_TARGET = int(_TUNABLES["serving.target_batch"])
        """), "transmogrifai_tpu/serving/server.py")
        assert "TX-T01" not in _rules(findings)

    def test_literal_inside_tuning_package_is_clean(self):
        findings = lint_source(
            "_DEFAULT_TARGET = 64\n",
            "transmogrifai_tpu/tuning/registry.py")
        assert "TX-T01" not in _rules(findings)

    def test_param_default_flagged_in_consumer_package(self):
        findings = lint_source(textwrap.dedent("""
            def __init__(self, evaluator, eta=3):
                pass
        """), "transmogrifai_tpu/selector/racing.py")
        assert "TX-T01" in _rules(findings)

    def test_kwonly_param_default_flagged(self):
        findings = lint_source(textwrap.dedent("""
            def decide(*, placement_margin=1.5):
                pass
        """), "transmogrifai_tpu/plans/placement.py")
        assert "TX-T01" in _rules(findings)

    def test_none_default_resolving_through_policy_is_clean(self):
        findings = lint_source(textwrap.dedent("""
            def __init__(self, evaluator, eta=None,
                         min_fidelity=None):
                pass
        """), "transmogrifai_tpu/selector/racing.py")
        assert "TX-T01" not in _rules(findings)

    def test_same_spelling_outside_consumer_package_is_clean(self):
        """``eta`` is ALSO the gradient-boosting learning rate — the
        param check is scoped to the knob's consumer layer."""
        findings = lint_source(textwrap.dedent("""
            def __init__(self, eta=0.3, max_depth=6):
                pass
        """), "transmogrifai_tpu/models/trees.py")
        assert "TX-T01" not in _rules(findings)

    def test_local_variable_is_clean(self):
        """Only module/class-level constants fork a default; a local
        named like one is somebody's loop temporary."""
        findings = lint_source(textwrap.dedent("""
            def f():
                _DEFAULT_TARGET = 64
                return _DEFAULT_TARGET
        """), "transmogrifai_tpu/serving/server.py")
        assert "TX-T01" not in _rules(findings)


class TestT02HardcodedPow2BucketMath:
    """TX-T02: hand-rolled power-of-two bucket math in the dispatch
    layers disagrees with a tuned non-power-of-two lattice
    (docs/ragged_batching.md); only plans/common.py and
    tuning/lattice.py may hold that arithmetic."""

    def test_doubling_loop_flagged_in_serving(self):
        findings = lint_source(textwrap.dedent("""
            def grow(n):
                b = 8
                while b < n:
                    b *= 2
                return b
        """), "transmogrifai_tpu/serving/server.py")
        flagged = [f for f in findings if f.rule_id == "TX-T02"]
        assert len(flagged) == 1
        assert flagged[0].severity == "error"
        assert "bucket_for" in (flagged[0].hint or "")

    def test_shift_and_pow_with_computed_exponent_flagged(self):
        findings = lint_source(textwrap.dedent("""
            def rungs(k):
                return [1 << i for i in range(k)], 2 ** k
        """), "transmogrifai_tpu/plans/prepare.py")
        assert len([f for f in findings
                    if f.rule_id == "TX-T02"]) == 2

    def test_literal_exponent_is_clean(self):
        # `2 ** 30` is a plain size constant, not a derived ladder
        findings = lint_source(
            "GIB = 2 ** 30\nPAGE = 1 << 12\n",
            "transmogrifai_tpu/serving/server.py")
        assert "TX-T02" not in _rules(findings)

    def test_exempt_files_are_clean(self):
        src = textwrap.dedent("""
            def grow(n):
                b = 8
                while b < n:
                    b *= 2
                return 1 << n
        """)
        for path in ("transmogrifai_tpu/plans/common.py",
                     "transmogrifai_tpu/tuning/lattice.py"):
            assert "TX-T02" not in _rules(lint_source(src, path))

    def test_outside_bucket_layers_is_clean(self):
        # models/ heap math doubles freely — out of TX-T02 scope
        findings = lint_source(textwrap.dedent("""
            def heap(depth):
                return 2 ** depth - 1
        """), "transmogrifai_tpu/models/trees.py")
        assert "TX-T02" not in _rules(findings)


# ---------------------------------------------------------------------------
# cross-procedure rules (TX-X01..TX-X04) — whole-program call graph
# ---------------------------------------------------------------------------

def _write_tree(root, files):
    """Write {relpath: source} under root, return [str(root)]."""
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return [str(root)]


def _xlint(root, **kw):
    kw.setdefault("cache_path", "")  # isolated: no incremental cache
    findings, _ = lint_paths([str(root)], **kw)
    return findings


class TestX01BlockingReachableFromHandler:
    def test_two_level_sync_chain_fires_with_full_chain(self, tmp_path):
        _write_tree(tmp_path, {"serving/handler.py": """
            import time

            def slow_io():
                time.sleep(0.5)

            def helper(req):
                slow_io()
                return req

            async def handle(req):
                return helper(req)
        """})
        x = [f for f in _xlint(tmp_path) if f.rule_id == "TX-X01"]
        assert len(x) == 1
        f = x[0]
        # anchored at the violating call site in the leaf helper
        assert f.path.endswith("handler.py") and f.line == 5
        assert "sleep" in f.message and "handle" in f.message
        # chain: handler entry point first, violating site last
        assert len(f.chain) == 4
        assert "async" in f.chain[0] and "handle" in f.chain[0]
        assert "helper" in f.chain[1]
        assert "slow_io" in f.chain[2]
        assert "sleep" in f.chain[3]
        # rendering carries the chain
        text = str(f)
        assert "via " in text and "-> " in text

    def test_executor_route_and_awaited_sleep_are_clean(self, tmp_path):
        _write_tree(tmp_path, {"serving/handler.py": """
            import asyncio
            import time

            def slow_io():
                time.sleep(0.5)

            async def handle(req, loop):
                await asyncio.sleep(0.01)
                await loop.run_in_executor(None, slow_io)
                return req
        """})
        assert _rules(_xlint(tmp_path)) == set()

    def test_direct_site_left_to_local_rule(self, tmp_path):
        # chain length 1 == TX-J10 territory, not TX-X01's
        _write_tree(tmp_path, {"pkg/helper.py": """
            import time

            def helper(req):
                time.sleep(0.5)
        """})
        assert "TX-X01" not in _rules(_xlint(tmp_path))

    def test_inline_suppression_at_leaf_site(self, tmp_path):
        _write_tree(tmp_path, {"serving/handler.py": """
            import time

            def slow_io():
                time.sleep(0.5)  # tx-lint: disable=TX-X01

            def helper(req):
                slow_io()

            async def handle(req):
                return helper(req)
        """})
        assert "TX-X01" not in _rules(_xlint(tmp_path))


class TestX02HostcallReachableFromJit:
    def test_clock_two_calls_from_jitted_body(self, tmp_path):
        _write_tree(tmp_path, {"pkg/kern.py": """
            import time

            import jax

            def record(y):
                t = time.perf_counter()
                return t

            def probe(y):
                return record(y)

            @jax.jit
            def kernel(x):
                probe(x)
                return x * 2
        """})
        x = [f for f in _xlint(tmp_path) if f.rule_id == "TX-X02"]
        assert len(x) == 1
        f = x[0]
        assert "time.perf_counter" in f.message
        assert "kernel" in f.message and "TRACE" in f.message
        assert "kernel" in f.chain[0] and "probe" in f.chain[1]
        assert "record" in f.chain[2]

    def test_blessed_compile_time_section_stops_traversal(self, tmp_path):
        # the deliberate trace-cost probe (TX-O01's carve-out) must not
        # be re-flagged interprocedurally
        _write_tree(tmp_path, {
            "proj/__init__.py": "",
            "proj/utils/__init__.py": "",
            "proj/utils/compile_time.py": """
                import time

                def section(label):
                    return time.perf_counter()
            """,
            "proj/kern.py": """
                import jax

                from proj.utils import compile_time

                @jax.jit
                def kernel(x):
                    compile_time.section("k")
                    return x
            """})
        assert "TX-X02" not in _rules(_xlint(tmp_path))

    def test_jitted_callee_not_doubly_reported(self, tmp_path):
        _write_tree(tmp_path, {"pkg/kern.py": """
            import time

            import jax

            @jax.jit
            def inner(x):
                t = time.time()
                return x

            @jax.jit
            def outer(x):
                return inner(x)
        """})
        # inner's direct site is TX-O01's; no TX-X02 via outer->inner
        assert "TX-X02" not in _rules(_xlint(tmp_path))


class TestX03EventLoopThreadRace:
    def test_unguarded_write_from_both_contexts(self, tmp_path):
        _write_tree(tmp_path, {"serving/worker.py": """
            class Server:
                def __init__(self):
                    self._plan = None

                def _rebuild(self):
                    self._plan = object()

                def _work(self):
                    self._rebuild()

                def _refresh(self):
                    self._plan = None

                async def _tick(self):
                    self._refresh()

                async def start(self, loop):
                    await loop.run_in_executor(None, self._work)
                    await self._tick()
        """})
        x = [f for f in _xlint(tmp_path) if f.rule_id == "TX-X03"]
        assert len(x) == 1
        f = x[0]
        assert "Server._plan" in f.message
        assert "event-loop" in f.message and "executor-thread" in f.message
        # BOTH chains present, each >= 2 calls deep
        assert "[event-loop path]" in f.chain
        assert "[executor-thread path]" in f.chain
        li = f.chain.index("[event-loop path]")
        ti = f.chain.index("[executor-thread path]")
        loop_frames = f.chain[li + 1:ti]
        thread_frames = f.chain[ti + 1:]
        assert len(loop_frames) >= 3  # start -> _tick -> _refresh -> write
        assert len(thread_frames) >= 2  # _work -> _rebuild -> write
        assert any("_refresh" in fr for fr in loop_frames)
        assert any("_rebuild" in fr for fr in thread_frames)

    def test_lock_guard_on_both_sides_is_clean(self, tmp_path):
        _write_tree(tmp_path, {"serving/worker.py": """
            import threading

            class Server:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._plan = None

                def _work(self):
                    with self._lock:
                        self._plan = object()

                async def start(self, loop):
                    await loop.run_in_executor(None, self._work)
                    with self._lock:
                        self._plan = None
        """})
        assert "TX-X03" not in _rules(_xlint(tmp_path))

    def test_call_soon_threadsafe_marshalling_is_clean(self, tmp_path):
        # the thread never writes directly: it marshals the write back
        # onto the loop, so both writes happen in loop context
        _write_tree(tmp_path, {"serving/worker.py": """
            class Server:
                def __init__(self, loop):
                    self._loop = loop
                    self._plan = None

                def _apply(self, plan):
                    self._plan = plan

                def _work(self):
                    plan = object()
                    self._loop.call_soon_threadsafe(self._apply, plan)

                async def start(self, loop):
                    await loop.run_in_executor(None, self._work)
                    self._plan = None
        """})
        assert "TX-X03" not in _rules(_xlint(tmp_path))

    def test_non_serving_class_out_of_scope(self, tmp_path):
        _write_tree(tmp_path, {"pkg/worker.py": """
            class Server:
                def _work(self):
                    self._plan = object()

                async def start(self, loop):
                    await loop.run_in_executor(None, self._work)
                    self._plan = None
        """})
        assert "TX-X03" not in _rules(_xlint(tmp_path))


class TestX04TornPersistWrite:
    def test_raw_open_two_calls_from_snapshot_entry(self, tmp_path):
        _write_tree(tmp_path, {"pkg/state.py": """
            import json

            def _emit(path, doc):
                with open(path, "w") as fh:
                    json.dump(doc, fh)

            def _store(path, doc):
                _emit(path, doc)

            def snapshot_state(path, doc):
                _store(path, doc)
        """})
        x = [f for f in _xlint(tmp_path) if f.rule_id == "TX-X04"]
        assert len(x) == 1
        f = x[0]
        assert "snapshot_state" in f.message and "'w'" in f.message
        assert "TORN" in f.message
        assert "snapshot_state" in f.chain[0]
        assert "_store" in f.chain[1] and "_emit" in f.chain[2]

    def test_tmp_staged_write_is_clean(self, tmp_path):
        _write_tree(tmp_path, {"pkg/state.py": """
            import json
            import os

            def _emit(path, doc):
                tmp = path + ".tmp"
                with open(tmp, "w") as fh:
                    json.dump(doc, fh)
                os.replace(tmp, path)

            def snapshot_state(path, doc):
                _emit(path, doc)
        """})
        assert "TX-X04" not in _rules(_xlint(tmp_path))

    def test_atomic_write_json_sink_stops_traversal(self, tmp_path):
        # the blessed writer itself is the fix — never re-flagged
        # through a persistence entry point
        _write_tree(tmp_path, {"pkg/state.py": """
            import json
            import os

            def atomic_write_json(path, doc):
                live = path + ".live"
                with open(live, "w") as fh:
                    json.dump(doc, fh)

            def snapshot_state(path, doc):
                atomic_write_json(path, doc)
        """})
        assert "TX-X04" not in _rules(_xlint(tmp_path))

    def test_read_mode_open_is_clean(self, tmp_path):
        _write_tree(tmp_path, {"pkg/state.py": """
            import json

            def _load(path):
                with open(path) as fh:
                    return json.load(fh)

            def snapshot_state(path):
                return _load(path)
        """})
        assert "TX-X04" not in _rules(_xlint(tmp_path))


class TestChangedScopeFilter:
    """--changed restricts REPORTING, not analysis: a cross-procedure
    finding surfaces when any frame of its chain touches a changed
    file."""

    FILES = {
        "serving/handler.py": """
            from pkg.helper import helper

            async def handle(req):
                return helper(req)
        """,
        "pkg/__init__.py": "",
        "pkg/helper.py": """
            import time

            def helper(req):
                time.sleep(0.5)
                return req
        """,
        "pkg/unrelated.py": """
            def other():
                return 1
        """,
    }

    def test_chain_touching_changed_file_is_reported(self, tmp_path):
        _write_tree(tmp_path, self.FILES)
        changed = [str(tmp_path / "pkg" / "helper.py")]
        findings = _xlint(tmp_path, changed=changed)
        assert "TX-X01" in _rules(findings)

    def test_untouched_chain_is_filtered_out(self, tmp_path):
        _write_tree(tmp_path, self.FILES)
        changed = [str(tmp_path / "pkg" / "unrelated.py")]
        findings = _xlint(tmp_path, changed=changed)
        assert findings == []

    def test_empty_changed_list_reports_nothing(self, tmp_path):
        _write_tree(tmp_path, self.FILES)
        assert _xlint(tmp_path, changed=[]) == []


# ---------------------------------------------------------------------------
# LintFinding JSON round trip (chain field)
# ---------------------------------------------------------------------------

class TestFindingJsonRoundTrip:
    def test_chain_round_trips(self):
        from transmogrifai_tpu.lint import LintFinding
        f = LintFinding(
            rule_id="TX-X01", message="m", severity="error",
            path="serving/handler.py", line=5, hint="h",
            chain=("async a.handle (serving/handler.py:9)",
                   "a.helper (serving/handler.py:7)",
                   "time.sleep (serving/handler.py:5)"))
        doc = f.to_json()
        assert doc["chain"] == list(f.chain)
        assert LintFinding.from_json(doc) == f

    def test_no_chain_key_when_empty(self):
        from transmogrifai_tpu.lint import LintFinding
        f = LintFinding(rule_id="TX-J01", message="m",
                        path="a.py", line=3)
        doc = f.to_json()
        assert "chain" not in doc  # unchanged document for consumers
        assert LintFinding.from_json(doc) == f

    def test_json_survives_serialization(self):
        import json as _json
        from transmogrifai_tpu.lint import LintFinding
        f = LintFinding(rule_id="TX-X03", message="race",
                        path="serving/w.py", line=2,
                        chain=("[event-loop path]", "x", "y"))
        wire = _json.dumps(f.to_json())
        assert LintFinding.from_json(_json.loads(wire)) == f

    def test_format_json_carries_chain_and_is_stable(self, tmp_path):
        from transmogrifai_tpu.lint import format_json
        _write_tree(tmp_path, {"serving/handler.py": """
            import time

            def slow_io():
                time.sleep(0.5)

            def helper(req):
                slow_io()

            async def handle(req):
                return helper(req)
        """})
        a = format_json(_xlint(tmp_path))
        b = format_json(_xlint(tmp_path))
        assert a == b  # deterministic ordering across runs
        import json as _json
        doc = _json.loads(a)
        x01 = [d for d in doc["findings"] if d["rule"] == "TX-X01"]
        assert x01 and len(x01[0]["chain"]) == 4

    def test_cross_procedure_findings_sorted(self, tmp_path):
        # rule id, then path, then line — stable under dict-order noise
        _write_tree(tmp_path, {
            "serving/b_handler.py": """
                import time

                def slow():
                    time.sleep(1)

                def mid():
                    slow()

                async def handle(req):
                    mid()
            """,
            "pkg/state.py": """
                def _emit(path):
                    with open(path, "w") as fh:
                        fh.write("x")

                def _store(path):
                    _emit(path)

                def snapshot_state(path):
                    _store(path)
            """})
        findings = [f for f in _xlint(tmp_path)
                    if f.rule_id.startswith("TX-X")]
        keys = [(f.rule_id, f.path, f.line) for f in findings]
        assert keys == sorted(keys)


# ---------------------------------------------------------------------------
# iter_py_files edge cases + incremental cache
# ---------------------------------------------------------------------------

class TestIterPyFiles:
    def test_symlink_loop_terminates_and_dedups(self, tmp_path):
        from transmogrifai_tpu.lint.engine import iter_py_files
        (tmp_path / "a" / "b").mkdir(parents=True)
        (tmp_path / "a" / "x.py").write_text("x = 1\n")
        (tmp_path / "a" / "b" / "y.py").write_text("y = 1\n")
        os.symlink(str(tmp_path / "a"), str(tmp_path / "a" / "b" / "loop"))
        files = iter_py_files([str(tmp_path)])
        names = sorted(os.path.basename(f) for f in files)
        assert names == ["x.py", "y.py"]  # finite, each file once

    def test_file_reached_via_two_links_listed_once(self, tmp_path):
        from transmogrifai_tpu.lint.engine import iter_py_files
        (tmp_path / "real").mkdir()
        (tmp_path / "real" / "m.py").write_text("m = 1\n")
        os.symlink(str(tmp_path / "real"), str(tmp_path / "alias"))
        files = iter_py_files([str(tmp_path)])
        assert len(files) == 1

    def test_vanished_file_raises_clear_error(self, tmp_path):
        from transmogrifai_tpu.lint.engine import iter_py_files
        # a dangling .py symlink models the deleted-mid-scan race:
        # listed by the walk, gone at the existence check
        os.symlink(str(tmp_path / "never-existed.py"),
                   str(tmp_path / "gone.py"))
        with pytest.raises(FileNotFoundError, match="vanished"):
            iter_py_files([str(tmp_path)])

    def test_non_py_path_rejected(self, tmp_path):
        from transmogrifai_tpu.lint.engine import iter_py_files
        p = tmp_path / "notes.txt"
        p.write_text("hi")
        with pytest.raises(FileNotFoundError, match="not a .py"):
            iter_py_files([str(p)])


class TestIncrementalCache:
    FILES = {
        "pkg/a.py": "def fa():\n    return 1\n",
        "pkg/b.py": "def fb():\n    return 2\n",
        "pkg/kern.py": ("import jax\nimport time\n\n\n"
                        "@jax.jit\ndef kernel(x):\n"
                        "    t0 = time.time()\n    return x\n"),
    }

    def _run(self, root, cp):
        stats = {}
        findings, _ = lint_paths([str(root)], cache_path=cp,
                                 stats_out=stats)
        return findings, stats

    def test_cold_then_warm_and_findings_survive(self, tmp_path):
        _write_tree(tmp_path, self.FILES)
        cp = str(tmp_path / "cache.json")
        cold, s1 = self._run(tmp_path, cp)
        assert s1 == {"files": 3, "hits": 0, "misses": 3, "poisoned": 0}
        warm, s2 = self._run(tmp_path, cp)
        assert s2 == {"files": 3, "hits": 3, "misses": 0, "poisoned": 0}
        # cached local findings identical to a fresh analysis
        assert ([(f.rule_id, f.path, f.line) for f in cold]
                == [(f.rule_id, f.path, f.line) for f in warm])
        assert "TX-O01" in _rules(warm)  # time.time() in the jitted body

    def test_single_edit_reanalyzes_only_that_file(self, tmp_path):
        _write_tree(tmp_path, self.FILES)
        cp = str(tmp_path / "cache.json")
        self._run(tmp_path, cp)
        (tmp_path / "pkg" / "a.py").write_text(
            "def fa():\n    return 42\n")
        _, stats = self._run(tmp_path, cp)
        assert stats["misses"] == 1 and stats["hits"] == 2

    def test_tampered_entry_poisons_whole_cache(self, tmp_path, capsys):
        import json as _json
        _write_tree(tmp_path, self.FILES)
        cp = str(tmp_path / "cache.json")
        self._run(tmp_path, cp)
        doc = _json.loads((tmp_path / "cache.json").read_text())
        key = sorted(doc["files"])[0]
        doc["files"][key]["findings"] = [{"rule": "TX-FAKE",
                                         "message": "injected"}]
        (tmp_path / "cache.json").write_text(_json.dumps(doc))
        findings, stats = self._run(tmp_path, cp)
        # loud counter + full re-analysis; the injected finding never
        # reaches the report
        assert stats["poisoned"] == 1
        assert stats["misses"] == 3 and stats["hits"] == 0
        assert "TX-FAKE" not in _rules(findings)
        assert "cache poisoned" in capsys.readouterr().err

    def test_corrupt_json_poisons(self, tmp_path, capsys):
        _write_tree(tmp_path, self.FILES)
        cp = str(tmp_path / "cache.json")
        self._run(tmp_path, cp)
        (tmp_path / "cache.json").write_text("{not json")
        _, stats = self._run(tmp_path, cp)
        assert stats["poisoned"] == 1 and stats["misses"] == 3
        assert "cache poisoned" in capsys.readouterr().err

    def test_schema_bump_is_routine_invalidation_not_poison(
            self, tmp_path, capsys):
        import json as _json
        _write_tree(tmp_path, self.FILES)
        cp = str(tmp_path / "cache.json")
        self._run(tmp_path, cp)
        doc = _json.loads((tmp_path / "cache.json").read_text())
        doc["schema"] = 999
        (tmp_path / "cache.json").write_text(_json.dumps(doc))
        _, stats = self._run(tmp_path, cp)
        assert stats["poisoned"] == 0 and stats["misses"] == 3
        assert "poisoned" not in capsys.readouterr().err


# ---------------------------------------------------------------------------
# repo gate: cross-procedure pass + --changed wiring + performance
# ---------------------------------------------------------------------------

class TestRepoGateCrossProc:
    """The whole-program pass gates this repo alongside the local rules
    (same lint_paths front door, shared warm cache across these tests)."""

    @pytest.fixture(scope="class")
    def gate_cache(self, tmp_path_factory):
        return str(tmp_path_factory.mktemp("txlint") / "gate.json")

    def test_full_tree_clean_under_all_tx_x_rules(self, gate_cache):
        import time as _time
        t0 = _time.monotonic()
        findings, _ = lint_paths([PKG], cache_path=gate_cache)
        cold = _time.monotonic() - t0
        x = [f for f in findings if f.rule_id.startswith("TX-X")]
        assert findings == [], "\n".join(str(f) for f in findings)
        assert x == []
        # budget: whole-tree cold analysis on a 1-CPU container
        assert cold < 10.0, f"cold full-tree lint took {cold:.1f}s"

    def test_warm_rerun_under_a_second(self, gate_cache):
        import time as _time
        lint_paths([PKG], cache_path=gate_cache)  # ensure warm
        t0 = _time.monotonic()
        stats = {}
        findings, _ = lint_paths([PKG], cache_path=gate_cache,
                                 stats_out=stats)
        warm = _time.monotonic() - t0
        assert findings == []
        assert stats["misses"] == 0 and stats["hits"] == stats["files"]
        assert warm < 1.0, f"warm full-tree lint took {warm:.2f}s"

    def test_changed_scope_gate_clean(self, gate_cache):
        """PR-style gate: whole tree analyzed (through the warm cache),
        findings reported only for files changed vs git HEAD."""
        from transmogrifai_tpu.lint.cli import _git_changed_files
        try:
            changed = _git_changed_files()
        except RuntimeError as e:  # pragma: no cover - no git in env
            pytest.skip(str(e))
        findings, _ = lint_paths([PKG], cache_path=gate_cache,
                                 changed=changed)
        assert findings == [], "\n".join(str(f) for f in findings)


class TestLintCli:
    def test_graph_dump(self, capsys):
        import argparse
        from transmogrifai_tpu.lint.cli import add_lint_parser, run_lint
        parser = argparse.ArgumentParser()
        sub = parser.add_subparsers()
        add_lint_parser(sub)
        args = parser.parse_args(
            ["lint", "--graph", "lint_cross_procedure", "--cache", "off"])
        assert run_lint(args) == 0
        out = capsys.readouterr().out
        assert "rules_xproc.lint_cross_procedure" in out
        assert "calls" in out

    def test_graph_unknown_symbol(self, capsys):
        import argparse
        from transmogrifai_tpu.lint.cli import add_lint_parser, run_lint
        parser = argparse.ArgumentParser()
        sub = parser.add_subparsers()
        add_lint_parser(sub)
        args = parser.parse_args(
            ["lint", "--graph", "definitely_not_a_symbol_xyz",
             "--cache", "off"])
        assert run_lint(args) == 1
        assert "no symbol matching" in capsys.readouterr().out

    def test_graph_json_wire_format_omits_empty(self, tmp_path,
                                                capsys):
        """The --graph JSON convention matches LintFinding.to_json's
        chain handling: empty collections are OMITTED, never emitted
        as [] — a leaf node carries no "calls" key, an untagged node
        no "tags" key (satellite fix: the omit-when-empty wire
        contract)."""
        import json as _json
        from transmogrifai_tpu.lint.cli import _dump_graph
        (tmp_path / "mod.py").write_text(
            "def leaf_fn():\n    return 1\n\n\n"
            "def caller_fn():\n    return leaf_fn()\n")
        assert _dump_graph([str(tmp_path)], "caller_fn", "",
                           fmt="json") == 0
        caller_doc = _json.loads(capsys.readouterr().out)
        (node,) = caller_doc["nodes"]
        assert node["name"].endswith("mod.caller_fn")
        assert [c["target"].split(".")[-1] for c in node["calls"]] \
            == ["leaf_fn"]
        assert "tags" not in node            # untagged: key omitted
        assert _dump_graph([str(tmp_path)], "leaf_fn", "",
                           fmt="json") == 0
        leaf_doc = _json.loads(capsys.readouterr().out)
        (leaf,) = leaf_doc["nodes"]
        assert "calls" not in leaf           # leaf: no empty [] key
        assert "tags" not in leaf
        assert set(leaf) == {"name", "path", "line"}

    def test_graph_json_unknown_symbol_document(self, capsys):
        import json as _json
        from transmogrifai_tpu.lint.cli import _dump_graph
        assert _dump_graph([PKG], "definitely_not_a_symbol_xyz",
                           "", fmt="json") == 1
        doc = _json.loads(capsys.readouterr().out)
        assert doc == {"symbol": "definitely_not_a_symbol_xyz",
                       "nodes": []}


class TestRepoGateAudit:
    """The HLO-level repo gate (docs/plan_audit.md): the shipped demo
    plans — scoring buckets AND prepare segments — lower with ZERO
    TX-P findings, inside the cold/warm budgets. Shares one audit
    cache across the class so the warm test exercises the real
    cache path."""

    @pytest.fixture(scope="class")
    def audit_cache(self, tmp_path_factory):
        return str(tmp_path_factory.mktemp("txaudit") / "gate.json")

    def test_demo_audit_cold_clean_within_budget(self, audit_cache):
        import time as _time
        from transmogrifai_tpu.analysis import audit_demo, lint_audits
        t0 = _time.monotonic()
        result = audit_demo(cache_path=audit_cache)
        cold = _time.monotonic() - t0
        assert cold < 15.0, f"cold demo audit took {cold:.1f}s"
        assert {a.plan for a in result.audits} == {"score", "prepare"}
        assert all(a.fusions >= 0 for a in result.audits)
        findings = result.findings + lint_audits(result.audits)
        assert findings == [], "\n".join(str(f) for f in findings)

    def test_demo_audit_warm_within_budget(self, audit_cache):
        import time as _time
        from transmogrifai_tpu.analysis import audit_demo
        audit_demo(cache_path=audit_cache)          # ensure warm
        t0 = _time.monotonic()
        result = audit_demo(cache_path=audit_cache)
        warm = _time.monotonic() - t0
        assert warm < 2.0, f"warm demo audit took {warm:.2f}s"
        assert result.stats["misses"] == 0
        assert result.stats["hits"] == 2            # score + prepare
        assert result.findings == []

    def test_warm_audits_bitwise_match_cold(self, audit_cache):
        from transmogrifai_tpu.analysis import audit_demo
        a1 = audit_demo(cache_path=audit_cache)
        a2 = audit_demo(cache_path=audit_cache)
        assert [a.to_json() for a in a1.audits] == \
            [a.to_json() for a in a2.audits]
