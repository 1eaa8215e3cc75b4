"""Typed columns through the compiled prepare plan: the sanity checker's
contingency tables counted on the device, the PickList vectorizer's one
pass over its strings, the ``criteo_bin_pool`` configuration's generator and
plain references, and the spans, counter and readers that measure them.
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:                 # ``benchmark`` is a root package
    sys.path.insert(0, ROOT)

import jax.numpy as jnp                               # noqa: E402

from benchmark.configs import criteo_bin_pool as cfg  # noqa: E402
from benchmark.layer_metrics import (                 # noqa: E402
    prepare_d2h_bytes_per_train, prepare_encode_s_per_train,
    sanity_check_s_per_train, sanity_stats_roofline)
from benchmark.reference.sanity_plain import sanity_check  # noqa: E402
from benchmark.reference.transmogrify_plain import (  # noqa: E402
    PlainTransmogrify, top_categories)
from benchmark.trace import scopes                    # noqa: E402
from transmogrifai_tpu.checkers import SanityChecker  # noqa: E402
from transmogrifai_tpu.checkers import sanity_checker  # noqa: E402
from transmogrifai_tpu.features.columns import FeatureColumn  # noqa: E402
from transmogrifai_tpu.ops.categorical import (       # noqa: E402
    OneHotVectorizer, OneHotVectorizerModel, _top_categories)
from transmogrifai_tpu.runtime import telemetry       # noqa: E402
from transmogrifai_tpu.types import PickList          # noqa: E402
from transmogrifai_tpu.utils.vector_meta import (     # noqa: E402
    NULL_INDICATOR, VectorColumnMetadata, VectorMetadata)

CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                     "criteo_bin_pool.json")))


# ---------------------------------------------------------------------------
# the sanity checker: tables on the device, the same model as on the host
# ---------------------------------------------------------------------------

def _design(seed, n, levels, constant_group=False, labels=2):
    """A design of numeric columns and indicator groups of ``levels`` levels
    each (one-hot rows), its metadata, and a label of ``labels`` values
    tied to the first group."""
    rng = np.random.default_rng(seed)
    blocks, metas = [], []
    for j in range(3):
        blocks.append(rng.normal(size=(n, 1)) * (j + 1))
        metas.append(VectorColumnMetadata(parent_feature_name=f"x{j}",
                                          parent_feature_type="Real"))
    first = None
    for g, k in enumerate(levels):
        codes = (np.zeros(n, np.int64) if constant_group and g == 0
                 else rng.integers(0, k, n))
        first = codes if first is None else first
        blocks.append(np.eye(k)[codes])
        metas += [VectorColumnMetadata(
            parent_feature_name=f"g{g}", parent_feature_type="PickList",
            grouping=f"g{g}", indicator_value=f"v{i}") for i in range(k)]
    # a null indicator group of one column, as an Integral's
    blocks.append((rng.random((n, 1)) < 0.1).astype(np.float64))
    metas.append(VectorColumnMetadata(parent_feature_name="i0",
                                      parent_feature_type="Integral",
                                      indicator_value=NULL_INDICATOR))
    X = np.concatenate(blocks, axis=1)
    meta = VectorMetadata(name="v", columns=tuple(metas))
    if labels == 1:
        y = np.ones(n)
    else:
        y = ((first + rng.integers(0, 2, n)) % labels).astype(np.float64)
    return X, y, meta


def _fields(model):
    return json.dumps([c.to_json() for c in model.summary.column_stats]
                      + [model.kept_indices, model.summary.dropped],
                      sort_keys=True)


@pytest.mark.parametrize("seed,n,levels,constant,labels", [
    (0, 997, (1, 2, 3), False, 2),
    (1, 1201, (40, 7), False, 2),
    (2, 613, (5, 13, 21, 40), False, 3),
    (3, 509, (4, 9), True, 2),                 # a constant group
    (4, 733, (6, 3), False, 1),                # a single-label sample
    (5, 1021, tuple(range(1, 12)), False, 2),
])
def test_device_tables_give_the_host_model(seed, n, levels, constant,
                                           labels):
    X, y, meta = _design(seed, n, levels, constant, labels)
    checker = SanityChecker(max_cramers_v=0.5)
    host = checker._fit_stats(y, X, meta)
    device = checker._fit_stats(y, jnp.asarray(X), meta)
    assert _fields(device) == _fields(host)


def test_device_tables_pull_only_the_counts(monkeypatch):
    """The device path brings back (columns x labels) counts and the
    columns' statistics, never the indicator block."""
    X, y, meta = _design(7, 4099, (40, 40, 40))
    telemetry.reset()
    SanityChecker()._fit_stats(y, jnp.asarray(X), meta)
    device_bytes = telemetry.counters()[telemetry.PREPARE_PULL_BYTES]
    assert 0 < device_bytes < X.shape[1] * 8 * 8
    assert device_bytes < X.nbytes / 100


def test_host_pull_counts_device_bytes_only():
    telemetry.reset()
    host = np.ones((4, 3))
    assert telemetry.host_pull(host) is host
    assert telemetry.counters().get(telemetry.PREPARE_PULL_BYTES, 0) == 0
    got = telemetry.host_pull(jnp.ones((4, 3), jnp.float32), np.float64)
    assert got.dtype == np.float64 and got.shape == (4, 3)
    assert telemetry.counters()[telemetry.PREPARE_PULL_BYTES] == 48


def test_a_non_binary_indicator_is_counted_on_the_host():
    X, y, meta = _design(8, 301, (3, 4))
    X[5, 4] = 0.5                          # an indicator column holding 0.5
    checker = SanityChecker()
    telemetry.reset()
    device = checker._fit_stats(y, jnp.asarray(X), meta)
    pulled = telemetry.counters()[telemetry.PREPARE_PULL_BYTES]
    assert _fields(device) == _fields(checker._fit_stats(y, X, meta))
    assert pulled >= 301 * 8 * 8           # the indicator block came back


def test_sanity_scopes_are_the_files_own():
    source = open(sanity_checker.__file__).read()
    for scope in sanity_checker.SCOPES:
        assert source.count(f"SCOPES[{sanity_checker.SCOPES.index(scope)}]"
                            ) == 1
    assert sanity_checker.SCOPES == ("sanity.stats", "sanity.contingency")


# ---------------------------------------------------------------------------
# the PickList vectorizer: one pass, the same categories and codes
# ---------------------------------------------------------------------------

def _column(values):
    data = np.empty(len(values), dtype=object)
    data[:] = values
    return FeatureColumn(PickList, data)


def _two_passes(data, top_k, min_support):
    counts = {}
    for v in data:
        if v is not None:
            counts[v] = counts.get(v, 0) + 1
    cats = _top_categories(counts, top_k, min_support)
    index = {c: j for j, c in enumerate(cats)}
    return cats, np.asarray([len(cats) + 1 if v is None
                             else index.get(v, len(cats)) for v in data],
                            np.int32)


@pytest.mark.parametrize("values,top_k,min_support", [
    # ties at the top-K edge: b, c, d all twice, two of them kept, lexically
    (["a"] * 3 + ["d", "c", "b"] * 2 + [None, "e"], 3, 1),
    (["a"] * 3 + ["d", "c", "b"] * 2 + [None, "e"], 3, 2),
    ([None, None, None], 20, 1),                        # only missing
    (["x", "y", "x", None, "z", "x"], 20, 1),           # fewer than K
    (["x", "y", "x", None, "z", "x"], 20, 3),           # support cuts
    ([], 20, 1),                                        # no row
    ([f"{i % 37:02x}" for i in range(500)] + [None] * 9, 20, 10),
    (["q"] * 12, 20, 10),                               # one category
    (["\u00e9", "e", "\u00e9", "E", "e", "\u00e9"], 1, 1),  # non-ASCII
    (["b", "a", "c", None, "a", "b", "c"], 1, 1),       # a three-way tie
])
def test_one_pass_gives_the_two_passes(values, top_k, min_support):
    col = _column(values)
    model = OneHotVectorizer(top_k=top_k, min_support=min_support
                             ).fit_columns([col])
    want_cats, want_codes = _two_passes(col.data, top_k, min_support)
    assert model.categories == [want_cats]
    codes = model.encode_input_column(0, col)
    np.testing.assert_array_equal(codes, want_codes)
    assert codes.dtype == np.int32
    # a copy of the column: the same codes
    np.testing.assert_array_equal(
        model.encode_input_column(0, _column(list(values))), want_codes)


def test_unseen_values_light_other():
    model = OneHotVectorizer(min_support=1).fit_columns(
        [_column(["a", "b", "a", None])])
    codes = model.encode_input_column(0, _column(["a", "zz", None, "b"]))
    np.testing.assert_array_equal(codes, [0, 2, 3, 1])
    untracked = OneHotVectorizerModel(categories=[["a"]], track_nulls=False)
    np.testing.assert_array_equal(
        untracked.encode_input_column(0, _column([None, "a", "q"])),
        [-1, 0, 1])


def test_a_fitted_model_holds_only_its_categories():
    """Nothing of the rows the fit walked stays on the model: its state is
    the categories and the null flag, as a model made from them."""
    col = _column(["a", "b", "a", None] * 50)
    fitted = OneHotVectorizer(min_support=1).fit_columns([col])
    fresh = OneHotVectorizerModel(categories=fitted.categories)
    assert vars(fitted).keys() == vars(fresh).keys()
    assert not any(isinstance(v, np.ndarray) for v in vars(fitted).values())


def test_held_codes_leave_the_state_fingerprint_alone():
    from transmogrifai_tpu.plans.prepare import _state_fingerprint
    col = _column(["a", "b", "a"] * 5)
    fitted = OneHotVectorizer(min_support=1).fit_columns([col])
    fresh = OneHotVectorizerModel(categories=fitted.categories)
    assert _state_fingerprint(fitted) == _state_fingerprint(fresh)
    assert _state_fingerprint(fitted) is not None


# ---------------------------------------------------------------------------
# the configuration's generator and plain references
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def full_table():
    return cfg.make_table(CONFIG, 4200000001, 98304)


def test_generator_is_deterministic_by_seed():
    a, ya, la = cfg.make_table(CONFIG, 2 ** 31 + 5, 2048)
    b, yb, lb = cfg.make_table(CONFIG, 2 ** 31 + 5, 2048)
    c, _, _ = cfg.make_table(CONFIG, 2 ** 31 + 6, 2048)
    for name in a:
        np.testing.assert_array_equal(a[name], b[name])
    np.testing.assert_array_equal(ya, yb)
    np.testing.assert_array_equal(la, lb)
    assert any(not np.array_equal(a[n], c[n]) for n in a if n != "I2")


def test_generator_hits_its_shares_and_cardinalities(full_table):
    table, y, _ = full_table
    rows = len(y)
    assert int(y.sum()) == cfg.positives(CONFIG, rows) == 25165
    ints, picks = CONFIG["integral"], CONFIG["picklist"]
    for name, share in zip(ints["names"], ints["missing"]):
        assert int(np.isnan(table[name]).sum()) == round(share * rows)
    present = table["I2"][~np.isnan(table["I2"])]
    assert present.min() == ints["negative_low"]
    assert int((present < 0).sum()) == round(ints["negative_share"] * rows)
    for name, card, share in zip(picks["names"], picks["cardinality"],
                                 picks["missing"]):
        values = table[name]
        assert sum(v is None for v in values) == round(share * rows)
        distinct = {v for v in values if v is not None}
        assert len(distinct) <= card
        assert all(len(v) == 8 for v in distinct)
        if card <= 20:
            assert len(distinct) == card


def test_the_designs_width_is_the_files(full_table):
    table, y, _ = full_table
    plain = PlainTransmogrify(CONFIG["integral"]["names"],
                              CONFIG["picklist"]["names"]).fit(table)
    assert len(plain.columns()) == CONFIG["design"]["columns"]
    widths = [len(c) + 2 for c in plain.categories]
    assert sum(widths) == CONFIG["design"]["picklist_columns"]


def test_plain_references_on_a_hand_made_table():
    table = {"I1": np.array([3.0, np.nan, 1.0, 1.0, 3.0, 3.0]),
             "C1": np.array(["b", "a", None, "b", "c", "b"], dtype=object)}
    plain = PlainTransmogrify(["I1"], ["C1"], top_k=1, min_support=1
                              ).fit(table)
    assert plain.fills == [3.0] and plain.categories == [["b"]]
    assert plain.columns() == [("I1", None), ("I1", NULL_INDICATOR),
                               ("C1", "b"), ("C1", "OTHER"),
                               ("C1", NULL_INDICATOR)]
    X = plain.transform(table)
    np.testing.assert_array_equal(X[1], [3.0, 1.0, 0.0, 1.0, 0.0])
    np.testing.assert_array_equal(X[2], [1.0, 0.0, 0.0, 0.0, 1.0])
    assert top_categories(["x", "y", "y", None], 5, 2) == ["y"]


def test_workflow_design_is_the_plain_references_at_4096_rows():
    """The workflow's transmogrify + sanity_check on the CPU against
    ``transmogrify_plain`` + ``sanity_plain``; the planted near-duplicate of
    the label goes, for its Cramer's V."""
    from transmogrifai_tpu.checkers import SanityCheckerModel
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.workflow import Workflow
    table, y, _ = cfg.make_table(CONFIG, 4200000003, 4096)
    ints, picks = CONFIG["integral"]["names"], CONFIG["picklist"]["names"]
    label = FeatureBuilder.real_nn("label").extract(
        lambda r: r["label"]).as_response()
    features = ([FeatureBuilder.integral(n).extract(cfg._field(n))
                 .as_predictor() for n in ints]
                + [FeatureBuilder.pick_list(n).extract(cfg._field(n))
                   .as_predictor() for n in picks])
    vector = transmogrify(features)
    checked = vector.sanity_check(label)
    model = Workflow().set_result_features(label, checked).set_input_dataset(
        cfg.dataset(table, y)).train(validate="off")
    plain = PlainTransmogrify(ints, picks).fit(table)
    X = plain.transform(table)
    pruned = sanity_check(X, y, plain.columns(), CONFIG["sanity"])
    checker = next(s for s in model.stages()
                   if isinstance(s, SanityCheckerModel))
    assert [(c.parent_feature_name, c.indicator_value)
            for c in checker.summary.column_stats] == plain.columns()
    assert checker.kept_indices == pruned["kept"]
    np.testing.assert_array_equal(
        np.asarray(model.train_dataset[checked.name].data),
        X[:, pruned["kept"]])
    planted = [j for j, (p, _) in enumerate(plain.columns()) if p == "C25"]
    assert all("cramers_v" in pruned["reasons"][j] for j in planted)
    assert not {r for j in planted for r in pruned["reasons"][j]} - {
        "cramers_v", "variance"}        # its constant NULL column goes too
    assert not set(planted) & set(checker.kept_indices)
    for c in checker.summary.column_stats:
        if c.indicator_value is not None and np.isfinite(c.cramers_v):
            assert c.cramers_v == pytest.approx(
                pruned["cramers_v"][c.parent_feature_name], abs=1e-12)


@pytest.mark.parametrize("forests,have,correct", [
    ([0.57, 0.52, 0.58, 0.59, 0.56, 0.60], 0.58, True),   # median 0.575
    ([0.57, 0.52, 0.58, 0.59, 0.56, 0.60], 0.61, False),
    ([0.50, 0.51, 0.49, 0.60, 0.61, 0.62], 0.53, True),   # median 0.555
])
def test_forest_lane_is_held_to_the_median_of_plain_forests(
        forests, have, correct, monkeypatch):
    """A forest lane is held to the median of ``forest_reference_seeds``
    plain forests, each fitted on its own seed; the other lanes to one
    reference."""
    from benchmark.jobs import typed_pool_search as job
    config = dict(CONFIG, reference=dict(CONFIG["reference"],
                                         forest_reference_seeds=6))
    families = {f["class"]: f for f in cfg.families(config)}
    forest, svc = (cfg.grid(families[c])[0]
                   for c in ("RandomForestClassifier", "LinearSVC"))
    tasks = []

    def values(cfg_, config_, tasks_, *args, **kwargs):
        tasks.extend(tasks_)
        return list(forests) + [0.7]
    monkeypatch.setattr(job, "reference_values", values)
    got = {"seed": 100, "metric": "AuPR",
           "winner": {"family": "LinearSVC", "params": svc},
           "lanes": {"RandomForestClassifier": {"0": {
                         "params": forest, "folds": [0.0, have, 0.0]}},
                     "LinearSVC": {"0": {"params": svc,
                                         "folds": [0.7, 0.0, 0.0]}}}}
    lanes = [["RandomForestClassifier", 0, 1, 0.025],
             ["LinearSVC", 0, 0, 0.0001]]
    problems = job.check_readings(cfg, config, lanes, got, None, None)
    # the winner here has no coefficients: its own problem, set aside
    problems = [p for p in problems if not p.startswith("the winner")]
    assert [t[-1] for t in tasks if t[0] == "lane"] == [100 + k for k in
                                                         range(6)] + [None]
    assert (problems == []) == correct


def test_sanity_plain_controls_move_the_result():
    table, y, _ = cfg.make_table(CONFIG, 4200000004, 4096)
    plain = PlainTransmogrify(CONFIG["integral"]["names"],
                              CONFIG["picklist"]["names"]).fit(table)
    X, cols = plain.transform(table), plain.columns()
    base = sanity_check(X, y, cols, CONFIG["sanity"])
    moved = sanity_check(X, y, cols, dict(CONFIG["sanity"],
                                          max_cramers_v=0.99))
    assert len(moved["kept"]) > len(base["kept"])
    bf16 = sanity_check(X, y, cols, CONFIG["sanity"],
                        tables_dtype="bfloat16")
    gap = max(abs(bf16["cramers_v"][g] - v)
              for g, v in base["cramers_v"].items() if np.isfinite(v))
    assert gap > 1e-6
    support1 = PlainTransmogrify(CONFIG["integral"]["names"],
                                 CONFIG["picklist"]["names"], min_support=1
                                 ).fit(table)
    assert support1.columns() != cols


# ---------------------------------------------------------------------------
# spans, the counter, and the readers
# ---------------------------------------------------------------------------

def test_train_emits_encode_spans_and_the_pull_counter():
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.observability import trace
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.workflow import Workflow
    table, y, _ = cfg.make_table(CONFIG, 4200000005, 512)
    label = FeatureBuilder.real_nn("label").extract(
        lambda r: r["label"]).as_response()
    features = [FeatureBuilder.integral("I1").extract(cfg._field("I1"))
                .as_predictor(),
                FeatureBuilder.pick_list("C1").extract(cfg._field("C1"))
                .as_predictor()]
    checked = transmogrify(features).sanity_check(label)
    telemetry.reset()
    trace.reset()
    trace.configure(True)
    try:
        Workflow().set_result_features(label, checked).set_input_dataset(
            cfg.dataset({k: table[k] for k in ("I1", "C1")}, y)
        ).train(validate="off")
        spans = [s for s in trace.spans() if s["name"] == "prepare.encode"]
    finally:
        trace.configure(False)
    assert {s["attrs"]["phase"] for s in spans} == {"fit", "encode"}
    assert all(s["attrs"]["rows"] == 512 for s in spans)
    assert sum(s["attrs"]["columns"] for s in spans
               if s["attrs"]["phase"] == "fit") == 2
    assert telemetry.PREPARE_PULL_BYTES in telemetry.counters()


def test_span_and_counter_readers():
    reps = [{"ok": True, "traced": False, "prepare_host_pull_bytes": 900,
             "stages": {"SanityChecker_sanityChecker/fit": 0.5,
                        "SanityCheckerModel_sanityChecker/transform": 0.25,
                        "ModelSelector_x/fit": 9.0}},
            {"ok": True, "traced": True, "prepare_host_pull_bytes": 1100,
             "stages": {"SanityChecker_sanityChecker/fit": 0.25}},
            {"ok": False, "traced": False}]
    assert sanity_check_s_per_train.read({"reps": reps}) == 0.5
    assert prepare_d2h_bytes_per_train.read({"reps": reps}) == 1000.0
    bare = [{"ok": True, "traced": False, "stages": {}}]
    assert sanity_check_s_per_train.read({"reps": bare}) is None
    assert prepare_d2h_bytes_per_train.read({"reps": bare}) is None
    spans = [{"sid": 1, "name": "train", "parent": None, "dur": 5.0},
             {"sid": 2, "name": "prepare.encode", "parent": 1, "dur": 0.25},
             {"sid": 3, "name": "prepare.encode", "parent": 1, "dur": 0.5},
             {"sid": 4, "name": "train", "parent": None, "dur": 5.0},
             {"sid": 5, "name": "prepare.encode", "parent": 4, "dur": 1.25}]
    from benchmark.layer_metrics import winner_tail_s_per_train as tail
    real = tail.package_spans
    try:
        prepare_encode_s_per_train.package_spans = lambda: spans
        assert prepare_encode_s_per_train.read({}) == 1.0
        prepare_encode_s_per_train.package_spans = lambda: spans[:1]
        assert prepare_encode_s_per_train.read({}) is None
    finally:
        prepare_encode_s_per_train.package_spans = real


def _sanity_trace(scope_names):
    ops = [["%fusion.1 = f32[532] fusion()", 2000, 3000,
            f"jit(_column_statistics)/{scope_names[0]}/reduce_sum"],
           ["%dot.2 = f32[532,2] dot()", 6000, 1000,
            f"jit(_indicator_tables)/{scope_names[1]}/dot_general"]]
    return {"source": "c", "marker": [1000, 10000], "devices": [{
        "device": 0, "ops": ops,
        "modules": [["jit__column_statistics(1)", 2000, 3000],
                    ["jit__indicator_tables(2)", 6000, 1000]]}]}


def test_sanity_roofline_reader(monkeypatch):
    monkeypatch.setattr(scopes, "newest_trace", lambda: "hand-made")
    monkeypatch.setattr(scopes, "load",
                        lambda path: _sanity_trace(sanity_checker.SCOPES))
    shape = {"rows": 98304, "columns": 532, "indicators": 519, "labels": 2}
    obs = {"trace": {"devices": [{}]}, "device_kind": "TPU v5 lite",
           "sanity_shape": shape,
           "reps": [{"ok": True, "traced": True}]}
    least = 98304 * 532 * 4 / 819e9           # the design read once
    assert sanity_stats_roofline.read(obs) == pytest.approx(
        100.0 * least / 4000e-9)
    assert sanity_stats_roofline.read(dict(obs, sanity_shape=None)) is None
    assert sanity_stats_roofline.read(dict(obs, trace=None)) is None
    # a package without the scopes
    monkeypatch.setattr(scopes, "load",
                        lambda path: _sanity_trace(("a.b", "c.d")))
    assert sanity_stats_roofline.read(obs) is None


# ---------------------------------------------------------------------------
# the linear drivers take the prepared matrix where it lives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["logistic", "svc", "squared"])
def test_linear_drivers_take_a_device_matrix_as_is(kind, monkeypatch):
    """A device matrix and its host copy give the same fold-grid metrics and
    parameters, and the device one is never read back to the host."""
    from transmogrifai_tpu.parallel import cv
    rng = np.random.default_rng(11)
    X = rng.normal(size=(96, 5))
    y = (X[:, 0] + 0.3 * rng.normal(size=96) > 0).astype(np.float64)
    masks = np.ones((3, 96))
    for f in range(3):
        masks[f, f::3] = 0.0
    X_val = np.stack([X[f::3] for f in range(3)])
    y_val = np.stack([y[f::3] for f in range(3)])
    grid = np.array([[0.01, 0.5], [0.1, 0.0]])
    spec = ("regression", "RootMeanSquaredError") if kind == "squared" \
        else ("binary", "AuPR")
    want = cv.eval_linear_fold_grid(kind, X, y, masks, grid, X_val, y_val,
                                    spec, max_iter=20)
    fitted = cv.fit_linear_fold_grid(kind, X, y, masks, grid, max_iter=20)
    pulled = []
    real = np.asarray

    def watch(a, *args, **kwargs):
        if a is X_dev or a is Xv_dev:
            pulled.append(a.shape)
        return real(a, *args, **kwargs)
    X_dev, Xv_dev = jnp.asarray(X), jnp.asarray(X_val)
    monkeypatch.setattr(cv.np, "asarray", watch)
    got = cv.eval_linear_fold_grid(kind, X_dev, y, masks, grid, Xv_dev,
                                   y_val, spec, max_iter=20)
    fitted_dev = cv.fit_linear_fold_grid(kind, X_dev, y, masks, grid,
                                         max_iter=20)
    assert pulled == []
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(fitted_dev, fitted)


def test_sanity_plain_samples_as_the_checker_does():
    """Past ``sample_limit`` rows both draw the same sample: the same kept
    columns and the same Cramer's V."""
    X, y, meta = _design(12, 3001, (6, 9, 2))
    params = {"check_sample": 1.0, "sample_limit": 1200, "sample_seed": 42,
              "min_variance": 1e-5, "max_correlation": 0.95,
              "min_correlation": 0.0, "max_cramers_v": 0.3,
              "min_required_rule_support": 0.001, "max_rule_confidence": 1.0}
    checker = SanityChecker(**{k: v for k, v in params.items()
                               if k != "sample_seed"}, sample_seed=42)
    model = checker._fit_stats(y, jnp.asarray(X), meta)
    columns = [(c.parent_feature_name, c.indicator_value)
               for c in meta.columns]
    plain = sanity_check(X, y, columns, params)
    assert model.summary.sample_size == 1200
    assert model.kept_indices == plain["kept"]
    for c in model.summary.column_stats:
        if c.indicator_value is not None and np.isfinite(c.cramers_v):
            assert c.cramers_v == pytest.approx(
                plain["cramers_v"][c.parent_feature_name], abs=1e-12)
