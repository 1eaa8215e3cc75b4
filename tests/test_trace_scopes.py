"""Device time by named scope (ISSUE 26): the ``jax.named_scope`` names of
``models/trees.py`` reach the compiled HLO under unchanged program names and
move no number; ``benchmark/trace/scopes.py`` turns ops with scope paths into
seconds per scope; the seven new readers read that, or the package's spans,
and return None where there is nothing to read.
"""
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:                 # ``benchmark`` is a root package
    sys.path.insert(0, ROOT)

import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402

from benchmark.layer_metrics import (                 # noqa: E402
    fit_node_sums_s, fit_route_s, gbt_pick_s, search_compress_s,
    search_design_s_per_train, search_node_sums_s, search_route_s,
    tail_traverse_s, winner_tail_s_per_train)
from benchmark.trace import scopes                    # noqa: E402
from transmogrifai_tpu.models import GBTClassifier, trees   # noqa: E402


def _components(hlo_text):
    """Every path component of every ``op_name`` in a compiled HLO text."""
    found = set()
    for op_name in re.findall(r'op_name="([^"]+)"', hlo_text):
        for part in op_name.split(";"):
            found.update(scopes.components(part))
    return found


def _module_name(hlo_text):
    return re.search(r"HloModule ([\w.\-]+)", hlo_text).group(1)


# ---------------------------------------------------------------------------
# the scopes are in the programs, and the programs keep their names
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fit_gbt_hlo():
    """``_fit_gbt`` compiled at a tiny size: 24 rows at depth 6, so the
    deepest levels outgrow the slot cap and take the compression path, and
    the ``matmul`` histogram mode, which builds the bin indicator."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(24, 5))
    y = (X[:, 0] > 0).astype(np.float64)
    design, _ = trees._design_args(X, 8)
    return trees._fit_gbt.lower(
        *design[:4], jnp.asarray(y), jax.random.PRNGKey(0), depth=6,
        num_rounds=2, step_size=0.1, reg_lambda=1.0, gamma=0.0,
        min_child_weight=1.0, subsample=1.0, objective="logistic",
        hist_mode="matmul").compile().as_text()


@pytest.fixture(scope="module")
def fold_grid_hlo():
    """The GBT fold-grid program (fit + validation metric) as the selector's
    driver builds it, captured where ``_gbt_fold_grid`` calls it, under the
    accelerator's ``matmul`` histogram mode."""
    rng = np.random.default_rng(1)
    X = rng.normal(size=(64, 4))
    y = (X[:, 1] > 0).astype(np.float64)
    masks = np.ones((2, 64))
    masks[0, :32] = 0.0
    masks[1, 32:] = 0.0
    X_val = np.stack([X[:32], X[32:]])
    y_val = np.stack([y[:32], y[32:]])
    captured = {}
    real = trees._gbt_eval_kernel

    def spy(*key):
        fn = real(*key)

        def call(*args):
            captured["hlo"] = fn.lower(*args).compile().as_text()
            return fn(*args)
        return call

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(trees, "_gbt_eval_kernel", spy)
        patch.setattr(trees, "_hist_mode", lambda n, tb: "matmul")
        metrics = GBTClassifier(num_rounds=2, max_depth=2, max_bins=8
                                ).eval_fold_grid_arrays(
            X, y, masks, [{"gamma": 0.0}, {"gamma": 0.1}], X_val, y_val,
            ("binary", "AuPR"))
    assert np.isfinite(np.asarray(metrics)).all()
    return captured["hlo"]


FIT_SCOPES = ("tree.indicator", "tree.compress", "tree.hist",
              "tree.node_sums", "tree.split", "tree.route", "gbt.round",
              "gbt.pick")


@pytest.mark.parametrize("scope", FIT_SCOPES)
def test_fit_gbt_carries_scope(fit_gbt_hlo, scope):
    assert scope in trees.SCOPES
    assert scope in _components(fit_gbt_hlo)


def test_fit_gbt_keeps_its_program_name(fit_gbt_hlo):
    # fit_gbt_roofline.py and fit_bin_share.py find the program by this name
    assert _module_name(fit_gbt_hlo) == "jit__fit_gbt"
    assert not any(c.startswith("fg.") for c in _components(fit_gbt_hlo))


@pytest.mark.parametrize("scope", ("fg.gbt", "fg.metric", "gbt.round",
                                   "tree.hist", "tree.node_sums",
                                   "tree.split", "tree.route", "gbt.pick"))
def test_fold_grid_program_carries_scope(fold_grid_hlo, scope):
    assert scope in trees.SCOPES
    assert scope in _components(fold_grid_hlo)


def test_fold_grid_program_keeps_its_name(fold_grid_hlo):
    # fold_grid_roofline.py finds the program by this name; the family is
    # told apart by the scope inside it
    assert _module_name(fold_grid_hlo) == "jit_batched"
    families = {c for c in _components(fold_grid_hlo)
                if c.startswith("fg.") and c != "fg.metric"}
    assert families == {"fg.gbt"}


@pytest.mark.parametrize("program", ("fit_gbt_hlo", "fold_grid_hlo"))
def test_no_gather_under_tree_route(program, request):
    """ISSUE 27: under the ``matmul`` family a level routes its rows by
    selects over the slot and the column axis; the scope keeps its name."""
    hlo = request.getfixturevalue(program)
    routed = [line for line in hlo.splitlines() if "tree.route" in line]
    # ``vmap(tree.route)``: the scope is the first opened under the ``vmap``
    # over the lanes still growing (trees._grow_blocks)
    assert any(re.search(r"tree\.route\)?/reduce_or", line)
               for line in routed)
    assert not [line for line in routed
                if re.search(r"= \S+ gather\(", line)]


def test_route_form_follows_hist_mode_and_retraces(monkeypatch):
    rng = np.random.default_rng(27)
    X = rng.normal(size=(80, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    seen = []
    for mode in ("scatter", "matmul", "scatter", "matmul_chunk"):
        monkeypatch.setattr(trees, "_hist_mode", lambda n, tb, m=mode: m)
        before = trees.tree_route_forms()
        GBTClassifier(num_rounds=2, max_depth=2, max_bins=8).fit_arrays(X, y)
        after = trees.tree_route_forms()
        seen.append({k: after[k] - before[k] for k in after})
    # a fit traces its tree grower under its own mode's form and no other;
    # the third fit finds the first one's program and traces nothing
    assert seen[0]["gather"] >= 1 and seen[0]["dense"] == 0
    assert seen[1]["dense"] >= 1 and seen[1]["gather"] == 0
    assert seen[2] == {"dense": 0, "gather": 0}
    assert seen[3]["dense"] >= 1 and seen[3]["gather"] == 0
    assert set(trees.tree_route_forms()) == {"dense", "gather"}


@pytest.mark.parametrize("program, rows, placements", [
    ("fit_gbt_hlo", 24, 1), ("fold_grid_hlo", 64, 0)])
def test_no_row_scatter_under_tree_node_sums(program, rows, placements,
                                             request):
    """ISSUE 31: under the ``matmul`` family the per-slot totals and the
    per-leaf sums are selects over the slot axis reduced over the rows; the
    scope keeps its name. No scatter-add is left, and the one scatter allowed
    is a tree's placement of the 2 * C leaf sums of a compressed last level
    (the fit fixture: 24 rows at depth 6, so level 5 has 24 slots): its
    updates run over the (slot, side) columns, never over the rows."""
    hlo = request.getfixturevalue(program)
    summed = [line for line in hlo.splitlines() if "tree.node_sums" in line]
    assert any("tree.node_sums/reduce_sum" in line for line in summed)
    assert not [line for line in summed if "scatter-add" in line]
    scatters = [line for line in summed
                if re.search(r"= \S+ scatter\(", line)]
    assert len(scatters) == placements      # the rounds are one scan body
    for line in scatters:
        updates = re.search(r"scatter\(\S+, \S+, (%[\w.\-]+)\)", line).group(1)
        (shape,) = re.findall(
            re.escape(updates) + r" = \w+\[([\d,]*)\]", hlo)
        assert int(shape.split(",")[0]) == 2 * 24 != rows


@pytest.mark.parametrize("program, table_reads", [
    ("fit_gbt_hlo", 1), ("fold_grid_hlo", 0)])
def test_no_row_gather_under_gbt_pick(program, table_reads, request):
    """Under the ``matmul`` family a round reads its rows' leaf
    values by a select over the last level's slots. The one gather allowed
    is the fit fixture's read of its (slot, side) table, 2 * 24 leaf values
    of a compressed last level (24 rows at depth 6), never one a row; the
    fold-grid fixture's depth-2 trees end on an identity level and read
    none."""
    hlo = request.getfixturevalue(program)
    picked = [line for line in hlo.splitlines() if "gbt.pick" in line]
    assert any(re.search(r"gbt\.pick\)?/reduce_sum", line)
               for line in picked)
    gathers = [line for line in picked
               if re.search(r"= \S+ gather\(", line)]
    assert len(gathers) == table_reads
    for line in gathers:
        assert re.search(r"= \w+\[(\d+)", line).group(1) == str(2 * 24)


def test_pick_form_follows_hist_mode_and_retraces(monkeypatch):
    """``tree_pick_forms`` counts one traced pick a round body by form: the
    gather under a CPU's default ``scatter`` mode, the dense read under the
    ``matmul`` family."""
    rng = np.random.default_rng(44)
    X = rng.normal(size=(80, 4))
    y = (X[:, 2] > 0).astype(np.float64)
    seen = []
    for mode in (None, "matmul", None, "matmul_chunk"):
        if mode is not None:
            monkeypatch.setattr(trees, "_hist_mode", lambda n, tb, m=mode: m)
        else:
            monkeypatch.undo()
        before = trees.tree_pick_forms()
        GBTClassifier(num_rounds=3, max_depth=3, max_bins=8).fit_arrays(X, y)
        after = trees.tree_pick_forms()
        seen.append({k: after[k] - before[k] for k in after})
    # one fit traces one round body, so one pick; the third fit finds the
    # first one's program and traces nothing
    assert seen[0] == {"dense": 0, "gather": 1}
    assert seen[1] == {"dense": 1, "gather": 0}
    assert seen[2] == {"dense": 0, "gather": 0}
    assert seen[3] == {"dense": 1, "gather": 0}
    assert set(trees.tree_pick_forms()) == {"dense", "gather"}


def test_sum_form_follows_hist_mode_and_retraces(monkeypatch):
    rng = np.random.default_rng(31)
    X = rng.normal(size=(80, 4))
    y = (X[:, 1] > 0).astype(np.float64)
    seen = []
    for mode in ("scatter", "matmul", "scatter", "matmul_chunk"):
        monkeypatch.setattr(trees, "_hist_mode", lambda n, tb, m=mode: m)
        before = trees.tree_sum_forms()
        GBTClassifier(num_rounds=3, max_depth=2, max_bins=8).fit_arrays(X, y)
        after = trees.tree_sum_forms()
        seen.append({k: after[k] - before[k] for k in after})
    # a fit traces its tree grower under its own mode's form and no other;
    # the third fit finds the first one's program and traces nothing
    assert seen[0]["scatter"] >= 1 and seen[0]["dense"] == 0
    assert seen[1]["dense"] >= 1 and seen[1]["scatter"] == 0
    assert seen[2] == {"dense": 0, "scatter": 0}
    assert seen[3]["dense"] >= 1 and seen[3]["scatter"] == 0
    assert set(trees.tree_sum_forms()) == {"dense", "scatter"}


def test_every_scope_is_used_in_trees():
    """``trees.SCOPES`` is the one list the scope readers take: every name
    on it is opened somewhere in the four modules that build the fit and
    fold-grid programs, and they open no other (ISSUE 28 added the linear
    family's in ``models/linear.py`` and ``parallel/cv.py``, ISSUE 32 the
    naive Bayes program's in ``models/bayes.py``, ISSUE 34 the IRLS
    program's ``fg.glm``, ``glm.gram`` and ``glm.solve`` in
    ``models/glm.py``)."""
    from transmogrifai_tpu.models import bayes, glm, linear
    from transmogrifai_tpu.parallel import cv
    source = "".join(open(module.__file__).read()
                     for module in (trees, linear, cv, bayes, glm))
    for scope in trees.SCOPES:
        assert f'jax.named_scope("{scope}")' in source
    assert len(set(trees.SCOPES)) == len(trees.SCOPES)
    assert set(re.findall(r'named_scope\("([^"]+)"\)', source)) \
        == set(trees.SCOPES)


@pytest.fixture(scope="module")
def sanity_hlo():
    """The sanity checker's two statistics programs, compiled at a tiny
    size, their HLO texts joined."""
    from transmogrifai_tpu.checkers import sanity_checker
    X = jnp.asarray(np.eye(3)[np.arange(12) % 3])
    y = jnp.asarray(np.arange(12) % 2, X.dtype)
    onehot = jnp.stack([y, 1.0 - y], axis=1)
    return (sanity_checker._column_statistics.lower(X, y).compile().as_text()
            + sanity_checker._indicator_tables.lower(
                X, onehot, jnp.ones(3, bool)).compile().as_text())


@pytest.mark.parametrize("scope", ("sanity.stats", "sanity.contingency"))
def test_sanity_programs_carry_scope(sanity_hlo, scope):
    """``checkers.sanity_checker.SCOPES``, the list ``sanity_stats_roofline``
    reads, as ``trees.SCOPES`` is the tree readers'."""
    from transmogrifai_tpu.checkers import sanity_checker
    assert scope in sanity_checker.SCOPES
    assert scope in _components(sanity_hlo)
    assert scope not in trees.SCOPES


def test_scopes_move_no_number():
    """The arrays of a tiny boosted fit, as the parent commit (1ce6bab,
    before any scope) computed them under the suite's float64."""
    rng = np.random.default_rng(26)
    X = rng.normal(size=(96, 4))
    y = ((X[:, 0] > 0.2) ^ (X[:, 3] < -0.4)).astype(np.float64)
    model = GBTClassifier(num_rounds=2, max_depth=2, max_bins=8,
                          seed=5).fit_arrays(X, y)
    assert np.asarray(model.feats).tolist() == [[0, 3, 3], [0, 3, 3]]
    np.testing.assert_allclose(model.thrs, [
        [0.4502167356754657, -0.6534414017891933, -0.28815620324264446],
        [0.2913803457631419, -0.28815620324264446, -0.28815620324264446]],
        rtol=1e-12)
    np.testing.assert_allclose(model.leaves, [
        [0.07006430003928305, -0.10261166797542619, -0.09690654509931616,
         0.1750651283959807],
        [0.09181964347898426, -0.15269573292747649, -0.11854550668687237,
         0.17029940884921385]], rtol=1e-12)
    assert float(model.base) == pytest.approx(-0.041672696400568185,
                                              rel=1e-12)


# ---------------------------------------------------------------------------
# the reduction, on plain data made by hand
# ---------------------------------------------------------------------------

SCOPES = ("tree.hist", "tree.node_sums", "tree.split", "tree.route",
          "gbt.round", "fg.metric", "fg.gbt")
IN = "jit(batched)/fg.gbt/vmap()/while/body/closed_call/gbt.round"


def _hand_made():
    """Two devices, each one run of ``jit_batched`` with a ``while`` and its
    body nested inside; device 0 then runs a program without scopes. The
    marker opens at 1,000 ns, so the op before it is left out, and closes at
    10,500 ns, inside device 1's metric fusion and before its copy."""
    def device(n, shift, tail):
        ops = [
            ["%early = f32[] add()", 100 + shift, 500, IN + "/tree.route/add"],
            ["%while = (s32[]) while()", 2000 + shift, 6000,
             "jit(batched)/fg.gbt/vmap()/while"],
            ["%fusion.1 = s32[8] fusion()", 2000 + shift, 3000,
             IN + "/tree.route/gather;" + IN + "/tree.route/concatenate"],
            ["%fusion.2 = f32[4,2] fusion()", 5000 + shift, 2000,
             IN + "/tree.split/tree.node_sums/scatter-add;"
             + IN + "/mul"],
            ["%fusion.3 = f32[4] fusion()", 7000 + shift, 500,
             IN + "/tree.split/argmax;" + IN + "/tree.route/select_n"],
            ["%fusion.4 = f32[] fusion()", 8000 + shift, 1000,
             ";jit(batched)/fg.gbt/vmap()/fg.metric/reduce_sum"],
            ["%copy.9 = f32[8] copy()", 9000 + shift, 400, ""],
        ] + tail
        return {"device": n, "ops": ops, "modules": [
            ["jit_batched(77)", 2000 + shift, 7400]] + (
            [["jit_other(5)", 9500, 600]] if n == 0 else [])}
    return {"source": "c", "marker": [1000, 10500], "devices": [
        device(0, 0, [["%sort.1 = f32[8] sort()", 9500, 600, ""]]),
        device(1, 2000, [])]}


def test_by_scope_on_hand_made_data():
    table = scopes.by_scope(_hand_made(), SCOPES)
    row = table["jit_batched"]
    assert row["runs"] == 2 and row["devices"] == 2
    # device 0 in full; device 1 is shifted by 2,000 ns, so the marker cuts
    # its metric fusion (10,000-10,500 kept) and drops its copy
    assert row["by_scope"]["tree.route"] == pytest.approx(6000e-9)
    assert row["by_scope"]["tree.node_sums"] == pytest.approx(4000e-9)
    assert row["by_scope"]["tree.split"] == pytest.approx(1000e-9)
    assert row["by_scope"]["fg.metric"] == pytest.approx(1500e-9)
    # the while's self time: 6,000 less the 5,500 nested in it, a device
    assert row["by_scope"]["fg.gbt"] == pytest.approx(1000e-9)
    assert "gbt.round" not in row["by_scope"]    # fusion.2's own path wins
    assert row["unscoped"] == pytest.approx(400e-9)
    # fusion.2 also holds a multiply of the round, fusion.3 a select of the
    # routing: both are charged to their own path and counted here
    assert row["disagree"] == pytest.approx(5000e-9)
    # fusion.4 has no path of its own: the metric's scope is inherited
    assert row["inherited"] == pytest.approx(1500e-9)
    assert row["by_family"] == {"fg.gbt": pytest.approx(13500e-9)}
    assert row["seconds"] == pytest.approx(13900e-9)
    assert sum(row["by_scope"].values()) + row["unscoped"] \
        == pytest.approx(row["seconds"])
    other = table["jit_other"]
    assert other["runs"] == 1 and other["by_scope"] == {}
    assert other["unscoped"] == pytest.approx(600e-9)


def test_scopes_of_matches_components_not_prefixes():
    assert scopes.scopes_of("jit(f)/tree.route_old/gather", SCOPES) \
        == (None, None, False, False)
    assert scopes.scopes_of("jit(f)/xtree.route/gather", SCOPES)[0] is None
    assert scopes.scopes_of(IN + "/tree.route/gather", SCOPES) \
        == ("tree.route", "fg.gbt", False, False)
    assert scopes.scopes_of("jit(f)/fg.gbt/fg.metric/sum", SCOPES) \
        == ("fg.metric", "fg.gbt", False, False)
    assert scopes.scopes_of("", SCOPES) == (None, None, False, False)
    # the compiler's own scatter: no path of its own, a scope fused into it
    assert scopes.scopes_of(";jit(f)/mul;" + IN + "/tree.node_sums/concatenate",
                            SCOPES) == ("tree.node_sums", "fg.gbt", False,
                                        True)
    # a transform wraps the first scope opened under it
    assert scopes.components("jit(batched)/fg.gbt/vmap(fg.metric)/vmap()/sub"
                             ) == ["batched", "fg.gbt", "fg.metric", "", "sub"]
    assert scopes.scopes_of("jit(f)/fg.gbt/jvp(vmap(tree.route))/gather",
                            SCOPES) == ("tree.route", "fg.gbt", False, False)


def test_clock_offsets_pair_spans_with_their_annotations():
    host = [["train", 5_000_100, 1_000_000_000],
            ["search.design", 5_000_200, 20_000_000],
            ["search.fetch", 5_000_400, 900_000_000],
            ["train", 2_000_000_350, 1_100_000_000]]
    records = [{"name": "train", "t0": 2.0000001, "dur": 1.1000002},
               {"name": "train", "t0": 0.0050000, "dur": 1.0000001},
               # a train outside the profiler, and a span still open
               {"name": "train", "t0": 9.0, "dur": 1.05},
               {"name": "search.fetch", "t0": 0.0050002, "dur": 0.9},
               {"name": "search.design", "t0": 0.0050001, "dur": None}]
    offsets = scopes.clock_offsets(host, records)
    assert sorted(offsets) == ["search.fetch", "train"]
    assert offsets["train"] == pytest.approx([100.0, 250.0], abs=1e-2)
    assert offsets["search.fetch"] == pytest.approx([200.0], abs=1e-2)


def _proto(*fields):
    """A protobuf message off the wire: (number, int) is a varint, (number,
    bytes) has a length."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def test_hlo_op_names_off_the_wire(tmp_path):
    """``/host:metadata`` as the profiler writes it, built by hand: one
    program, a fusion whose fused computation holds another scope."""
    def instruction(name, opcode, op_name, called=()):
        return _proto((1, name.encode()), (2, opcode.encode()),
                      (7, _proto((1, b"add"), (2, op_name.encode()))),
                      (35, 9), *[(38, c) for c in called])
    nested = _proto((1, b"fused_computation.1"), (5, 301),
                    (2, instruction("scatter.1", "scatter", "")),
                    (2, instruction("reshape.1", "reshape",
                                    "jit(f)/tree.node_sums/concatenate")))
    fused = _proto((1, b"fused_computation"), (5, 300),
                   (2, instruction("gather.1", "gather",
                                   "jit(f)/tree.route/gather")),
                   (2, instruction("fusion.6", "fusion", "", [301])),
                   (2, instruction("mul.1", "multiply",
                                   "jit(f)/gbt.round/mul")))
    entry = _proto((1, b"main"), (5, 1),
                   (2, instruction("fusion.7", "fusion",
                                   "jit(f)/tree.route/gather", [300])),
                   (2, instruction("copy.2", "copy", "")))
    hlo_proto = _proto((1, _proto((1, b"jit_f"), (3, nested), (3, fused),
                                  (3, entry))))
    metadata = _proto((1, 77), (2, b"jit_f(77)"),
                      (5, _proto((1, 1), (6, hlo_proto))))
    plane = _proto((1, 3), (2, b"/host:metadata"),
                   (4, _proto((1, 77), (2, metadata))))
    other = _proto((1, 4), (2, b"/host:CPU"), (3, _proto((1, 1))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_proto((1, other), (1, plane)))
    assert scopes.hlo_op_names(str(path)) == {"jit_f(77)": {
        "scatter.1": "", "reshape.1": "jit(f)/tree.node_sums/concatenate",
        "gather.1": "jit(f)/tree.route/gather",
        "fusion.6": ";jit(f)/tree.node_sums/concatenate",
        "mul.1": "jit(f)/gbt.round/mul",
        # its own path, then every other inside it, the nested fusion's too
        "fusion.7": "jit(f)/tree.route/gather;jit(f)/gbt.round/mul;"
                    "jit(f)/tree.node_sums/concatenate",
        "copy.2": ""}}


def test_recorded_cut_of_the_chip_trace():
    """A cut of the builder's traced ``synth100_gbt.fit`` run on the v5e
    (PR 26), in ``scopes.load``'s plain shape, with its seconds pinned."""
    path = os.path.join(ROOT, "benchmark", "trace",
                        "recorded_scopes_v5e.json")
    with open(path) as fh:
        recorded = json.load(fh)
    table = scopes.by_scope(recorded["plain"], recorded["scopes"])
    for program, pins in recorded["pinned"].items():
        row = table[program]
        assert row["runs"] == pins["runs"]
        assert row["seconds"] == pytest.approx(pins["seconds"], rel=1e-9)
        assert row["unscoped"] == pytest.approx(pins["unscoped"], rel=1e-9)
        assert row["disagree"] == pytest.approx(pins["disagree"], rel=1e-9)
        assert row["inherited"] == pytest.approx(pins["inherited"], rel=1e-9)
        assert row["by_scope"] == {k: pytest.approx(v, rel=1e-9)
                                   for k, v in pins["by_scope"].items()}


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

SCOPE_READERS = [(fit_route_s, "jit__fit_gbt", "tree.route"),
                 (fit_node_sums_s, "jit__fit_gbt", "tree.node_sums"),
                 (search_route_s, "jit_batched", "tree.route"),
                 (search_node_sums_s, "jit_batched", "tree.node_sums"),
                 (search_compress_s, "jit_batched", "tree.compress"),
                 (gbt_pick_s, "jit_batched", "gbt.pick")]


@pytest.fixture
def table_of(monkeypatch):
    """Point the readers' shared table at plain data instead of a file."""
    def use(plain):
        monkeypatch.setattr(scopes, "newest_trace", lambda: "hand-made")
        monkeypatch.setattr(scopes, "load", lambda path: plain)
        scopes.table.cache_clear()
        scopes._say_once.cache_clear()
    yield use
    scopes.table.cache_clear()


@pytest.mark.parametrize("reader,program,scope", SCOPE_READERS,
                         ids=[r[0].__name__.rsplit(".", 1)[-1]
                              for r in SCOPE_READERS])
def test_scope_reader(reader, program, scope, table_of, capsys):
    plain = _hand_made()
    for device in plain["devices"]:
        device["modules"] = [[m[0].replace("jit_batched", program), *m[1:]]
                             for m in device["modules"]]
        device["ops"][2][3] = device["ops"][2][3].replace(
            "tree.route/gather", scope + "/gather")
    table_of(plain)
    # per train: two runs on two devices are one train, its chip seconds
    # summed over the devices
    assert reader.read({"trace": {"devices": [{}, {}]}}) == pytest.approx(
        6000e-9 + (4000e-9 if scope == "tree.node_sums" else 0.0))
    assert reader.read({"trace": None}) is None     # not a traced run
    # the same programs without one scoped op: an executable from a cache
    # filled before the scopes were added. Absent, never 0
    for device in plain["devices"]:
        for op in device["ops"]:
            op[3] = re.sub(r"(tree|fg|gbt)\.\w+/", "", op[3])
    table_of(plain)
    capsys.readouterr()
    assert reader.read({"trace": {"devices": [{}, {}]}}) is None
    assert "no package scope in this trace" in capsys.readouterr().out


@pytest.mark.parametrize("trains", [1, 2])
def test_tail_traverse_reader_divides_by_traced_trains(trains, table_of):
    """ISSUE 40: ``tree.traverse`` in ``jit__predict_leaves``, per TRACED
    TRAIN and not per run over devices: the program runs twice a train, on
    one chip of four under a mesh."""
    assert "tree.traverse" in trees.SCOPES
    plain = _hand_made()
    for device in plain["devices"]:
        device["modules"] = [[m[0].replace("jit_batched", "jit__predict_leaves"),
                              *m[1:]] for m in device["modules"]]
        device["ops"][2][3] = device["ops"][2][3].replace(
            "tree.route/gather", "tree.traverse/gather")
    table_of(plain)
    reps = [{"ok": True, "traced": True}] * trains + [
        {"ok": True, "traced": False}, {"ok": False, "traced": True}]
    obs = {"trace": {"devices": [{}, {}, {}, {}]}, "reps": reps}
    assert tail_traverse_s.read(obs) == pytest.approx(6000e-9 / trains)
    assert tail_traverse_s.read(dict(obs, reps=[])) is None
    assert tail_traverse_s.read(dict(obs, trace=None)) is None
    # the parent commit: the program walks without the scope
    for device in plain["devices"]:
        device["ops"][2][3] = device["ops"][2][3].replace(
            "tree.traverse/", "")
    table_of(plain)
    assert tail_traverse_s.read(obs) is None


def test_scope_readers_without_a_trace(table_of, monkeypatch):
    monkeypatch.setattr(scopes, "newest_trace", lambda: None)
    scopes.table.cache_clear()
    assert all(reader.read({"trace": {"devices": [{}]}}) is None
               for reader, _, _ in SCOPE_READERS)
    assert tail_traverse_s.read({"trace": {"devices": [{}]}, "reps": [
        {"ok": True, "traced": True}]}) is None


def _span(sid, name, parent, dur):
    return {"sid": sid, "name": name, "parent": parent, "dur": dur,
            "t0": float(sid)}


SPANS = [
    # three trains; the tail of the second ran twice as long, the third has
    # two static groups (two designs) and a span still open
    _span(1, "train", None, 10.0),
    _span(2, "search.family", 1, 8.0),
    _span(3, "search.design", 2, 0.5), _span(4, "search.fetch", 2, 7.0),
    _span(5, "search.refit", 1, 0.25), _span(6, "search.train_eval", 1, 0.5),
    _span(10, "train", None, 11.0),
    _span(11, "search.family", 10, 8.0),
    _span(12, "search.design", 11, 0.75),
    _span(13, "search.refit", 10, 0.5), _span(14, "search.train_eval", 10, 1.0),
    _span(20, "train", None, 12.0),
    _span(21, "search.family", 20, 9.0),
    _span(22, "search.design", 21, 0.5), _span(23, "search.design", 21, 0.125),
    _span(24, "search.refit", 20, 0.125),
    _span(25, "search.train_eval", 20, None),
    # a refit outside every train is nobody's tail
    _span(30, "search.refit", None, 100.0),
]


def test_span_readers_on_a_hand_made_span_list(monkeypatch):
    monkeypatch.setattr(winner_tail_s_per_train, "package_spans",
                        lambda: SPANS)
    monkeypatch.setattr(search_design_s_per_train, "package_spans",
                        lambda: SPANS)
    assert winner_tail_s_per_train.read({}) == 0.75     # of .75, 1.5, .125
    assert search_design_s_per_train.read({}) == 0.625  # of .5, .75, .625


def test_span_readers_without_the_spans(monkeypatch):
    bare = [s for s in SPANS if s["name"] in ("train", "search.family")]
    monkeypatch.setattr(winner_tail_s_per_train, "package_spans",
                        lambda: bare)
    monkeypatch.setattr(search_design_s_per_train, "package_spans",
                        lambda: bare)
    # the parent commit under this benchmark: its trains have no such span
    assert winner_tail_s_per_train.read({}) is None
    assert search_design_s_per_train.read({}) is None
    assert winner_tail_s_per_train.per_train_median([], ("x",)) is None
