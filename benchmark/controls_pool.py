#!/usr/bin/env python3
"""Controls for the ``correct`` of ``synth100_pool.search``: the readings one
run of the cell logged (``bench: readings: {...}``) held, by the job's own
``check_readings``, to references made wrong on purpose.

    python3 benchmark/controls_pool.py --log <the run's output> [--table t.npz]
        [--only float16,trees10]
    python3 benchmark/controls_pool.py --dump-table t.npz --seed <n>

The first control is no fault at all (float64, as the cell runs it) and has
to come out correct; ``float16`` is the linear references computed in the
nearest precision below the configuration's float32 and has to come out NOT
correct: these two decide the exit code. Every other control is a fault the
cell should show; the table printed at the end says which it shows, by which
limit, and which it cannot. A control degrades the REFERENCE, so a reading is
system minus a wrong reference: the sign of what the same fault would read in
the system, turned round.

The table is made from the run's seed on whatever backend is here. The chip's
generator and the CPU's agree to the last bit on most seeds, not on all
(PERF.md section 6), so ``--dump-table`` writes the table where the cell ran
and ``--table`` reads it back. Nothing here is timed; no result line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

LINEAR_FAMILIES = {"LogisticRegression", "LinearSVC"}
FOREST, GBT = {"RandomForestClassifier"}, {"GBTClassifier"}
#: name -> (the families whose checks are run again, the override)
CONTROLS = {
    "float64": (None, {}),
    "float16": (LINEAR_FAMILIES, {
        "LogisticRegression": {"dtype": "float16", "max_iter": 60},
        "LinearSVC": {"dtype": "float16", "max_iter": 60}}),
    "reg_x10": (LINEAR_FAMILIES, {"LogisticRegression": {"reg_param": 0.1},
                                  "LinearSVC": {"reg_param": 0.1}}),
    "reg_over10": (LINEAR_FAMILIES, {
        "LogisticRegression": {"reg_param": 0.001},
        "LinearSVC": {"reg_param": 0.001}}),
    "elastic_net_0.1": (LINEAR_FAMILIES, {
        "LogisticRegression": {"elastic_net_param": 0.1}}),
    "no_standardization": (LINEAR_FAMILIES, {
        "LogisticRegression": {"standardization": False},
        "LinearSVC": {"standardization": False}}),
    "wrong_folds": (None, {"fold_seed": 1}),
    "trees10": (FOREST, {"RandomForestClassifier": {"num_trees": 10}}),
    "no_bootstrap": (FOREST, {"RandomForestClassifier": {"bootstrap": False}}),
    "all_features": (FOREST, {"RandomForestClassifier": {
        "feature_subset_strategy": "all"}}),
    "forest_level_short": (FOREST, {"RandomForestClassifier": {
        "max_depth": 5}}),
    "gbt_level_short": (GBT, {"GBTClassifier": {"max_depth": 11}}),
    "gbt_rounds10": (GBT, {"GBTClassifier": {"num_rounds": 10}}),
    "trees_float16_table": (FOREST | GBT, {"tree_data": "float16"}),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--log")
    ap.add_argument("--table")
    ap.add_argument("--only", default="")
    ap.add_argument("--dump-table")
    ap.add_argument("--seed", type=int)
    args = ap.parse_args()
    import numpy as np

    from benchmark import harness
    from benchmark.configs import synth100_pool as cfg
    from benchmark.jobs import pool_search
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "synth100_pool.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "pool_search.json")) as fh:
        traffic = json.load(fh)
    if args.dump_table:
        X, y, _ = (np.asarray(a) for a in cfg.make_table(
            config, args.seed, traffic["rows"]))
        np.savez_compressed(args.dump_table, X=X, y=y, seed=args.seed)
        return 0
    with open(args.log) as fh:
        got = json.loads(next(
            line for line in fh if line.startswith("bench: readings: ")
        ).split("readings: ", 1)[1])
    if args.table:
        saved = np.load(args.table)
        assert int(saved["seed"]) == got["seed"], "another seed's table"
        X, y = saved["X"], saved["y"]
    else:
        X, y, _ = (np.asarray(a) for a in cfg.make_table(
            config, got["seed"], got["rows"]))
    names = [n for n in args.only.split(",") if n] or list(CONTROLS)
    verdicts = {}
    for name in names:
        only, override = CONTROLS[name]
        harness.say.prefix = f"control {name} | "
        verdicts[name] = pool_search.check_readings(
            cfg, config, traffic["check_lanes"], got, X, y,
            override=override, only=only)
    harness.say.prefix = ""
    for name, problems in verdicts.items():
        print(f"control {name}: " + ("correct" if not problems
                                     else "NOT correct: " + "; ".join(problems)))
    wrong = [n for n in ("float64",) if verdicts.get(n)] + [
        n for n in ("float16",) if n in verdicts and not verdicts[n]]
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
