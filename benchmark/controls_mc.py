#!/usr/bin/env python3
"""Controls for the ``correct`` of ``covtype_mc_pool.search``: the readings
one run of the cell logged (``bench: readings: {...}``) held, by the job's own
``check_readings``, to references made wrong on purpose (as
``controls_pool.py`` does for the binary pool).

    python3 benchmark/controls_mc.py --log <the run's output> [--table t.npz]
        [--only bfloat16,trees10] [--minimiser]
    python3 benchmark/controls_mc.py --dump-table t.npz --seed <n>

The first control is no fault at all (float64, as the cell runs it) and has
to come out correct; ``bfloat16`` is the logistic reference computed in the
nearest precision below the configuration's float32 that holds this table
(``reference/multinomial_plain.py``) and has to come out NOT correct, by the
coefficient limit and by no other: these two decide the exit code. Every
other control is a fault the cell should show; the lines printed at the end
say which it shows and by which limit. A control degrades the REFERENCE, so a
reading is system minus a wrong reference: the sign of what the same fault
would read in the system, turned round.

What each limit shows and what it cannot (readings: the final tree's run of
seed 3200000301 on the chip and its table, PR 32; system minus reference):

- ``winner_coefficients_within`` 0.0005 (the winner's standardized
  coefficients against the same 250 steps in float64; the run read 5.6e-5):
  arithmetic below float32 (bfloat16 8.5; NumPy float32 reads 1.7e-4 and is
  correct), another ``reg_param`` (x10: 4.3) or elastic-net (0.1 for 0.5:
  0.24), no standardization (49.5). It cannot show that 250 steps stop short
  of the minimiser: ``--minimiser`` prints how far (0.205 there: a finding,
  PERF.md section 6, not a fault of the run);
- the logistic lane's F1 against the MINIMISER's, 0.001 (-4.0e-5): the
  objective itself (``reg_param`` x10 +0.095, the other elastic-net -0.0073);
  not the precision (a bfloat16 fit +5.6e-4: an F1 counts argmaxes), nor
  folds drawn from another seed (+7.5e-4);
- naive Bayes, a closed form, 0.0015 (+1.0e-5): other folds (-0.011), one
  bf16 pass in its products (0.006-0.007 on three runs); not the smoothing
  (x10: -5.5e-4, 1 against sums in the millions);
- the single tree, which draws nothing, 0.0005 (equal): a level short
  (+0.015), 16 bins for 32 (-0.0062), other folds (-0.0061), a table rounded
  to bfloat16 (-5.7e-4, just);
- the forest against the mean of three plain forests, [-0.03, +0.04]
  (+0.0025): a forest that has not learnt, and little else: ten trees for
  fifty read +0.016, a level short +0.008, other folds 0.0000, a table
  rounded to bfloat16 +0.003, all inside (no bootstrap, which here also means
  every feature at every node, reads -0.083 and shows).
  Its draws' law is held by ``tests/test_multiclass_pool.py`` and
  ``test_pool_reference.py`` on the CPU.

The table is made from the run's seed on whatever backend is here;
``--dump-table`` writes it where the cell ran and ``--table`` reads it back.
Nothing here is timed; no result line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

LOGISTIC, BAYES = {"LogisticRegression"}, {"NaiveBayes"}
TREE, FOREST = {"DecisionTreeClassifier"}, {"RandomForestClassifier"}
#: name -> (the families whose checks are run again, the override)
CONTROLS = {
    "float64": (None, {}),
    "bfloat16": (LOGISTIC, {"LogisticRegression": {"dtype": "bfloat16"}}),
    "float32": (LOGISTIC, {"LogisticRegression": {"dtype": "float32"}}),
    "reg_x10": (LOGISTIC, {"LogisticRegression": {"reg_param": 0.1}}),
    "elastic_net_0.1": (LOGISTIC, {
        "LogisticRegression": {"elastic_net_param": 0.1}}),
    # unstandardized, the minimiser is thousands of steps away: capped
    "no_standardization": (LOGISTIC, {
        "LogisticRegression": {"standardization": False, "max_iter": 2000}}),
    "wrong_folds": (None, {"fold_seed": 1}),
    "smoothing_x10": (BAYES, {"NaiveBayes": {"smoothing": 10.0}}),
    "tree_level_short": (TREE, {"DecisionTreeClassifier": {"max_depth": 5}}),
    "tree_bins16": (TREE, {"DecisionTreeClassifier": {"max_bins": 16}}),
    "trees10": (FOREST, {"RandomForestClassifier": {"num_trees": 10}}),
    "no_bootstrap": (FOREST, {"RandomForestClassifier": {"bootstrap": False}}),
    "all_features": (FOREST, {"RandomForestClassifier": {
        "feature_subset_strategy": "all"}}),
    "forest_level_short": (FOREST, {"RandomForestClassifier": {
        "max_depth": 5}}),
    "trees_bfloat16_table": (TREE | FOREST, {"tree_data": "bfloat16"}),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--log")
    ap.add_argument("--table")
    ap.add_argument("--only", default="")
    ap.add_argument("--minimiser", action="store_true")
    ap.add_argument("--dump-table")
    ap.add_argument("--seed", type=int)
    args = ap.parse_args()
    import numpy as np

    from benchmark import harness
    from benchmark.configs import covtype_mc_pool as cfg
    from benchmark.jobs import mc_pool_search as job
    from benchmark.reference.multinomial_plain import PlainMultinomial
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "covtype_mc_pool.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "mc_pool_search.json")) as fh:
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
        verdicts[name] = job.check_readings(
            cfg, config, traffic["check_lanes"], got, X, y,
            override=override, only=only)
    harness.say.prefix = ""
    if args.minimiser and "coefficients" in got["winner"]:
        winner = got["winner"]
        full = PlainMultinomial(**winner["params"]).fit(job.design(X), y)
        print(f"the winner {winner['params']} stops "
              f"{job.coefficient_distance(winner['coefficients'], winner['intercept'], full):.3e}"
              f" short of the minimiser ({full.steps} steps to it)")
    for name, problems in verdicts.items():
        print(f"control {name}: " + ("correct" if not problems
                                     else "NOT correct: " + "; ".join(problems)))
    wrong = [n for n in ("float64",) if verdicts.get(n)] + [
        n for n in ("bfloat16",) if n in verdicts and not verdicts[n]]
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
