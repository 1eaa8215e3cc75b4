#!/usr/bin/env python3
"""Controls for the ``correct`` of ``msd_reg_pool.search``: the readings one
run of the cell logged (``bench: readings: {...}``) held, by the job's own
``check_readings``, to references made wrong on purpose (as
``controls_mc.py`` does for the multiclass pool).

    python3 benchmark/controls_reg.py --log <the run's output> [--table t.npz]
        [--only bfloat16,forest_bfloat16_statistics]
    python3 benchmark/controls_reg.py --dump-table t.npz --seed <n>

Three controls decide the exit code. ``float64`` is no fault at all (the cell
as it runs) and has to come out correct. ``bfloat16`` is the winner's own
family's reference computed in the nearest precision below the
configuration's float32 that holds this table (``linreg_plain`` /
``glm_plain`` with ``dtype="bfloat16"``) and has to come out NOT correct by
the coefficient limit. ``forest_bfloat16_statistics`` is a plain forest whose
``[w, wy, wyy]`` are rounded to bfloat16 before the level histograms and
whose node sums are not, which is what the chip's contraction did to the
package's regression trees before PR 34: it has to come out NOT correct by the
forest lane's tolerance, or that tolerance is too wide to see the fault the
cell exists for. Every other control is a fault the cell should show; the
lines printed at the end say which it shows and by which limit. A control
degrades the REFERENCE, so a reading is system minus a wrong reference: the
sign of what the same fault would read in the system, turned round.

What each limit shows and what it cannot (readings: the final tree's run of
seed 3400000201 on the chip and its table, PR 34; system minus reference):

- ``winner_coefficients_within`` 0.004 (the winner's standardized
  coefficients against its family's float64 reference, as a share of the
  largest; the run read 5.1e-5): arithmetic below float32 (the winner's
  ``linreg_plain`` in bfloat16 1.9e-2, by this limit ALONE: its lane moves by
  5e-5; NumPy float32 is correct), another ``reg_param`` (x10: 6.1e-2);
- the squared lane against the MINIMISER and the two IRLS lanes against IRLS
  to convergence, 0.001 (-8.9e-5, -1e-6, +3e-6): ``reg_param`` x10 (-0.008,
  -0.012), folds from another seed (-0.007, +0.030, -0.023), a bfloat16 IRLS
  (``glm_bfloat16``: -0.39 and -6.4: a year does not fit bfloat16, so that
  family's bfloat16 reference fails by its lanes too) and NumPy's float32
  IRLS in the step form (-0.0019: what solving for the increment repaired in
  the package); not where the penalty sits at ``reg_param`` 0.01
  (``glm_raw_penalty`` -1.8e-4 on the gaussian lane, inside);
- the boosted lane, 0.02 (+0.0031): a level short (-0.063), ten rounds for
  twenty (-0.226), other folds (-0.030);
- the forest lane against one plain forest, [-0.02, +0.02] (+0.0018):
  **bfloat16-rounded histogram statistics (-0.420)**, a level short (-0.074),
  ten trees for fifty (-0.0201, just); not every feature at every node
  (-0.0016) nor other folds (+0.0076: a forest's RMSE moves little with the
  fold). The table is made from the run's seed on whatever backend is here;
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

LINREG, GLM = {"LinearRegression"}, {"GeneralizedLinearRegression"}
GBT, FOREST = {"GBTRegressor"}, {"RandomForestRegressor"}
#: name -> (the families whose checks are run again, the override)
CONTROLS = {
    "float64": (None, {}),
    # of the WINNER's family alone (main() narrows ``only`` to it)
    "bfloat16": (LINREG | GLM, {
        "LinearRegression": {"dtype": "bfloat16"},
        "GeneralizedLinearRegression": {"dtype": "bfloat16"}}),
    "glm_bfloat16": (GLM, {
        "GeneralizedLinearRegression": {"dtype": "bfloat16"}}),
    "float32": (LINREG | GLM, {
        "LinearRegression": {"dtype": "float32"},
        "GeneralizedLinearRegression": {"dtype": "float32"}}),
    "forest_bfloat16_statistics": (FOREST, {
        "RandomForestRegressor": {"round_stats": "bfloat16"}}),
    "reg_x10": (LINREG | GLM, {
        "LinearRegression": {"reg_param": 0.1},
        "GeneralizedLinearRegression": {"reg_param": 0.1}}),
    "glm_raw_penalty": (GLM, {
        "GeneralizedLinearRegression": {"standardize": False}}),
    "wrong_folds": (None, {"fold_seed": 1}),
    "trees10": (FOREST, {"RandomForestRegressor": {"num_trees": 10}}),
    "forest_level_short": (FOREST, {"RandomForestRegressor": {
        "max_depth": 5}}),
    "all_features": (FOREST, {"RandomForestRegressor": {
        "feature_subset_strategy": "all"}}),
    "gbt_level_short": (GBT, {"GBTRegressor": {"max_depth": 5}}),
    "gbt_rounds10": (GBT, {"GBTRegressor": {"num_rounds": 10}}),
}
#: the controls that decide the exit code, and whether each must be correct
DECISIVE = {"float64": True, "bfloat16": False,
            "forest_bfloat16_statistics": False}


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
    from benchmark.configs import msd_reg_pool as cfg
    from benchmark.jobs import reg_pool_search as job
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "msd_reg_pool.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "reg_pool_search.json")) as fh:
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
        if name == "bfloat16":
            only = {got["winner"]["family"]}
        harness.say.prefix = f"control {name} | "
        verdicts[name] = job.check_readings(
            cfg, config, traffic["check_lanes"], got, X, y,
            override=override, only=only)
    harness.say.prefix = ""
    for name, problems in verdicts.items():
        print(f"control {name}: " + ("correct" if not problems
                                     else "NOT correct: " + "; ".join(problems)))
    wrong = [n for n, correct in DECISIVE.items()
             if n in verdicts and bool(verdicts[n]) == correct]
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
