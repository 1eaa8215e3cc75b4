#!/usr/bin/env python3
"""Controls for the ``correct`` of ``criteo_bin_pool.search``: the readings
one run of the cell logged (``bench: design readings: {...}`` and ``bench:
readings: {...}``) held, by the job's own ``check_design`` and
``check_readings``, to references made wrong on purpose.

    python3 benchmark/controls_typed.py --log <the run's output> [--only a,b]

The table is made again from the run's seed (on the host, in NumPy: the same
table on any machine). The first control is no fault at all (the references
as the cell runs them) and has to come out correct; every other one has to
come out NOT correct, and the exit code says whether they all did:

- ``float16``: the linear references computed in float16, the nearest
  precision below the configuration's float32, on the design standardized
  in float64 (its raw integer columns pass float16's 65,504):
  ``winner_coefficients_within`` is the limit that shows it;
- ``top19`` and ``support1``: the plain pivot keeps 19 categories, or every
  category seen once (the design's columns show them);
- ``tables_bfloat16``: the plain checker's contingency tables rounded to
  bfloat16 before anything is computed from them (``cramers_v_within``);
- ``cramers_v_0.99``: the plain checker's Cramer's V limit moved from 0.95 to
  0.99, which keeps the planted near-duplicate (the kept columns show it).

A control degrades the REFERENCE, so a reading is system minus a wrong
reference. Nothing here is timed; no result line.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np

from benchmark.reference.linear_plain import (PlainLogistic, PlainSVC,
                                              standardize)

LINEAR_FAMILIES = {"LogisticRegression", "LinearSVC"}


class _Standardized:
    """A linear reference fitted, in its own dtype, on the design already
    standardized in float64 (on the rows ``mask`` marks), its coefficients
    mapped back to the raw columns."""

    def fit(self, X, y, mask=None):
        X = np.asarray(X, np.float64)
        w = np.ones(len(X)) if mask is None else np.asarray(mask, np.float64)
        Xs, mu, sigma = standardize(X, w)
        super().fit(Xs, y, mask)
        coefficients = np.asarray(self.coefficients, np.float64)
        self.coefficients = coefficients / sigma
        self.intercept = float(self.intercept - self.coefficients @ mu)
        return self


class Float16Logistic(_Standardized, PlainLogistic):
    pass


class Float16SVC(_Standardized, PlainSVC):
    pass


FLOAT16 = {"dtype": "float16", "max_iter": 60, "standardization": False}
#: name -> (what is run again: "design" or the families whose checks are,
#: the override)
CONTROLS = {
    "as_run": ("all", {}),
    "float16": (LINEAR_FAMILIES, {
        "classes": {"LogisticRegression": Float16Logistic,
                    "LinearSVC": Float16SVC},
        "LogisticRegression": FLOAT16, "LinearSVC": FLOAT16}),
    "top19": ("design", {"transmogrify": {"top_k": 19}}),
    "support1": ("design", {"transmogrify": {"min_support": 1}}),
    "tables_bfloat16": ("design", {"tables_dtype": "bfloat16"}),
    "cramers_v_0.99": ("design", {"sanity": {"max_cramers_v": 0.99}}),
}


def _line(log: str, kind: str):
    """The last ``kind`` readings the run logged (a dry run's lines carry
    a platform label before them)."""
    pattern = re.compile(r"(?:bench: |\| )" + kind + r": (\{.*)$")
    with open(log) as fh:
        found = [m.group(1) for m in map(pattern.search, fh) if m]
    if not found:
        raise SystemExit(f"no '{kind}' line in {log}")
    return json.loads(found[-1])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--log", required=True)
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    from benchmark import harness
    from benchmark.configs import criteo_bin_pool as cfg
    from benchmark.jobs import typed_pool_search as job
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "criteo_bin_pool.json")) as fh:
        config = json.load(fh)
    design_got = _line(args.log, "design readings")
    got = _line(args.log, "readings")
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "typed_pool_search.json")) as fh:
        lanes = json.load(fh)["check_lanes"]
    table, y, _ = cfg.make_table(config, got["seed"], got["rows"])
    _, design = job.check_design(cfg, config, design_got, table, y)
    wanted = [c for c in args.only.split(",") if c] or list(CONTROLS)
    outcome = {}
    for name in wanted:
        what, override = CONTROLS[name]
        harness.say(f"control {name}")
        problems = []
        if what in ("all", "design"):
            problems, _ = job.check_design(cfg, config, design_got, table, y,
                                           override)
        if what != "design":
            problems += job.check_readings(
                cfg, config, lanes, got, design, y, override,
                only=None if what == "all" else what,
                workers=job.REFERENCE_WORKERS)
        outcome[name] = problems
        harness.say(f"control {name}: "
                    + ("correct" if not problems else
                       "NOT correct: " + "; ".join(problems)))
    print(json.dumps({name: not problems
                      for name, problems in outcome.items()}))
    sound = not outcome.get("as_run", [])
    shown = all(outcome[name] for name in outcome if name != "as_run")
    return 0 if sound and shown else 1


if __name__ == "__main__":
    sys.exit(main())
