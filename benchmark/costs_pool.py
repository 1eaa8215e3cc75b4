"""Operations and bytes the pool's non-boosted families need, computed from
shapes: the numerators of ``forest_grid_roofline`` and
``linear_grid_roofline``. Beside ``costs.py`` (whose ``gbt_fit_cost`` and
``least_seconds`` are used as they are), for the cell ``synth100_pool.search``.
"""
from __future__ import annotations

from typing import Dict, List

from benchmark import costs


def forest_fit_cost(rows: int, pooled_bins: int, depth: int, trees: int,
                    classes: int = 2) -> Dict[str, float]:
    """A bagged forest by level histograms, counted as ``costs.gbt_fit_cost``
    counts a boosted lane: one tree of ``depth`` levels per member, each
    level contracting the rows against the bin indicator for every node that
    can hold rows (at most 256) and every per-row statistic. A forest's
    statistics are one weighted indicator a class where the boosted fit has
    gradient and hessian, and a tree's indicator spans its feature pool's
    ``pooled_bins``, not the whole design's (which is why the pool exists).
    Left out, as there: the bootstrap draw, the pool's column gather, the
    routing, the split search and the validation metric."""
    return costs.gbt_fit_cost(rows=rows, total_bins=pooled_bins, depth=depth,
                              rounds=trees, stat_columns=classes)


def linear_grid_cost(lanes: List[Dict[str, int]], matrix_rows: int,
                     element_bytes: int = 4) -> Dict[str, float]:
    """One linear fold-grid program: every lane takes ``steps`` gradient
    steps, each one product of its fold's ``rows`` x ``columns`` training
    matrix with the coefficients and one with the residuals (``2 * 2 * rows *
    columns`` operations); however the lanes are batched, a step has to
    sweep the shared ``matrix_rows`` x ``columns`` float32 matrix once, and
    once is what the chip is held to for all lanes together. Left out: the
    standardization, the power iteration that sets the step, the metric."""
    steps = max(lane["steps"] for lane in lanes)
    columns = lanes[0]["columns"]
    return {
        "flops": float(sum(lane["steps"] * 2 * 2 * lane["rows"]
                           * lane["columns"] for lane in lanes)),
        "bytes": float(steps) * matrix_rows * columns * element_bytes,
    }


def summed(parts: List[Dict[str, float]]) -> Dict[str, float]:
    return {key: sum(part[key] for part in parts)
            for key in ("flops", "bytes")}
