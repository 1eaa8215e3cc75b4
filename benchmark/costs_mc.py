"""Operations and bytes of the multiclass pool's fold-grid programs, computed
from shapes with the class count in them: the numerator of
``softmax_grid_roofline``. Beside ``costs_pool.py`` (whose ``forest_fit_cost``
already takes ``classes``, and is used as it is for the K-class forest), for
the cell ``covtype_mc_pool.search``.
"""
from __future__ import annotations

from typing import Dict, List


def softmax_grid_cost(lanes: List[Dict[str, int]], matrix_rows: int,
                      element_bytes: int = 4) -> Dict[str, float]:
    """The multinomial logistic fold-grid program: every lane takes ``steps``
    gradient steps, each one product of its fold's ``rows`` x ``columns``
    training matrix with the ``classes`` coefficient rows (the logits) and one
    of its transpose with the ``rows`` x ``classes`` residuals (the gradient):
    ``2 * 2 * rows * columns * classes`` operations, counted once however
    many passes the chip's multiplier takes for float32. However the lanes
    are batched, a step has to sweep the shared ``matrix_rows`` x ``columns``
    float32 matrix once, and once is what the chip is held to for all lanes
    together (as ``costs_pool.linear_grid_cost`` holds the binary lanes).
    Left out: the standardization, the power iteration that sets the step,
    the softmax itself (``rows * classes`` exponentials a step) and the
    validation metric."""
    steps = max(lane["steps"] for lane in lanes)
    columns = lanes[0]["columns"]
    return {
        "flops": float(sum(lane["steps"] * 2 * 2 * lane["rows"]
                           * lane["columns"] * lane["classes"]
                           for lane in lanes)),
        "bytes": float(steps) * matrix_rows * columns * element_bytes,
    }
