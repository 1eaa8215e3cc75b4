"""Operations and bytes of the regression pool's IRLS lanes, computed from
shapes and from the iterations the program ran: the numerator of
``glm_grid_roofline``. Beside ``costs_pool.py`` (whose ``forest_fit_cost`` and
``linear_grid_cost`` are used as they are for the regression forest and the
squared lanes), for the cell ``msd_reg_pool.search``.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional


def family_iterations(calls: List[Dict[str, Any]]) -> Dict[str, int]:
    """IRLS trip count by family from the ``search.fetch`` spans of
    ``jit_glm_batched`` (attributes ``family``, ``irls_iterations``): the
    smallest a family's calls of the window report, so that what is counted
    was run in every one of them."""
    out: Dict[str, int] = {}
    for call in calls:
        family, ran = call["family"], int(call["irls_iterations"])
        out[family] = min(out.get(family, ran), ran)
    return out


def glm_grid_cost(lanes: List[Dict[str, Any]], calls: List[Dict[str, Any]],
                  matrix_rows: int, element_bytes: int = 4
                  ) -> Optional[Dict[str, float]]:
    """The IRLS fold-grid programs of one train (one a family): every lane
    runs its family's ``irls_iterations``, each one Gram product of its
    fold's ``rows`` x ``(columns + 1)`` weighted training matrix with itself,
    ``2 * rows * (columns + 1)^2`` operations, counted once however many
    passes the chip's multiplier takes for float32. However the lanes are
    batched, an iteration has to sweep the shared ``matrix_rows`` x
    ``(columns + 1)`` float32 matrix once, and once is what the chip is held
    to for a family's lanes together. Counted at the iterations the program
    RAN (the span's attribute), never at ``max_iter``: lanes that converge
    early must not be credited with work nobody did, or the share of the
    roofline could pass 100 %; an iteration count above a lane's ``max_iter``
    is refused for the same reason. Left out: the standardization, the start
    (one more Gram product), the (columns + 1)^3 solve, the link's
    exponentials and the validation metric. None where a family of the lanes
    has no call to read."""
    ran = family_iterations(calls)
    if not lanes or any(lane["family"] not in ran for lane in lanes):
        return None
    for lane in lanes:
        if ran[lane["family"]] > lane["max_iter"]:
            raise ValueError(
                f"{lane['family']} lanes report {ran[lane['family']]} IRLS "
                f"iterations, over their max_iter {lane['max_iter']}")
    width = lanes[0]["columns"] + 1
    return {
        "flops": float(sum(ran[lane["family"]] * 2 * lane["rows"]
                           * (lane["columns"] + 1) ** 2 for lane in lanes)),
        "bytes": float(sum(ran[family] for family in
                           {lane["family"] for lane in lanes}))
        * matrix_rows * width * element_bytes,
    }
