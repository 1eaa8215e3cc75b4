"""Operations and bytes of the prepare layer's device work, computed from
shapes: the numerators of ``sanity_stats_roofline``. Kept apart from
``costs.py`` (the tree kernels) and ``costs_pool.py`` (the pool's families),
by the same rules.
"""
from __future__ import annotations

from typing import Dict


def sanity_stats_cost(rows: int, columns: int, indicators: int, labels: int,
                      element_bytes: int = 4) -> Dict[str, float]:
    """The sanity checker's statistics over its (rows, columns) float32
    sample of the design, however they are arranged:

    - ``bytes``: the design read once, ``rows * columns * element_bytes``;
      every statistic (moments, label correlation, contingency counts) is a
      reduction over the rows that one pass can feed;
    - ``flops``: the contingency contraction, each indicator column's 0/1
      rows against the one-hot label, ``2 * rows * indicators * labels``;
      the moments' and correlations' O(rows * columns) elementwise work is
      left out, as ``costs.gbt_fit_cost`` leaves out binning.
    """
    return {"flops": 2.0 * rows * indicators * labels,
            "bytes": float(rows) * columns * element_bytes}
