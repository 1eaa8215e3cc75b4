"""Operations and bytes the algorithm needs, computed from shapes. These are
the numerators of every roofline share the benchmark reports; the package's
own estimates (``examples/scale_bench.py`` ``hist_flops``) are not read.
"""
from __future__ import annotations

from typing import Dict


def gbt_fit_cost(rows: int, total_bins: int, depth: int, rounds: int,
                 stat_columns: int = 2, element_bytes: int = 1,
                 node_cap: int = 256) -> Dict[str, float]:
    """A boosted fit by level histograms, one tree of ``depth`` levels per
    round, of whose level ``l`` at most ``min(2**l, node_cap)`` nodes hold
    rows (the estimator's cap on the nodes of a level).

    - ``flops``: level ``l`` contracts, for each of those nodes and
      each of the ``stat_columns`` per-row statistics (gradient, hessian),
      the rows against the (rows, total_bins) bin indicator: ``2 * rows *
      nodes * stat_columns * total_bins`` multiply-adds counted as 2;
    - ``bytes``: however the contraction is arranged, every level has to read
      which bin each row falls in for every bin column once, and the form the
      system keeps (a dense 0/1 indicator) is the one charged: ``rows *
      total_bins * element_bytes`` per level. The package declares the
      indicator float32, the v5e compiler keeps it as ``pred``, one byte an
      entry, and sweeps it at 92 % of the chip's bandwidth (PERF.md, PR 24,
      call 10): one byte is what the chip is held to. Binning, the per-row
      statistics and the split search are left out: they are O(rows *
      columns) against O(rows * total_bins).
    """
    nodes = sum(min(2 ** level, node_cap) for level in range(depth))
    return {
        "flops": float(rounds) * 2.0 * rows * nodes * stat_columns
        * total_bins,
        "bytes": float(rounds) * depth * rows * total_bins * element_bytes,
    }


def least_seconds(cost: Dict[str, float], peaks: Dict[str, float]
                  ) -> Dict[str, float]:
    """The least time the chip could take, and which peak sets it: the
    bandwidth for the depth-6 fit, the arithmetic for the search's 54 lanes
    (a third of them at depth 12)."""
    compute = cost["flops"] / peaks["flops_per_s"]
    memory = cost["bytes"] / peaks["bytes_per_s"]
    return {"seconds": max(compute, memory), "compute_seconds": compute,
            "memory_seconds": memory,
            "bound": "compute" if compute >= memory else "bandwidth"}
