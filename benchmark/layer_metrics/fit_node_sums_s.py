"""Layer: tree_kernels. Device seconds a fit in the scope ``tree.node_sums``
of the program ``jit__fit_gbt``: the per-slot ``segment_sum`` of every level
and the per-leaf one of every tree (``benchmark/trace/scopes.py``)."""
from benchmark.trace import scopes


def read(obs):
    return scopes.seconds_per_run(obs, "jit__fit_gbt", "tree.node_sums")
