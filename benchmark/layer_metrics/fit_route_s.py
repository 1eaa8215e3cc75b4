"""Layer: tree_kernels. Device seconds a fit in the scope ``tree.route`` of
the program ``jit__fit_gbt``: the per-level row routing,
``packed[rows, feature_of_node]``. Self time of the ``XLA Ops`` whose
``op_name`` has that scope innermost (``benchmark/trace/scopes.py``)."""
from benchmark.trace import scopes


def read(obs):
    return scopes.seconds_per_run(obs, "jit__fit_gbt", "tree.route")
