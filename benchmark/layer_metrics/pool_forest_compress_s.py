"""Layer: tree_kernels. Chip seconds a train in the scope ``tree.compress`` of
the forest's fold-grid program ``jit_forest_batched``: active-node slot compression, which the levels past the node cap pay
(``benchmark/trace/scopes.py``). None where
the trace shows no program of that name (the parent of PR 28)."""
from benchmark.layer_metrics.pool_forest_s import FOREST
from benchmark.trace import scopes


def read(obs):
    return scopes.seconds_per_run(obs, FOREST, "tree.compress")
