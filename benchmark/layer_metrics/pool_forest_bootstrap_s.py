"""Layer: tree_kernels. Chip seconds a train in the scope ``tree.bootstrap`` of
the forest's fold-grid program ``jit_forest_batched``: a tree's Poisson row weights and its weighted class indicators, drawn for every lane
(``benchmark/trace/scopes.py``). None where
the trace shows no program of that name (the parent of PR 28)."""
from benchmark.layer_metrics.pool_forest_s import FOREST
from benchmark.trace import scopes


def read(obs):
    return scopes.seconds_per_run(obs, FOREST, "tree.bootstrap")
