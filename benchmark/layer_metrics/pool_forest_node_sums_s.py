"""Layer: tree_kernels. Chip seconds a train in the scope ``tree.node_sums`` of
the forest's fold-grid program ``jit_forest_batched``: the per-slot and per-leaf segment_sums of every lane, one statistic column a class
(``benchmark/trace/scopes.py``). None where
the trace shows no program of that name (the parent of PR 28)."""
from benchmark.layer_metrics.pool_forest_s import FOREST
from benchmark.trace import scopes


def read(obs):
    return scopes.seconds_per_run(obs, FOREST, "tree.node_sums")
