"""Layer: tree_kernels. Chip seconds a train in ``jit_forest_batched`` charged
to the scope ``fg.forest`` itself (what no inner scope of the program
explains): the pick of every validation row's leaf values over the trees, K
wide, and the votes' sum (``benchmark/trace/scopes.py``). Per traced train.
None where the trace shows no program of that name or no such scope in it."""
from benchmark.layer_metrics.mc_forest_hist_s import scope_seconds_per_train
from benchmark.layer_metrics.pool_forest_s import FOREST


def read(obs):
    return scope_seconds_per_train(obs, FOREST, "fg.forest")
