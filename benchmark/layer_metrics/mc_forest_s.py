"""Layer: tree_kernels. Chip seconds a train in ``jit_forest_batched`` in a
pool that runs the forest AND the single decision tree through it (the
multiclass default pool: two runs a train, the forest's 54 lanes of 50 trees
and the tree's 54 lanes of one), from the trace's ``XLA Modules`` lane, per
traced train. None where the trace shows no program of that name."""
from benchmark.layer_metrics.mc_softmax_s import program_seconds_per_train
from benchmark.layer_metrics.pool_forest_s import FOREST


def read(obs):
    return program_seconds_per_train(obs, FOREST)
