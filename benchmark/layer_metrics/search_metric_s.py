"""Layer: tree_kernels. Chip seconds a train, summed over the chips, in the
scope ``fg.metric`` of the fold-grid program ``jit_batched``: the validation
scores and the metric of every boosted candidate (a second walk of the
validation rows down the finished trees where the program holds one;
``benchmark/trace/scopes.py``)."""
from benchmark.trace import scopes


def read(obs):
    return scopes.seconds_per_run(obs, "jit_batched", "fg.metric")
