"""Layer: tree_kernels. Chip seconds a train, summed over the chips, in the
scope ``tree.compress`` of the fold-grid program ``jit_batched``: active-node
slot compression, which the levels past the node cap pay (a sort over the
rows of every lane; ``benchmark/trace/scopes.py``)."""
from benchmark.trace import scopes


def read(obs):
    return scopes.seconds_per_run(obs, "jit_batched", "tree.compress")
