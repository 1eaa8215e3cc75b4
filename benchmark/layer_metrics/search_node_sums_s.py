"""Layer: tree_kernels. Chip seconds a train, summed over the chips, in the
scope ``tree.node_sums`` of the fold-grid program ``jit_batched``: the
per-slot and per-leaf ``segment_sum``s of every lane
(``benchmark/trace/scopes.py``)."""
from benchmark.trace import scopes


def read(obs):
    return scopes.seconds_per_run(obs, "jit_batched", "tree.node_sums")
