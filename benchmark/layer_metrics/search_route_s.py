"""Layer: tree_kernels. Chip seconds a train, summed over the chips, in the
scope ``tree.route`` of the fold-grid program ``jit_batched``: the per-level
row routing of every lane (``benchmark/trace/scopes.py``; trains = runs of
the program / devices, as ``fold_grid_roofline.py`` counts them)."""
from benchmark.trace import scopes


def read(obs):
    return scopes.seconds_per_run(obs, "jit_batched", "tree.route")
