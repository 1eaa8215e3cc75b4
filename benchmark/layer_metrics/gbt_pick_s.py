"""Layer: tree_kernels. Chip seconds a train, summed over the chips, in the
scope ``gbt.pick`` of the fold-grid program ``jit_batched``: every boosting
round's read of its rows' leaf values, added to their margins
(``benchmark/trace/scopes.py``; trains = runs of the program / devices, as
``fold_grid_roofline.py`` counts them). None where the package has no such
scope (a package whose read lies inside ``gbt.round``)."""
from benchmark.trace import scopes


def read(obs):
    return scopes.seconds_per_run(obs, "jit_batched", "gbt.pick")
