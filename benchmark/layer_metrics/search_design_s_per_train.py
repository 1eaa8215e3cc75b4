"""Layer: search. Median over the window's trains of the seconds a family's
dispatch thread spent making its design (span ``search.design``: the memoized
``_design_args``, which bins the prepared matrix on one device) before the
fold-grid program was enqueued, from the package's own spans."""
from benchmark.layer_metrics.winner_tail_s_per_train import (
    package_spans, per_train_median)


def read(obs):
    return per_train_median(package_spans(), ("search.design",))
