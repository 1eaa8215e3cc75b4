"""Layer: ingest_prepare. Median over the window's trains of the seconds the
prepare plan spent turning typed host columns into arrays: the package's
``prepare.encode`` spans (the vocabulary fits of the Integral and PickList
vectorizers, ``phase: fit``, and the encoders of a segment's host inputs,
``phase: encode``), summed under each ``train`` span, read in-process as
``winner_tail_s_per_train.py`` reads its spans. None where the package has
no such span."""
from benchmark.layer_metrics.winner_tail_s_per_train import (
    package_spans, per_train_median)


def read(obs):
    return per_train_median(package_spans(), ("prepare.encode",))
