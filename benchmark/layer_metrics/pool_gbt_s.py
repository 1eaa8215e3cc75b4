"""Layer: tree_kernels. Chip seconds a train in the boosted trees'
fold-grid program, ``jit_batched``, per traced train. None unless the
forest's program shows under its own name beside it (else ``jit_batched``
is both tree families')."""
from benchmark.layer_metrics.pool_forest_s import (
    GBT, program_seconds_per_train)


def read(obs):
    return program_seconds_per_train(obs, GBT)
