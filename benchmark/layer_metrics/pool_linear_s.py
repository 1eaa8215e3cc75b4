"""Layer: linear_solvers. Chip seconds a train in the linear fold-grid
programs, ``jit_linear_batched`` (the logistic lanes' run and the SVC lanes'
run together), per traced train."""
from benchmark.layer_metrics.pool_forest_s import (
    LINEAR, program_seconds_per_train)


def read(obs):
    return program_seconds_per_train(obs, LINEAR)
