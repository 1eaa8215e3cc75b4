"""Layer: linear_solvers. Chip seconds a train in the multinomial logistic
fold-grid program, ``jit_softmax_batched`` (one run a train: every (fold, grid
point) lane of the multiclass ``LogisticRegression``), from the trace's ``XLA
Modules`` lane, per traced train. None where the trace shows no program of
that name: the package then has no such program, as the parent of PR 32,
whose multiclass logistic lanes run one after another on the validator's host
path."""
from benchmark.layer_metrics.pool_forest_s import traced_trains

SOFTMAX = "jit_softmax_batched"


def program_seconds_per_train(obs, program):
    """Chip seconds a traced train in ``program``, or None."""
    programs = {p[0]: p[1] for p in (obs.get("trace") or {}).get(
        "programs", ())}
    trains = traced_trains(obs)
    if not trains or program not in programs:
        return None
    return programs[program] / trains


def read(obs):
    return program_seconds_per_train(obs, SOFTMAX)
