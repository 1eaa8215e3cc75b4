"""Layer: linear_solvers. Chip seconds a train in the IRLS fold-grid program,
``jit_glm_batched`` (two runs a train: the gaussian and the poisson lanes of
the regression pool's ``GeneralizedLinearRegression``), from the trace's ``XLA
Modules`` lane, per traced train. None where the trace shows no program of
that name: the package then has none, as the parent of PR 34, whose IRLS
lanes run as ``jit__eval_glm_folds``."""
from benchmark.layer_metrics.mc_softmax_s import program_seconds_per_train

GLM = "jit_glm_batched"


def read(obs):
    return program_seconds_per_train(obs, GLM)
