"""Layer: linear_solvers. The IRLS fold-grid programs' share of their
roofline, in %: the least chip time of the Gram products of every lane at the
iterations its program ran, a family's lanes sweeping the shared matrix once
an iteration (``benchmark/costs_reg.py`` over ``benchmark/peaks.json``), over
the chip seconds a train in ``jit_glm_batched``. The whole programs' share:
standardization, the start, the solves and the validation metric included.
None where the job recorded no ``irls_iterations`` (the package's
``search.fetch`` spans of the program): at ``max_iter`` the share could pass
100 %. The log line names the bound."""
from benchmark import costs, costs_reg, harness
from benchmark.layer_metrics.reg_glm_s import GLM, program_seconds_per_train

FAMILY = "GeneralizedLinearRegression"


def read(obs):
    seconds = program_seconds_per_train(obs, GLM)
    lanes = (obs.get("pool_lane_shapes") or {}).get(FAMILY)
    if not seconds or not lanes or not obs.get("glm_calls"):
        return None
    cost = costs_reg.glm_grid_cost(lanes, obs["glm_calls"],
                                   obs["matrix_rows"])
    if cost is None:
        return None
    least = costs.least_seconds(cost, harness.load_peaks(obs["device_kind"]))
    harness.say(f"least chip time of the {len(lanes)} IRLS lanes at "
                f"{costs_reg.family_iterations(obs['glm_calls'])} iterations "
                f"{least['seconds']:.4f} s, {least['bound']}-bound (compute "
                f"{least['compute_seconds']:.4f} s, memory "
                f"{least['memory_seconds']:.4f} s); {GLM} took "
                f"{seconds:.4f} chip seconds a train")
    return 100.0 * least["seconds"] / seconds
