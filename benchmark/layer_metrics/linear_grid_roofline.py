"""Layer: linear_solvers. The linear fold-grid programs' share of their
roofline, in %: the least chip time of the solver steps of every logistic
and SVC lane, each family's lanes sweeping the shared matrix once a step
(``benchmark/costs_pool.py`` over ``benchmark/peaks.json``), over the chip
seconds a train in ``jit_linear_batched``. The whole programs' share:
standardization, the power iteration and the validation metric included."""
from benchmark import costs, costs_pool, harness
from benchmark.layer_metrics.pool_forest_s import (
    LINEAR, program_seconds_per_train)

FAMILIES = ("LogisticRegression", "LinearSVC")


def read(obs):
    seconds = program_seconds_per_train(obs, LINEAR)
    shapes = obs.get("pool_lane_shapes") or {}
    if not seconds or not all(shapes.get(f) for f in FAMILIES):
        return None
    least = costs.least_seconds(
        costs_pool.summed([costs_pool.linear_grid_cost(
            shapes[f], obs["matrix_rows"]) for f in FAMILIES]),
        harness.load_peaks(obs["device_kind"]))
    harness.say(f"least chip time of the "
                f"{sum(len(shapes[f]) for f in FAMILIES)} linear lanes "
                f"{least['seconds']:.4f} s, {least['bound']}-bound (compute "
                f"{least['compute_seconds']:.4f} s, memory "
                f"{least['memory_seconds']:.4f} s); {LINEAR} took "
                f"{seconds:.4f} chip seconds a train")
    return 100.0 * least["seconds"] / seconds
