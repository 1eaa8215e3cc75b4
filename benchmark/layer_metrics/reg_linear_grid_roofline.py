"""Layer: linear_solvers. The squared lanes' fold-grid program's share of its
roofline, in %: the least chip time of the solver steps of every
``LinearRegression`` lane, the lanes sweeping the shared matrix once a step
(``costs_pool.linear_grid_cost`` over ``benchmark/peaks.json``), over the chip
seconds a train in ``jit_linear_batched``. The whole program's share:
standardization, the power iteration and the validation metric included."""
from benchmark import costs, costs_pool, harness
from benchmark.layer_metrics.pool_forest_s import (
    LINEAR, program_seconds_per_train)

FAMILY = "LinearRegression"


def read(obs):
    seconds = program_seconds_per_train(obs, LINEAR)
    lanes = (obs.get("pool_lane_shapes") or {}).get(FAMILY)
    if not seconds or not lanes:
        return None
    least = costs.least_seconds(
        costs_pool.linear_grid_cost(lanes, obs["matrix_rows"]),
        harness.load_peaks(obs["device_kind"]))
    harness.say(f"least chip time of the {len(lanes)} squared lanes "
                f"{least['seconds']:.4f} s, {least['bound']}-bound (compute "
                f"{least['compute_seconds']:.4f} s, memory "
                f"{least['memory_seconds']:.4f} s); {LINEAR} took "
                f"{seconds:.4f} chip seconds a train")
    return 100.0 * least["seconds"] / seconds
