"""Layer: linear_solvers. The multinomial logistic fold-grid program's share
of its roofline, in %: the least chip time of the solver steps of every lane,
the lanes sweeping the shared matrix once a step
(``benchmark/costs_mc.py`` over ``benchmark/peaks.json``), over the chip
seconds a train in ``jit_softmax_batched``. The whole program's share:
standardization, the power iteration, the softmax and the validation metric
included. The log line names the bound."""
from benchmark import costs, costs_mc, harness
from benchmark.layer_metrics.mc_softmax_s import (
    SOFTMAX, program_seconds_per_train)

FAMILY = "LogisticRegression"


def read(obs):
    seconds = program_seconds_per_train(obs, SOFTMAX)
    lanes = (obs.get("pool_lane_shapes") or {}).get(FAMILY)
    if not seconds or not lanes or "classes" not in lanes[0]:
        return None
    least = costs.least_seconds(
        costs_mc.softmax_grid_cost(lanes, obs["matrix_rows"]),
        harness.load_peaks(obs["device_kind"]))
    harness.say(f"least chip time of the {len(lanes)} softmax lanes "
                f"{least['seconds']:.4f} s, {least['bound']}-bound (compute "
                f"{least['compute_seconds']:.4f} s, memory "
                f"{least['memory_seconds']:.4f} s); {SOFTMAX} took "
                f"{seconds:.4f} chip seconds a train")
    return 100.0 * least["seconds"] / seconds
