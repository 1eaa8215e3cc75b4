"""Layer: tree_kernels. The regression forest's fold-grid program's share of
its roofline, in %: the least chip time of the histogram work of every (grid
point, fold) ``RandomForestRegressor`` lane at its own depth, 50 trees of
three statistic columns over the whole design's bins (a regression node
samples a third of the columns, so a tree has no pool;
``costs_pool.forest_fit_cost`` over ``benchmark/peaks.json``), over the chip
seconds a train in ``jit_forest_batched``. The whole program's share:
bootstrap, routing, split search and the validation metric included."""
from benchmark import costs, costs_pool, harness
from benchmark.layer_metrics.pool_forest_s import (
    FOREST, program_seconds_per_train)

FAMILY = "RandomForestRegressor"


def read(obs):
    seconds = program_seconds_per_train(obs, FOREST)
    lanes = (obs.get("pool_lane_shapes") or {}).get(FAMILY)
    if not seconds or not lanes:
        return None
    least = costs.least_seconds(
        costs_pool.summed([costs_pool.forest_fit_cost(**lane)
                           for lane in lanes]),
        harness.load_peaks(obs["device_kind"]))
    harness.say(f"least chip time of the {len(lanes)} regression forest "
                f"lanes {least['seconds']:.4f} s, {least['bound']}-bound "
                f"(compute {least['compute_seconds']:.4f} s, memory "
                f"{least['memory_seconds']:.4f} s); {FOREST} took "
                f"{seconds:.4f} chip seconds a train")
    return 100.0 * least["seconds"] / seconds
