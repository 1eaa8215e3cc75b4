"""Layer: tree_kernels. The forest's fold-grid program's share of its
roofline, in %: the least chip time of the histogram work of every (grid
point, fold) forest lane at its own depth, 50 trees over the pooled bins
(``benchmark/costs_pool.py`` over ``benchmark/peaks.json``), over the chip
seconds a train in ``jit_forest_batched``. The whole program's share:
bootstrap, pools, routing, split search and the validation metric
included."""
from benchmark import costs, costs_pool, harness
from benchmark.layer_metrics.pool_forest_s import (
    FOREST, program_seconds_per_train)


def read(obs):
    seconds = program_seconds_per_train(obs, FOREST)
    lanes = (obs.get("pool_lane_shapes") or {}).get("RandomForestClassifier")
    if not seconds or not lanes:
        return None
    least = costs.least_seconds(
        costs_pool.summed([costs_pool.forest_fit_cost(**lane)
                           for lane in lanes]),
        harness.load_peaks(obs["device_kind"]))
    harness.say(f"least chip time of the {len(lanes)} forest lanes "
                f"{least['seconds']:.4f} s, {least['bound']}-bound (compute "
                f"{least['compute_seconds']:.4f} s, memory "
                f"{least['memory_seconds']:.4f} s); {FOREST} took "
                f"{seconds:.4f} chip seconds a train")
    return 100.0 * least["seconds"] / seconds
