"""Layer: tree_kernels. The selector's fold-grid program's share of its
roofline, in %: the least chip time of the histogram work of every (grid
point, fold) lane at the lane's own depth (``benchmark/costs.py`` over
``benchmark/peaks.json``) over the chip seconds the trace shows in the program
``jit_batched`` per train, summed over the chips. Every family's fold-grid
program bears that one name, so the reader is right only where one family is
searched; and it is the whole program's share, routing, split search and the
validation metric included."""
from benchmark import costs, harness

PROGRAM = "jit_batched"


def read(obs):
    trace = obs.get("trace") or {}
    ran = [p for p in trace.get("programs", []) if p[0] == PROGRAM]
    if not ran or not obs.get("lane_shapes"):
        return None
    _, chip_seconds, runs = ran[0]
    trains = runs / len(trace["devices"])
    lanes = [costs.gbt_fit_cost(**shape) for shape in obs["lane_shapes"]]
    least = costs.least_seconds(
        {key: sum(lane[key] for lane in lanes) for key in ("flops", "bytes")},
        harness.load_peaks(obs["device_kind"]))
    harness.say(f"least chip time of the {len(lanes)} lanes "
                f"{least['seconds']:.4f} s, {least['bound']}-bound (compute "
                f"{least['compute_seconds']:.4f} s, memory "
                f"{least['memory_seconds']:.4f} s); {PROGRAM} took "
                f"{chip_seconds / trains:.4f} chip seconds a train")
    return 100.0 * least["seconds"] * trains / chip_seconds
