"""Layer: tree_kernels. The boosted-fit program's share of its roofline, in %:
the least time the chip could take for the fit's histogram work
(``benchmark/costs.py`` over ``benchmark/peaks.json``) over the seconds the
device spent in the program ``jit__fit_gbt`` per run, from the trace. It is
the whole program's share, routing and split search included: no kernel
inside it can be found by name in the trace yet."""
from benchmark import costs, harness

PROGRAM = "jit__fit_gbt"


def read(obs):
    ran = [p for p in (obs.get("trace") or {}).get("programs", [])
           if p[0] == PROGRAM]
    if not ran or not obs.get("fit_shape"):
        return None
    _, seconds, runs = ran[0]
    least = costs.least_seconds(costs.gbt_fit_cost(**obs["fit_shape"]),
                                harness.load_peaks(obs["device_kind"]))
    harness.say(f"least time of a fit {least['seconds']:.4f} s, "
                f"{least['bound']}-bound (compute "
                f"{least['compute_seconds']:.4f} s, memory "
                f"{least['memory_seconds']:.4f} s); {PROGRAM} took "
                f"{seconds / runs:.4f} device seconds a run over {runs} runs")
    return 100.0 * least["seconds"] * runs / seconds
