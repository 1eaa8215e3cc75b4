"""Layer: tree_kernels. Share of the device's busy seconds in the traced fits
that fell outside the boosted-fit program ``jit__fit_gbt``: binning the design
(sort, digitize, edge gather) and the small programs around it."""
from benchmark.layer_metrics.fit_gbt_roofline import PROGRAM


def read(obs):
    trace = obs.get("trace") or {}
    ran = [p for p in trace.get("programs", []) if p[0] == PROGRAM]
    busy = sum(d["busy_s"] for d in trace.get("devices", []))
    if not ran or not busy:
        return None
    return 1.0 - ran[0][1] / busy
