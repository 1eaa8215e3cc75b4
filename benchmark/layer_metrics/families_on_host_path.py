"""Layer: search. Families a train that the validator evaluated on its host
path (one fit after another, each followed by a NumPy evaluation) because the
family has no fold-grid device program for the search: the package's telemetry
counter ``host_path_families``, as the job read it around every train of the
window (``observations["reps"]``), at its largest. 0 where every family of
the pool ran as a device program. None where the job read no counter (the
parent of PR 32 keeps none; the job refuses such a package before it compiles
anything, and its ``search.family`` spans with ``path: "host"`` said the same
when it was tried once, PERF.md)."""


def read(obs):
    counted = [r.get("host_path_families") for r in obs.get("reps", ())
               if r.get("ok")]
    if not counted or None in counted:
        return None
    return float(max(counted))
