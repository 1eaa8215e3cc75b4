"""Layer: ingest_prepare. Median over the window's trains of the bytes the
prepare plan and the checkers read back from the device (``np.asarray`` of a
device array there): the package's telemetry counter
``prepare_host_pull_bytes``, as the job read it around every train
(``observations["reps"]``). None where the job read no counter (a package
without it)."""
import statistics


def read(obs):
    counted = [r.get("prepare_host_pull_bytes") for r in obs.get("reps", ())
               if r.get("ok")]
    if not counted or None in counted:
        return None
    return float(statistics.median(counted))
