"""Layer: process_setup. Programs that entered the process before the
window (compiled, or loaded from the persistent cache): the set-up's records
of the package's compile log, which the harness's own count of backend events
cuts (``_setup_log.py``). None where the log cannot be read."""
from benchmark.layer_metrics import _setup_log


def read(obs):
    records = _setup_log.records(obs)
    return None if records is None else len(records)
