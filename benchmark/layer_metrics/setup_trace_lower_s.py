"""Layer: process_setup. Seconds the set-up's programs spent being traced
and lowered on the host, before the backend saw them: the sum of ``trace_s +
lower_s`` over the set-up's records of the package's compile log
(``_setup_log.py``). A program's ``trace_s`` is its outermost trace only, so
nested jitted functions are not counted twice. The cache serves none of it:
every process pays it again. None where the log cannot be read."""
from benchmark.layer_metrics import _setup_log


def read(obs):
    return _setup_log.total(obs, ("trace_s", "lower_s"))
