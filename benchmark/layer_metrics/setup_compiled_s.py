"""Layer: process_setup. Seconds of ``compile_s`` the persistent cache did
NOT serve: the sum of ``backend_s`` over the set-up's records with ``cache ==
"compiled"`` (``_setup_log.py``). On a checkout's first run all of
``compile_s``; on a cached start an evicted entry, or a program under the
cache's least compile time (0.5 s, ``utils/jax_setup.py``), which every
process compiles again. What tells a ``setup_s`` that rose because an entry
was evicted from one that rose because a program grew. None where the log
cannot be read."""
from benchmark.layer_metrics import _setup_log


def read(obs):
    return _setup_log.total(obs, ("backend_s",), cache="compiled")
