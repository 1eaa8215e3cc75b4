"""Layer: process_setup. Seconds the set-up spent retrieving executables from
the persistent compile cache: the sum of ``retrieval_s`` over the set-up's
records that the cache served (``cache == "hit"``; ``_setup_log.py``). It is
inside ``compile_s`` (a load is a backend event too): on a cached start most
of it, on a checkout's first run 0. None where the log cannot be read."""
from benchmark.layer_metrics import _setup_log


def read(obs):
    return _setup_log.total(obs, ("retrieval_s",), cache="hit")
