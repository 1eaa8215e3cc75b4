"""Layer: tree_kernels. Chip seconds a train in the scope ``tree.hist`` of
``jit_forest_batched`` (both of its runs a train: the forest's lanes and the
single tree's): the per-level histograms of every lane, one statistic column a
class, which is where the class count shows first
(``benchmark/trace/scopes.py``). Per traced TRAIN, as ``pool_metric_s.py``
counts, not per run of the program. None where the trace shows no program of
that name or no such scope in it."""
from benchmark.layer_metrics.pool_forest_s import FOREST, traced_trains
from benchmark.trace import scopes


def scope_seconds_per_train(obs, program, scope):
    trains = traced_trains(obs)
    if not trains or not (obs.get("trace") or {}).get("devices"):
        return None
    row = (scopes.table() or {}).get(program)
    if not row or scope not in row["by_scope"]:
        return None
    return row["by_scope"][scope] / trains


def read(obs):
    return scope_seconds_per_train(obs, FOREST, "tree.hist")
