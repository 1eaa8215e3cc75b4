"""Layer: tree_kernels. Chip seconds a train in the scope ``fg.metric``
(validation scores + metric) summed over the three fold-grid programs, per
traced train (``benchmark/trace/scopes.py``). None where none of them shows
the scope."""
from benchmark.layer_metrics.pool_forest_s import (
    FOREST, GBT, LINEAR, traced_trains)
from benchmark.trace import scopes

SCOPE = "fg.metric"


def read(obs):
    trains = traced_trains(obs)
    if not trains or not (obs.get("trace") or {}).get("devices"):
        return None
    table = scopes.table() or {}
    found = [table[p]["by_scope"][SCOPE] for p in (FOREST, GBT, LINEAR)
             if p in table and SCOPE in table[p]["by_scope"]]
    if FOREST not in table or not found:
        return None
    return sum(found) / trains
