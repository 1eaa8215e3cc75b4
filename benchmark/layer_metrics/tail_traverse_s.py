"""Layer: tree_kernels. Chip seconds a traced train, summed over the chips,
in the scope ``tree.traverse`` of the scoring program ``jit__predict_leaves``:
the refitted winner's walks down its heaps (``search.train_eval`` scores the
training rows, then ``Workflow.train()`` transforms them through the
``SelectedModel``). Divided by the traced trains, not by runs over devices:
the program runs twice a train, on one chip of four under a mesh. None
without a traced train, without the program, or without the scope (a
package before PR 40)."""
from benchmark.layer_metrics.pool_forest_s import traced_trains
from benchmark.trace import scopes

PROGRAM, SCOPE = "jit__predict_leaves", "tree.traverse"


def read(obs):
    trains = traced_trains(obs)
    if not trains or not (obs.get("trace") or {}).get("devices"):
        return None
    row = (scopes.table() or {}).get(PROGRAM)
    if not row or SCOPE not in row["by_scope"]:
        return None
    return row["by_scope"][SCOPE] / trains
