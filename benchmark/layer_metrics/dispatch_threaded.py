"""Layer: search. 1 where every train of the traced window dispatched its
families through the thread pool, 0 where any took them in sequence: the
``threaded`` attribute of the package's ``search.dispatch`` spans, read
in-process as ``winner_tail_s_per_train.py`` reads its spans. None where the
package has no such span."""
from benchmark.layer_metrics.winner_tail_s_per_train import package_spans


def read(obs):
    took = [s["attrs"].get("threaded") for s in package_spans()
            if s["name"] == "search.dispatch" and s.get("dur") is not None]
    if not took or None in took:
        return None
    return float(all(took))
