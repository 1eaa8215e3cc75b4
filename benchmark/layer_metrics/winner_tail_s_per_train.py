"""Layer: search. Median over the window's trains of the seconds the host
spent after the search proper: the winner's refit (span ``search.refit``) and
its evaluation on the training rows (``search.train_eval``), read in-process
from the package's own spans (``observability.trace.spans()`` keeps them
after ``configure(False)``). On several chips this tail runs on one."""
import statistics
from typing import Dict, Iterable, List, Optional, Sequence

NAMES = ("search.refit", "search.train_eval")


def per_train_median(records: Iterable[Dict], names: Sequence[str]
                     ) -> Optional[float]:
    """Median over the ``train`` spans of the summed seconds of their
    descendants named in ``names``; None without a ``train`` span or without
    any such descendant."""
    records = [r for r in records if r.get("dur") is not None]
    by_sid = {r["sid"]: r for r in records}
    totals = {r["sid"]: 0.0 for r in records if r["name"] == "train"}
    found = False
    for record in records:
        if record["name"] not in names:
            continue
        up = by_sid.get(record.get("parent"))
        while up is not None and up["name"] != "train":
            up = by_sid.get(up.get("parent"))
        if up is not None:
            totals[up["sid"]] += record["dur"]
            found = True
    return statistics.median(totals.values()) if found else None


def package_spans() -> List[Dict]:
    from transmogrifai_tpu.observability import trace
    return trace.spans()


def read(obs):
    return per_train_median(package_spans(), NAMES)
