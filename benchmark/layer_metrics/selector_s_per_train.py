"""Layer: search. Median over the window's trains of the listener's seconds
for the ModelSelector stage (fit and transform): the search and the winner's
refit, as the host sees them."""
import statistics


def read(obs):
    per_train = [sum(s for name, s in r["stages"].items()
                     if "ModelSelector" in name)
                 for r in obs["reps"] if r["ok"] and r.get("stages")]
    return statistics.median(per_train) if per_train else None
