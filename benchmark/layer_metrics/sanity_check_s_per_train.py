"""Layer: ingest_prepare. Median over the window's trains of the listener's
seconds for the SanityChecker stage (its fit and its transform): the
moments, label correlations and contingency tables of every design column,
and the group logic on the host. None where no train had the stage."""
import statistics


def read(obs):
    per_train = [sum(s for name, s in r["stages"].items()
                     if "SanityChecker" in name)
                 for r in obs.get("reps", ()) if r["ok"] and r.get("stages")
                 and any("SanityChecker" in name for name in r["stages"])]
    return statistics.median(per_train) if per_train else None
