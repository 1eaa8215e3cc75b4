"""Layer: ingest_prepare. Median over the window's trains of the listener's
stage seconds, every stage but the selector's."""
import statistics


def read(obs):
    per_train = [sum(s for name, s in r["stages"].items()
                     if "ModelSelector" not in name)
                 for r in obs["reps"] if r["ok"] and r.get("stages")]
    return statistics.median(per_train) if per_train else None
