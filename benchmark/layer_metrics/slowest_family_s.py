"""Layer: search. Wall seconds on the slowest family's dispatch thread, per
train: ``selector.validator.family_profile()`` over the window, its slowest
row divided by the trains. Family threads overlap, so the rows do not add up
to a train; the slowest one bounds it from below."""


def read(obs):
    rows, trains = obs.get("family_profile"), sum(r["ok"] for r in obs["reps"])
    if not rows or not trains:
        return None
    return max(row["seconds"] for row in rows) / trains
