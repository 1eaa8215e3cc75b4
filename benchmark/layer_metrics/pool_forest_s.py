"""Layer: tree_kernels. Chip seconds a train in the forest's fold-grid
program, ``jit_forest_batched``, from the trace's ``XLA Modules`` lane.
Trains are the repetitions that ran under the profiler, not runs of a
program (the linear program runs twice a train). None where the trace shows
no program of that name: the package then names every tree family's program
``jit_batched``, as the parent of PR 28 does."""

FOREST, GBT, LINEAR = ("jit_forest_batched", "jit_batched",
                       "jit_linear_batched")


def traced_trains(obs):
    return sum(1 for r in obs.get("reps", ()) if r["ok"] and r["traced"])


def program_seconds_per_train(obs, program):
    """Chip seconds a traced train in ``program``, or None; the boosted
    program is told from the forest's only where the forest has its own
    name."""
    programs = {p[0]: p[1] for p in (obs.get("trace") or {}).get(
        "programs", ())}
    trains = traced_trains(obs)
    if not trains or program not in programs or FOREST not in programs:
        return None
    return programs[program] / trains


def read(obs):
    return program_seconds_per_train(obs, FOREST)
