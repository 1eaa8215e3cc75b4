"""Layer: process_setup. How far the set-up's programs overlap ON THE HOST:
the sum of ``t1 - t0`` (a program's own trace, or its lowering, to the end of
its backend event) over the set-up's records, over the length of the union of
those intervals (``_setup_log.py``). 1.0 = the threads took turns, so the
first train pays the SUM of the families' host phases; 2.0 = two programs in
the making all the time. What ``family_overlap`` says of the chip, said of the
start-up the family threads were added for. None where the log cannot be
read."""
from benchmark.layer_metrics import _setup_log


def read(obs):
    return _setup_log.overlap(obs)
