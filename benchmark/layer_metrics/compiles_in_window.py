"""Layer: process_setup. Executables that entered the process inside the
measured window (a compile, or a load from the persistent cache). Must be 0:
a run with any is not ``correct``, because its window timed set-up."""


def read(obs):
    return obs["window_compiles"][0]
