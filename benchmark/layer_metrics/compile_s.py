"""Layer: process_setup. Seconds of XLA backend compilation (or of loading
the same programs from the persistent cache) before the window opened, from
``jax.monitoring``. Most of ``setup_s`` on a checkout's first run."""


def read(obs):
    return obs["setup_compiles"][1]
