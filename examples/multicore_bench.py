"""Flagship search on MULTI-CORE XLA-CPU — the honest host baseline.

The north-star target (BASELINE.json) is ">= 20x wall-clock vs 32-core
CPU Spark"; every historical CPU figure is single-core because
the build container exposes exactly one core (``nproc`` = 1), which
flatters per-chip ratios. This harness produces the missing multi-core
number on any machine that has the cores:

  python examples/multicore_bench.py            # uses all visible cores
  TX_CORES=8 python examples/multicore_bench.py # cap the device count

It provisions one XLA-CPU device PER CORE (``jax_num_cpu_devices``),
builds the production ("models", "data") mesh, and runs the SAME
Titanic default-pool search ``chip_smoke.py`` trains. The printed
models x folds/s is a CPU figure: it says nothing about the chip (the
benchmark's cells do, ``PERF.md``). On a 1-core host it still runs but clearly labels the result
single-core (no false multi-core claim).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    cores = len(os.sched_getaffinity(0))
    want = int(os.environ.get("TX_CORES", cores))
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    try:
        import jax.extend.backend as jax_backend
        jax_backend.clear_backends()
    except Exception:
        pass
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", want)
    from transmogrifai_tpu.utils.jax_setup import enable_compilation_cache
    enable_compilation_cache()
    n_dev = len(jax.devices())

    from examples.titanic import default_selector, run
    from transmogrifai_tpu.parallel.cv import models_mesh
    from transmogrifai_tpu.selector.selector import models_x_folds

    mesh = None
    if n_dev > 1:
        # candidates shard over `models`; favor a wide models axis
        data = 2 if n_dev % 2 == 0 and n_dev >= 8 else 1
        mesh = models_mesh(data_shards=data)
    selector = default_selector()
    if mesh is not None:
        selector.validator.mesh = mesh

    t0 = time.perf_counter()
    metrics, fit_seconds, model = run(model_stage=selector, verbose=False)
    total = time.perf_counter() - t0
    n_candidates = models_x_folds(model)
    print(json.dumps({
        "metric": "titanic_multicore_models_x_folds_per_sec",
        "value": round(n_candidates / max(fit_seconds, 1e-9), 3),
        "unit": "models_x_folds/s",
        "physical_cores": cores,
        "xla_cpu_devices": n_dev,
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "holdout_aupr": round(float(metrics.AuPR), 4),
        "train_eval_seconds": round(fit_seconds, 2),
        "total_seconds": round(total, 2),
        "single_core_host": cores == 1,
    }))


if __name__ == "__main__":
    main()
