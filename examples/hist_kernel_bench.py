"""Level-histogram strategy microbench — the hardware half of the
tree-throughput investigation.

The per-level split-search histogram is the hot op of every tree fit
(the role of libxgboost's C++ scatter-adds behind the reference's
OpXGBoostClassifier, core/build.gradle:27). ``models/trees`` implements
five mathematically-equivalent strategies (`_hist_mode`); this harness
measures all of them ON THE CURRENT BACKEND at real tree-fit shapes and
validates the Pallas kernel against the platform compiler (Mosaic on
TPU — everywhere else it has only ever met interpret mode).

  python examples/hist_kernel_bench.py                   # default backend
  TX_HKB_ROWS=1000000 python examples/hist_kernel_bench.py

Prints one JSON line per (shape, mode): warm seconds/level-call,
useful-work throughput (n*d*S scatter-adds/s), achieved contraction
FLOP/s for the matmul modes, and max|delta| vs the exact scatter
reference. Every mode runs the SAME `_level_histograms` entry the tree
kernels call, so numbers transfer directly.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    from transmogrifai_tpu.utils.jax_setup import enable_compilation_cache
    enable_compilation_cache()
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from transmogrifai_tpu.models.trees import (_bin_indicator,
                                                _level_histograms)

    platform = jax.devices()[0].platform
    n = int(os.environ.get("TX_HKB_ROWS", "200000"))
    d = int(os.environ.get("TX_HKB_FEATS", "100"))
    B = int(os.environ.get("TX_HKB_BINS", "32"))      # bins per feature
    C = int(os.environ.get("TX_HKB_SLOTS", "32"))     # active nodes
    S = 3                                             # grad/hess/count
    iters = int(os.environ.get("TX_HKB_ITERS", "10"))
    TB = d * B

    rng = np.random.default_rng(0)
    packed = (np.arange(d, dtype=np.int32)[None, :] * B
              + rng.integers(0, B, size=(n, d), dtype=np.int32))
    feat_of = np.repeat(np.arange(d, dtype=np.int32), B)
    slot = rng.integers(0, C, size=n).astype(np.int32)
    stats = rng.normal(size=(n, S)).astype(np.float32)

    packed_d = jnp.asarray(packed)
    feat_of_d = jnp.asarray(feat_of)
    slot_d = jnp.asarray(slot)
    stats_d = jnp.asarray(stats)
    # a second, distinct stats buffer: timing alternates between the
    # two so no runtime layer can serve a repeated launch from a cache
    # of identical (program, inputs)
    stats_d2 = jnp.asarray(rng.normal(size=(n, S)).astype(np.float32))

    # the (n, TB) indicator is built ONCE PER TREE in the real kernels
    # (_grow_tree), so it stays outside the per-level timing; the
    # matmul_chunk mode rebuilds per level by design and is timed so
    @functools.partial(jax.jit, static_argnames=("dt",))
    def build_oh(packed, dt):
        return _bin_indicator(packed, TB, dt, feat_of_d)

    @functools.partial(jax.jit, static_argnames=("mode",))
    def level(packed, slot, stats, oh, *, mode: str):
        return _level_histograms(packed, slot, stats, C, TB,
                                 bin_oh=oh, mode=mode,
                                 feat_of=feat_of_d)

    # useful work: every row deposits S stats into one bin per feature
    useful = n * d * S
    # matmul-strategy contraction FLOPs: 2 * n * (C*S) * TB
    mm_flops = 2.0 * n * C * S * TB

    ref = None
    rows = []
    modes = ("scatter", "matmul", "matmul_bf16", "matmul_chunk", "pallas")
    only = os.environ.get("TX_HKB_MODES")
    if only:
        modes = tuple(m for m in modes if m in only.split(","))
    for mode in modes:
        with_oh = mode in ("matmul", "matmul_bf16", "pallas")
        try:
            oh = None
            oh_build_s = None
            if with_oh:
                dt = jnp.bfloat16 if mode == "matmul_bf16" else jnp.float32
                oh = build_oh(packed_d, dt)        # cold: trace+compile
                oh.block_until_ready()
                # warm per-tree build cost: same dependency-chain +
                # final-fetch discipline as the level timing below
                pk = packed_d + oh[0, 0].astype(packed_d.dtype) * 0
                t0 = time.perf_counter()
                for _ in range(3):
                    oh = build_oh(pk, dt)
                    pk = packed_d + oh[0, 0].astype(packed_d.dtype) * 0
                float(oh[0, 0].astype(jnp.float32))
                oh_build_s = (time.perf_counter() - t0) / 3
            t0 = time.perf_counter()
            out = level(packed_d, slot_d, stats_d, oh, mode=mode)
            float(out[0, 0, 0])
            cold = time.perf_counter() - t0
            # timing: each iteration's input depends on the previous
            # output (a zero-scaled scalar), so launches cannot overlap
            # or be elided, and ONE final host fetch forces the whole
            # chain
            float(level(packed_d, slot_d, stats_d2, oh,
                        mode=mode)[0, 0, 0])
            st = stats_d
            t0 = time.perf_counter()
            for i in range(iters):
                out = level(packed_d, slot_d, st, oh, mode=mode)
                st = ((stats_d if i % 2 else stats_d2)
                      + out[0, 0, 0] * 0)
            float(out[0, 0, 0])
            warm = (time.perf_counter() - t0) / iters
        except Exception as e:
            rows.append({"mode": mode, "error": repr(e)[:300]})
            print(json.dumps(rows[-1]))
            continue
        if ref is None and mode == "scatter":
            ref = np.asarray(out, dtype=np.float64)
        delta = (float(np.max(np.abs(np.asarray(out, np.float64) - ref)))
                 if ref is not None else None)
        row = {
            "mode": mode,
            "platform": platform,
            "shape": {"n": n, "d": d, "TB": TB, "C": C, "S": S},
            "cold_s": round(cold, 3),
            "warm_s_per_level": round(warm, 5),
            "useful_adds_per_s": round(useful / warm, 1),
            "rows_per_s_per_level": round(n / warm, 1),
            "max_abs_delta_vs_scatter": delta,
        }
        if oh_build_s is not None:
            row["oh_build_s_per_tree"] = round(oh_build_s, 5)
        if with_oh or mode == "matmul_chunk":
            row["contraction_gflops_per_s"] = round(mm_flops / warm / 1e9, 1)
        rows.append(row)
        print(json.dumps(row))
    # summary line: fastest mode on this backend at this shape
    timed = [r for r in rows if "warm_s_per_level" in r]
    if timed:
        best = min(timed, key=lambda r: r["warm_s_per_level"])
        print(json.dumps({"metric": "level_hist_best_mode",
                          "platform": platform, "best": best["mode"],
                          "warm_s_per_level": best["warm_s_per_level"]}))


if __name__ == "__main__":
    main()
