"""Pallas TPU kernel: fused level-histogram accumulation for tree fits.

Computes the per-level split-search tensor

    hist[c, b, s] = sum_r [slot_r == c] * stats[r, s] * bin_oh[r, b]

— the hot op of histogram tree growth (SURVEY.md §2.9: the reference's
XGBoost dependency builds the same (node, bin, stat) tensor with native
C++ scatter-adds inside libxgboost; here it is a TPU kernel instead).

The XLA "matmul" strategy in ``models/trees.py`` expresses this as one
einsum, which materializes the (n, C*S) slot-weighted stats intermediate
in HBM every level and streams the (n, TB) bin indicator past it. This
kernel fuses both contractions into a single pass over row blocks:

  - the (S * C_pad, TB_tile) accumulator lives in VMEM for the whole
    row loop (grid iterates row blocks fastest, so the revisited
    output block never leaves the chip);
  - each step builds the slot one-hot for its row block on the VPU
    (iota compare — no scatter) and issues one MXU contraction per
    statistic: ``(slot_oh * stats[:, s])^T @ bin_oh_block`` into the
    s-th accumulator row block (the S axis is statically unrolled —
    see _hist_kernel for why no (R, C*S) interleaved operand exists);
  - nothing of size O(n * C) ever touches HBM.

Numerics match the einsum: float32 operands, float32 MXU accumulation,
identical row-major summation order per (c, b, s) cell up to XLA's own
dot reassociation (same guarantee the matmul strategy gives).

The kernel compiles through Mosaic, i.e. on a TPU only; tests pass
``interpret=True`` to run it under the Pallas interpreter on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pallas_level_hist"]

#: rows per grid step — one (R, TB_tile) indicator block + two
#: (R, C_pad) temporaries (slot one-hot, per-s product) in VMEM per step
_ROW_BLOCK = 512
#: packed-bin tile width (lane-aligned); TB above this adds grid steps
_TB_TILE = 2048
#: VMEM working-set budget (bytes): accumulator + double-buffered input
#: blocks must fit well under the ~16 MB/core VMEM
_VMEM_BUDGET = 8 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _plan_tiles(CS_pad: int, S: int, TB: int):
    """(R, TB_tile) such that the VMEM working set
    acc(CS_pad x TB_tile) + 2x double-buffered inputs
    (R x TB_tile indicator, R x CS_pad/S one-hot + per-s product,
    R x (S+1) stats+slot) stays under _VMEM_BUDGET; None if no tiling
    fits (huge C*S — the caller falls back to the XLA einsum, which
    HBM-streams instead). ``CS_pad`` is the accumulator height
    S * C_pad (s-major row blocks, see _hist_kernel)."""
    R, TB_tile = _ROW_BLOCK, min(_round_up(TB, 128), _TB_TILE)
    C_pad = CS_pad // S

    def fits(r, tbt):
        # acc + double-buffered inputs (indicator, stats+slot) + the
        # kernel's two (R, C_pad) temporaries (slot one-hot, per-s
        # product) — the unrolled kernel never materializes (R, CS_pad)
        return 4 * (CS_pad * tbt + 2 * r * (tbt + S + 1)
                    + 2 * r * C_pad) <= _VMEM_BUDGET

    while not fits(R, TB_tile) and TB_tile > 128:
        TB_tile //= 2
    while not fits(R, TB_tile) and R > 128:
        R //= 2
    return (R, TB_tile) if fits(R, TB_tile) else None


def _hist_kernel(slot_ref, stats_ref, binoh_ref, out_ref, *,
                 C_pad: int):
    """One (TB tile, row block) grid step; row blocks iterate fastest so
    ``out_ref`` stays VMEM-resident while a tile accumulates.

    The per-stat contractions are unrolled over the (tiny, static) S
    axis: ``comb_s = slot_oh * stats[:, s]`` then one MXU dot per s
    into the ``[s*C_pad, (s+1)*C_pad)`` row block of the accumulator.
    The unrolled form needs no (R, C*S) interleaved operand (a 3D
    broadcast-multiply + reshape, which Mosaic would have to re-tile)
    and runs the same MXU contractions."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    stats = stats_ref[:]                       # (R, S) f32
    R, S = stats.shape
    cls = jax.lax.broadcasted_iota(jnp.int32, (R, C_pad), 1)
    # slots are < C, so the C..C_pad padding columns are zero for free
    slot_oh = (cls == slot_ref[:]).astype(stats.dtype)      # (R, C_pad)
    binoh = binoh_ref[:]
    for s in range(S):                         # static unroll (S <= 4)
        comb = slot_oh * stats[:, s][:, None]               # (R, C_pad)
        out_ref[s * C_pad:(s + 1) * C_pad, :] += jax.lax.dot_general(
            comb, binoh,
            (((0,), (0,)), ((), ())),          # contract over rows
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_slots", "interpret"))
def pallas_level_hist(bin_oh: jnp.ndarray, slot: jnp.ndarray,
                      stats: jnp.ndarray, num_slots: int,
                      interpret: bool = False) -> jnp.ndarray:
    """(num_slots, TB, S) histograms from a (n, TB) 0/1 bin indicator,
    (n,) slot ids and (n, S) per-row statistics.

    Drop-in replacement for the einsum in
    ``models.trees._level_histograms`` (matmul strategy); selected there
    via ``TX_TREE_HIST=pallas``. ``interpret`` is for tests only.
    """
    n, TB = bin_oh.shape
    S = stats.shape[1]
    C = int(num_slots)
    if stats.dtype == jnp.float64:
        # the kernel accumulates in f32 (MXU-native); under
        # jax_enable_x64 that would silently downgrade split-search
        # precision vs the scatter/matmul strategies, breaking the
        # "mathematically identical strategies" contract of
        # _level_histograms — stream the f64 case via the XLA einsum
        slot_oh = jax.nn.one_hot(slot, C, dtype=stats.dtype)
        return jnp.einsum("nc,ns,nb->cbs", slot_oh, stats,
                          bin_oh.astype(stats.dtype))

    C_pad = _round_up(C, 8)
    CS_pad = C_pad * S
    plan = _plan_tiles(CS_pad, S, TB)
    if plan is None:  # pragma: no cover - needs enormous C*S
        # accumulator cannot fit VMEM at any tile size: stream via the
        # mathematically identical XLA einsum instead of failing Mosaic
        slot_oh = jax.nn.one_hot(slot, C, dtype=stats.dtype)
        return jnp.einsum("nc,ns,nb->cbs", slot_oh, stats, bin_oh)
    R, TB_tile = plan
    if n < R:
        R = _round_up(max(n, 8), 8)
    n_pad = _round_up(n, R)
    TB_pad = _round_up(_round_up(TB, 128), TB_tile)

    f32 = jnp.float32
    bin_oh = bin_oh.astype(f32)
    stats = stats.astype(f32)
    if TB_pad != TB:
        bin_oh = jnp.pad(bin_oh, ((0, 0), (0, TB_pad - TB)))
    if n_pad != n:
        # zero stats rows contribute nothing whatever their slot/bin
        bin_oh = jnp.pad(bin_oh, ((0, n_pad - n), (0, 0)))
        stats = jnp.pad(stats, ((0, n_pad - n), (0, 0)))
        slot = jnp.pad(slot, (0, n_pad - n))
    slot2d = slot.astype(jnp.int32)[:, None]               # (n_pad, 1)

    grid = (TB_pad // TB_tile, n_pad // R)
    vmem = pl.ANY if interpret else pltpu.VMEM
    out = pl.pallas_call(
        functools.partial(_hist_kernel, C_pad=C_pad),
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, 1), lambda i, j: (j, 0), memory_space=vmem),
            pl.BlockSpec((R, S), lambda i, j: (j, 0), memory_space=vmem),
            pl.BlockSpec((R, TB_tile), lambda i, j: (j, i),
                         memory_space=vmem),
        ],
        out_specs=pl.BlockSpec((CS_pad, TB_tile), lambda i, j: (0, i),
                               memory_space=vmem),
        out_shape=jax.ShapeDtypeStruct((CS_pad, TB_pad), f32),
        interpret=interpret,
    )(slot2d, stats, bin_oh)
    # rows are laid out s-major: block s holds slots [0, C_pad), of
    # which the first C are real
    return (out[:, :TB].reshape(S, C_pad, TB)[:, :C, :]
            .transpose(1, 2, 0))
