"""Pallas TPU kernel: skinny-M packed low-bit GEMV for the decode fast path.

    y[M, N] = x[M, K] @ dequant(W_packed[N, K/lanes], scale[N]).T,   M <= 8

Decode is the memory-bound regime the paper's per-layer bitwidth targets
(DESIGN.md §2): every generated token re-reads all packed weight bytes while
M is the handful of active slots.  ``quant_matmul_pallas`` tiles M to the
128-wide MXU dimension, so at M=4 >96% of each x-block and out-block is
zero padding and the grid still iterates an M axis of size one.  This kernel
instead:

  * pads M once to the 8-row f32 sublane (the hardware minimum — no M grid
    axis at all), so the full x row-block stays resident in VMEM for every
    (N, K) step;
  * runs grid (N/bn, K/bk), K innermost ("arbitrary") to accumulate the
    (8, bn) output block in place — weight bytes stream through VMEM exactly
    once, which is the whole HBM cost of a decode step;
  * factors the per-output-channel scale out of the K loop: the inner step
    accumulates x @ levels.T on integer levels, and the scale multiplies the
    finished block once on the last K step (bn*8 multiplies instead of
    bn*bk per step).

Weight bytes are read as lane planes (``kernels/lanes.py``): the caller's
x is de-interleaved into ``lanes`` planes once, outside the kernel, and each
K step takes one integer-level dot per plane.  Both kernels share one packed
HBM layout, so ``quant_matmul`` dispatches here for M <= GEMV_MAX_M with no
repacking.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import LANES
from repro.kernels.lanes import deinterleave, lane_planes, pick_tile

#: largest M served by the GEMV fast path (one f32 sublane)
GEMV_MAX_M = 8


def compute_dtype(dtype):
    """MXU operand dtype: bf16 activations meet bf16 levels (|level| <= 127
    is exact in bf16, and products accumulate in f32); anything else runs
    in f32."""
    return jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32


def plane_dot(x_ref, packed, bits: int) -> jax.Array:
    """sum over lane planes of x_plane @ levels_plane.T -> (rows(x), rows(w)).

    ``x_ref``: (lanes, m, kp) de-interleaved activations; ``packed``: the
    (n, kp) int8 weight block.
    """
    acc = None
    for lane, lev in enumerate(lane_planes(packed, bits)):
        x = x_ref[lane]
        # f32 operands need the full-precision MXU passes; bf16 ones are exact
        prec = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
        d = jax.lax.dot_general(
            x, lev.astype(jnp.float32).astype(x.dtype), (((1,), (1,)), ((), ())),
            precision=prec, preferred_element_type=jnp.float32)
        acc = d if acc is None else acc + d
    return acc


def k_tile(k: int, bits: int, target: int) -> int:
    """K block: a divisor of K whose packed width is a multiple of 128
    lanes (the whole K where none exists, e.g. K=11008 at 2 bits)."""
    lanes = LANES[bits]
    return pick_tile(k // lanes, target // lanes, 128) * lanes


def _kernel(x_ref, packed_ref, scale_ref, out_ref, *, bits: int, k_steps: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += plane_dot(x_ref, packed_ref[...], bits)     # (8, bn)

    @pl.when(pl.program_id(1) == k_steps - 1)
    def _apply_scale():
        out_ref[...] *= scale_ref[...]                          # (1, bn) bcast


@functools.partial(
    jax.jit, static_argnames=("bits", "k", "bn", "bk", "interpret", "out_dtype")
)
def quant_gemv_pallas(
    x: jax.Array,        # (M, K) float32/bfloat16, M <= GEMV_MAX_M
    packed: jax.Array,   # (N, K/lanes) int8
    scale: jax.Array,    # (1, N) f32
    *,
    bits: int,
    k: int,
    bn: int = 256,
    bk: int = 1024,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    m, kx = x.shape
    n = packed.shape[0]
    lanes = LANES[bits]
    assert kx == k, (kx, k)
    if m > GEMV_MAX_M:
        raise ValueError(f"GEMV fast path is for M <= {GEMV_MAX_M}, got M={m}")
    if k % lanes:
        raise ValueError(f"K={k} must be divisible by lanes={lanes}")
    out_dtype = out_dtype or x.dtype

    bk = k_tile(k, bits, bk)
    bn = pick_tile(n, bn, 128)
    if m != GEMV_MAX_M:
        x = jnp.pad(x, ((0, GEMV_MAX_M - m), (0, 0)))
    xp = deinterleave(x.astype(compute_dtype(x.dtype)), lanes, 0)  # (lanes, 8, K/lanes)

    k_steps = k // bk
    bkp = bk // lanes
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, k_steps=k_steps),
        grid=(n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((lanes, GEMV_MAX_M, bkp), lambda j, kk: (0, 0, kk)),
            pl.BlockSpec((bn, bkp), lambda j, kk: (j, kk)),
            pl.BlockSpec((1, bn), lambda j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((GEMV_MAX_M, bn), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((GEMV_MAX_M, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xp, packed, scale)
    return out[:m].astype(out_dtype)
