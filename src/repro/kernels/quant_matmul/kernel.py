"""Pallas TPU kernel: packed low-bit weight x float activation GEMM.

    y[M, N] = x[M, K] @ dequant(W_packed[N, K/lanes], scale[N]).T

The paper's MAC (8-bit x n-bit shift-add) maps on TPU to *dequant-in-kernel*:
the packed int8 lanes are the only weight bytes that cross HBM->VMEM, so a
W4 layer moves half the bytes of a W8 layer — the decode-roofline win that
stands in for the ASIC's cycle savings (DESIGN.md §2).

Blocking: grid (M/bm, N/bn, K/bk), K innermost ("arbitrary") so the f32
output block is revisited and accumulated in place in VMEM.  bm = bn = 128
aligns the MXU.  Weight bytes are read as lane planes (``kernels/lanes.py``,
shared with the GEMV): x arrives de-interleaved, and each step takes one
dot per plane, so no unpacked (bn, bk) block is ever built.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import LANES
from repro.kernels.lanes import deinterleave, pick_tile
from repro.kernels.quant_gemv.kernel import compute_dtype, k_tile, plane_dot


def _kernel(x_ref, packed_ref, scale_ref, out_ref, *, bits: int, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += plane_dot(x_ref, packed_ref[...], bits)     # (bm, bn)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _apply_scale():
        out_ref[...] *= scale_ref[...]                          # (1, bn) bcast


@functools.partial(
    jax.jit, static_argnames=("bits", "k", "bm", "bn", "bk", "interpret", "out_dtype")
)
def quant_matmul_pallas(
    x: jax.Array,        # (M, K) float32/bfloat16
    packed: jax.Array,   # (N, K/lanes) int8
    scale: jax.Array,    # (1, N) f32
    *,
    bits: int,
    k: int,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    m, kx = x.shape
    n = packed.shape[0]
    lanes = LANES[bits]
    assert kx == k, (kx, k)
    if k % lanes:
        raise ValueError(f"K={k} must be divisible by lanes={lanes}")
    out_dtype = out_dtype or x.dtype

    bm = min(bm, _round_up(m, 8))
    bk = k_tile(k, bits, bk)
    bn = pick_tile(n, bn, 128)
    m_pad = _round_up(m, bm)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    xp = deinterleave(x.astype(compute_dtype(x.dtype)), lanes, 0)  # (lanes, M, K/lanes)

    k_steps = k // bk
    bkp = bk // lanes
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, k_steps=k_steps),
        grid=(m_pad // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((lanes, bm, bkp), lambda i, j, kk: (0, i, kk)),
            pl.BlockSpec((bn, bkp), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xp, packed, scale)
    return out[:m].astype(out_dtype)


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m
