"""Pallas TPU kernel: packed low-bit weight x float activation GEMM.

    y[M, N] = x[M, K] @ dequant(W_packed[N, K/lanes], scale[N]).T

The paper's MAC (8-bit x n-bit shift-add) maps on TPU to *dequant-in-kernel*:
the packed int8 lanes are the only weight bytes that cross HBM->VMEM, so a
W4 layer moves half the bytes of a W8 layer — the decode-roofline win that
stands in for the ASIC's cycle savings (DESIGN.md §2).

Blocking: grid (M/bm, N/bn, K/bk), K innermost ("arbitrary") so the f32
output block is revisited and accumulated in place in VMEM.  Weight bytes
are read as lane planes (``kernels/lanes.py``, shared with the GEMV): x
arrives de-interleaved, and each step takes one dot per plane, so no
unpacked (bn, bk) block is ever built.

Block shape: :func:`gemm_blocks` picks ``(bm, bn, bk)`` from the call's
own ``(M, N, K, bits)``.  A TPU grid step has a fixed cost of about
0.35 µs, so a step has to carry far more work than that: a 128x128x512
step is 85 ns of MXU work, and at M = 32 its 32 KB weight block takes 40
ns to stream.  So bm covers the whole padded M up to a few hundred rows
(a skinny decode batch has no M axis, and each dequantized weight block
feeds that many rows), and bn and bk grow until a step streams about
:data:`STEP_WEIGHT_BYTES` of packed weight, within the VMEM the blocks may
use.  The DMA block is decoupled from the compute: each step walks its
``bk`` in sub-slices of at most :data:`SUB_COLS` packed columns, so the
int32 lane planes stay small whatever ``bk`` is.  That lets K reach a
whole-K block where it has no mid-sized legal tile (K = 11008 at 4 bits
is 43 x 128 packed columns).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import LANES
from repro.kernels.lanes import deinterleave, pick_tile
from repro.kernels.quant_gemv.kernel import compute_dtype, plane_dot

#: largest bm: a prefill M beyond it is split into M blocks of this size
BM_MAX = 512
#: packed weight bytes one grid step should stream, at least where the
#: call's weight holds that many
STEP_WEIGHT_BYTES = 1 << 20
#: largest bn one block may span
BN_MAX = 1024
#: packed columns per in-kernel sub-slice (a multiple of 128); on a v5e
#: 2048 was the fastest of 128-2048, or tied, at every prefill shape swept
SUB_COLS = 2048
#: VMEM the chosen blocks may fill: double-buffered x, weight, scale and
#: output blocks plus one sub-slice's lane planes (a v5e core has 128 MiB)
VMEM_BUDGET = 32 << 20
#: the scoped VMEM a kernel gets unless it asks for more
_DEFAULT_SCOPED_VMEM = 16 << 20


def padded_m(m: int) -> int:
    """M as the kernel pads it: to 8 rows up to 128, to 128 above."""
    return _round_up(m, min(128, _round_up(m, 8)))


def sub_cols(bkp: int) -> int:
    """Packed columns one in-kernel sub-slice of a ``bkp`` block spans."""
    return pick_tile(bkp, SUB_COLS, 128)


def vmem_bytes(bm: int, bn: int, bkp: int, bits: int, x_bytes: int = 2) -> int:
    """VMEM of one block choice: double-buffered x planes, packed weight,
    scale and f32 output blocks, and one sub-slice's int32 lane planes
    with their MXU-operand copies."""
    lanes = LANES[bits]
    sub = sub_cols(bkp)
    blocks = lanes * bm * bkp * x_bytes + bn * bkp + 8 * bn * 4 + bm * bn * 4
    planes = lanes * bn * sub * (4 + x_bytes) + bm * bn * 4
    return 2 * blocks + planes


def gemm_blocks(m: int, n: int, k: int, bits: int, x_bytes: int = 2
                ) -> tuple[int, int, int]:
    """``(bm, bn, bk)`` for a ``(m, k) x (n, k/lanes)`` call at ``bits``.

    bm: the whole padded M up to :data:`BM_MAX` rows, else the padded M
    cut into the fewest equal blocks of at most that many rows (each a
    multiple of 16, the bf16 sublane tile).  bn, bk: legal tiles (``pick_tile``)
    of N and of the packed K; the packed bk grows first, to a whole-K
    block where it fits, then bn, until a step streams
    :data:`STEP_WEIGHT_BYTES` or the blocks fill :data:`VMEM_BUDGET`.
    """
    lanes = LANES[bits]
    kp = k // lanes
    m_pad = padded_m(m)
    # m_pad > BM_MAX is a multiple of 128, so 16-row blocks always divide it
    bm = m_pad if m_pad <= BM_MAX else next(
        m_pad // d for d in range(2, m_pad // 16 + 1)
        if m_pad % d == 0 and m_pad // d <= BM_MAX and m_pad // d % 16 == 0)

    def fits(bn, bkp):
        return vmem_bytes(bm, bn, bkp, bits, x_bytes) <= VMEM_BUDGET

    bn = pick_tile(n, 128, 128)
    bkp = pick_tile(kp, 128, 128)
    for t in _tiles(kp, kp):
        if bn * bkp >= STEP_WEIGHT_BYTES:
            break
        if fits(bn, t):
            bkp = t
    for t in _tiles(n, BN_MAX):
        if bn * bkp >= STEP_WEIGHT_BYTES:
            break
        if fits(t, bkp):
            bn = t
    return bm, bn, bkp * lanes


def _tiles(n: int, cap: int) -> list[int]:
    """Legal tiles of an axis of ``n`` (128-multiple divisors, or ``n``
    itself) up to ``cap``, ascending; the smallest legal one if none fits."""
    tiles = sorted({t for t in range(128, min(n, cap) + 1, 128) if n % t == 0}
                   | ({n} if n <= cap else set()))
    return tiles or [pick_tile(n, 128, 128)]


def _kernel(x_ref, packed_ref, scale_ref, out_ref, *, bits: int, sub: int,
            k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def _sub_step(s, carry):
        cols = pl.ds(pl.multiple_of(s * sub, 128), sub)
        out_ref[...] += plane_dot(x_ref.at[:, :, cols], packed_ref[:, cols], bits)
        return carry                                             # (bm, bn) +=

    jax.lax.fori_loop(0, packed_ref.shape[1] // sub, _sub_step, 0)

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
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """``bm``/``bn``/``bk`` override :func:`gemm_blocks`'s choice (tests);
    each must be a legal tile of its padded axis."""
    m, kx = x.shape
    n = packed.shape[0]
    lanes = LANES[bits]
    assert kx == k, (kx, k)
    if k % lanes:
        raise ValueError(f"K={k} must be divisible by lanes={lanes}")
    out_dtype = out_dtype or x.dtype
    cdt = compute_dtype(x.dtype)
    x_bytes = jnp.dtype(cdt).itemsize

    auto = gemm_blocks(m, n, k, bits, x_bytes)
    bm, bn, bk = bm or auto[0], bn or auto[1], bk or auto[2]
    m_pad = padded_m(m)
    assert m_pad % bm == 0 and n % bn == 0 and k % bk == 0, (m_pad, bm, n, bn, k, bk)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    xp = deinterleave(x.astype(cdt), lanes, 0)                 # (lanes, M, K/lanes)

    k_steps = k // bk
    bkp = bk // lanes
    vmem = vmem_bytes(bm, bn, bkp, bits, x_bytes)
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, sub=sub_cols(bkp), k_steps=k_steps),
        grid=(m_pad // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((lanes, bm, bkp), lambda i, j, kk: (0, i, kk)),
            pl.BlockSpec((bn, bkp), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # the estimate leaves out the compiler's own scratch: ask for
            # half as much again, and never less than the default
            vmem_limit_bytes=max(_DEFAULT_SCOPED_VMEM, vmem * 3 // 2),
        ),
        interpret=interpret,
    )(xp, packed, scale)
    return out[:m].astype(out_dtype)


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m
