"""Lane-plane view of the packed sub-byte layout, shared by every kernel.

``core/packing`` interleaves b-bit levels along the packed axis: value ``k``
sits in byte ``k // lanes`` at lane ``k % lanes``.  Unpacking that back into
a contiguous ``(..., K)`` block inside a kernel needs a minor axis of size
``lanes`` (2 or 4), which the TPU pads to a full 128-lane tile, so the
intermediate costs 32-64x its size in VMEM.

The kernels never build it.  They work on *lane planes* instead: plane ``l``
is the ``(..., K/lanes)`` array of the values at lane ``l`` of every byte,
which two shifts extract from the packed bytes with no relayout.  A dot
product over K is the sum over planes of dots over K/lanes, so the operand
that meets the packed side (activations, queries, new K/V rows) is
de-interleaved into planes outside the kernel, and an output that lives
along the packed axis (attention over V) is re-interleaved outside.  The HBM
packing layout is unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.packing import LANES


def lane_planes(packed: jax.Array, bits: int) -> list[jax.Array]:
    """int8 (or sign-extended int32) packed bytes -> ``lanes`` int32 planes
    of signed levels, each shaped like ``packed``."""
    p = packed.astype(jnp.int32)
    lanes = LANES[bits]
    if lanes == 1:
        return [p]
    # left-align the lane's field, then arithmetic-shift back to sign extend
    return [(p << (32 - bits * (lane + 1))) >> (32 - bits) for lane in range(lanes)]


def pack_planes(planes: list[jax.Array], bits: int) -> jax.Array:
    """Inverse of :func:`lane_planes`: int32 level planes -> int8 bytes."""
    if LANES[bits] == 1:
        return planes[0].astype(jnp.int8)
    mask = (1 << bits) - 1
    out = planes[0] & mask
    for lane in range(1, len(planes)):
        out = out | ((planes[lane] & mask) << (bits * lane))
    # sign-extend the byte (0..255) so the narrowing cast stays in range
    return ((out << 24) >> 24).astype(jnp.int8)


def deinterleave(x: jax.Array, lanes: int, axis: int) -> jax.Array:
    """``(..., K)`` -> lane planes ``(..., K/lanes)`` stacked at ``axis``."""
    y = x.reshape(*x.shape[:-1], x.shape[-1] // lanes, lanes)
    return jnp.moveaxis(y, -1, axis)


def interleave(planes: jax.Array, axis: int) -> jax.Array:
    """Inverse of :func:`deinterleave`."""
    y = jnp.moveaxis(planes, axis, -1)
    return y.reshape(*y.shape[:-2], -1)


def pick_tile(n: int, target: int, quantum: int) -> int:
    """Largest divisor of ``n`` that is a multiple of ``quantum`` and at most
    ``target``; ``n`` itself (one whole-axis block) when there is none.

    A TPU block's minor dims must be multiples of the (8, 128) tile or span
    the whole array axis, so these are the only legal tiles of the axis.
    """
    for t in range(min(target, n) // quantum * quantum, 0, -quantum):
        if n % t == 0:
            return t
    return n
