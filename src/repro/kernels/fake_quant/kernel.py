"""Pallas TPU kernel: fused per-channel fake-quant (round/clip/rescale).

QAT runs this on every weight on every microbatch; fusing the 4 elementwise
ops + broadcast into one VMEM pass avoids 3 extra HBM round-trips of the
full weight matrix.  The per-channel scale is computed outside (one cheap
column-max reduction) and streamed in as a (1, bn) block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(w_ref, scale_ref, bits_ref, out_ref):
    b = bits_ref[0, 0]
    q = jnp.exp2(b.astype(jnp.float32) - 1.0) - 1.0
    scale = scale_ref[...]
    lev = jnp.clip(jnp.round(w_ref[...].astype(jnp.float32) / scale), -q, q)
    out_ref[...] = (lev * scale).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "bn", "interpret"))
def fake_quant_pallas(
    w: jax.Array,       # (K, N)
    scale: jax.Array,   # (1, N) f32
    bits: jax.Array,    # () or (1,) — traced scalar, int32/float32
    *,
    bk: int = 256,
    bn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    k, n = w.shape
    bk = min(bk, k)
    bn = min(bn, n)
    kp, np_ = _round_up(k, bk), _round_up(n, bn)
    if (kp, np_) != (k, n):
        w = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
        scale = jnp.pad(scale, ((0, 0), (0, np_ - n)), constant_values=1.0)
    bits2d = jnp.asarray(bits, jnp.float32).reshape(1, 1)
    grid = (kp // bk, np_ // bn)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((kp, np_), w.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(w, scale, bits2d)
    return out[:k, :n]


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m
