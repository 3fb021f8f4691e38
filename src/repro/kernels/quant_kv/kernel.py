"""Pallas TPU kernels for the quantized decode state (DESIGN.md §11).

Kernels over the ``kvcache/cache.py`` packed layout (int8 lanes along
head_dim, f32 scales per sequence block) and its paged twin:

* **fused dequant-attention** — one decode token per slot attends over the
  packed cache.  Grid ``(B, n_kv)``: each program reads its head's packed K
  and V bytes into VMEM, dequantizes them there, and runs the masked
  softmax for that head's query group.  The packed bytes are the only
  state bytes that cross HBM->VMEM — the decode-state analogue of the
  weight kernels' dequant-in-kernel contract.

* **quantized append** — writes one new token at a per-slot position.
  Scalar-prefetched positions drive the BlockSpec index maps, so each
  program DMAs exactly ONE ``(H, block, hd/lanes)`` sequence block and its
  scale, dequantizes it, inserts the new row, masks positions beyond the
  write point (container invariant: stale levels stay zero), and
  requantizes under a fresh scale.  The caller places the block back
  (ops.py).

* **fused decode step** — append + attend in one kernel (dense and paged).

Layout inside the kernels:

* The cache is viewed as ``(nb, block, hd/lanes)`` bytes with ``(nb, 1, 1)``
  scales (free reshapes of the container), so a block's scale broadcasts
  over its rows with no relayout, and the touched block is one index of the
  leading axis.
* Like the weight kernels, nothing is unpacked into a contiguous ``hd``
  axis (``kernels/lanes.py``): K bytes are read as lane planes and the
  query arrives de-interleaved into the same planes, so ``q . k`` is a sum
  of one dot per plane; attention over V yields one output plane per V
  lane, which the wrapper re-interleaves.
* Every block's last two dims tile (8, 128) or span the array's own last
  two dims: row vectors (mask, new K/V rows) carry a unit axis.

CPU tests run ``interpret=True``; ``tests/test_tpu_compile.py`` compiles
them for a described v5e.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import LANES
from repro.kernels.lanes import deinterleave, interleave, lane_planes, pack_planes

_F32 = jnp.float32


def _dot(a, b, contract):
    """f32 dot at full f32 precision (the MXU default would round to bf16)."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _dequant(packed, col, bits: int) -> list[jax.Array]:
    """(nb, block, hdp) packed bytes + (nb, 1, 1) block scales -> the
    ``lanes`` dequantized (S, hdp) f32 planes."""
    nb, block, hdp = packed.shape
    return [(lev.astype(_F32) * col).reshape(nb * block, hdp)
            for lev in lane_planes(packed, bits)]


def _set_block(col: jax.Array, bidx, sc: jax.Array) -> jax.Array:
    """(nb, 1, 1) scales with block ``bidx`` replaced by ``sc``."""
    idx = jax.lax.broadcasted_iota(jnp.int32, col.shape, 0)
    return jnp.where(idx == bidx, sc, col)


def _attend(q, kbytes, kcol, vbytes, vcol, mask, *, k_bits: int, v_bits: int,
            hd: int) -> list[jax.Array]:
    """The shared fused dequant-attention body.

    ``q``: (lanes_k, g, hd/lanes_k) query planes; ``kbytes``/``vbytes``:
    (nb, block, ·) packed bytes (int8, or int32 holding sign-extended
    bytes); ``kcol``/``vcol``: (nb, 1, 1) block scales; ``mask``: (1, S)
    additive.  Returns the ``lanes_v`` output planes, each (g, hd/lanes_v).
    Every attention kernel (dense, paged, fused) calls it on the same
    bytes, so they agree bitwise.
    """
    scores = None
    for lane, k in enumerate(_dequant(kbytes, kcol, k_bits)):
        d = _dot(q[lane].astype(_F32), k, ((1,), (1,)))
        scores = d if scores is None else scores + d
    scores = scores * (1.0 / math.sqrt(hd)) + mask               # (g, S)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return [_dot(p, v, ((1,), (0,))) / l
            for v in _dequant(vbytes, vcol, v_bits)]


def _store_planes(out_ref, planes):
    for lane, plane in enumerate(planes):
        out_ref[0, 0, lane] = plane


def _requant(old, sc_old, new, off, *, bits: int):
    """Insert the new row at ``off`` into a packed block and requantize.

    ``old``: (..., block, hdp) packed bytes; ``sc_old``: (..., 1, 1);
    ``new``: ``lanes`` planes, each broadcastable to (..., 1, hdp).
    Returns the new int8 bytes and (..., 1, 1) scale.  The math is
    ``kvcache.cache.requantize_block_levels`` plane by plane, so the
    levels match the jnp path bit for bit.
    """
    idx = jax.lax.broadcasted_iota(jnp.int32, old.shape, old.ndim - 2)
    q = float(2 ** (bits - 1) - 1)
    fps = []
    for lev, row in zip(lane_planes(old, bits), new):
        fp = jnp.where(idx < off, lev.astype(_F32) * sc_old, 0.0)
        fps.append(jnp.where(idx == off, row.astype(_F32), fp))
    amax = None
    for fp in fps:
        a = jnp.max(jnp.max(jnp.abs(fp), axis=-1, keepdims=True), axis=-2,
                    keepdims=True)
        amax = a if amax is None else jnp.maximum(amax, a)
    sc = jnp.maximum(amax, 1e-12) / q
    levs = [jnp.clip(jnp.round(fp / sc), -q, q).astype(jnp.int32) for fp in fps]
    return pack_planes(levs, bits), sc


def _new_planes(new, lanes: int):
    """(…, lanes, 1, hdp) de-interleaved row -> list of (…, 1, hdp) planes."""
    return [new[..., lane, :, :] for lane in range(lanes)]


def _rows_as_planes(x: jax.Array, lanes: int) -> jax.Array:
    """(..., hd) rows -> (..., lanes, 1, hd/lanes): planes with a unit axis."""
    return deinterleave(x.astype(_F32)[..., None, :], lanes, x.ndim - 1)


def _blocks(packed: jax.Array, scale: jax.Array, block: int):
    """Container (B, H, S, hdp) bytes + (B, H, nb, 1) scales -> the free
    (B, H, nb, block, hdp) / (B, H, nb, 1, 1) views the kernels read."""
    b, h, s, hdp = packed.shape
    return (packed.reshape(b, h, s // block, block, hdp),
            scale.reshape(b, h, s // block, 1, 1))


def _compiler_params(*semantics):
    # the whole per-head cache sits in VMEM; allow more than the default
    # scoped limit for long contexts
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=100 * 2**20)


def _head_spec(shape):
    """Block ``(1, 1, *shape)`` at (slot, head) for a grid whose first two
    axes are (slot, head); further grid axes and scalar-prefetch refs are
    ignored."""
    return pl.BlockSpec((1, 1, *shape),
                        lambda i, j, *_: (i, j) + (0,) * len(shape))


def _mask_spec(s: int):
    return pl.BlockSpec((1, 1, s), lambda i, *_: (i, 0, 0))


# ---------------------------------------------------------------------------
# fused dequant-attention
# ---------------------------------------------------------------------------


def _attn_kernel(q_ref, kp_ref, ks_ref, vp_ref, vs_ref, mask_ref, out_ref, *,
                 k_bits: int, v_bits: int, hd: int):
    _store_planes(out_ref, _attend(
        q_ref[0, 0], kp_ref[0, 0], ks_ref[0, 0], vp_ref[0, 0], vs_ref[0, 0],
        mask_ref[0], k_bits=k_bits, v_bits=v_bits, hd=hd))


@functools.partial(jax.jit, static_argnames=("k_bits", "v_bits", "hd", "block",
                                             "interpret"))
def quant_kv_attention_pallas(
    q: jax.Array,         # (B, n_kv, g, hd) float
    k_packed: jax.Array,  # (B, n_kv, S, hd/lanes_k) int8
    k_scale: jax.Array,   # (B, n_kv, S/block, 1) f32
    v_packed: jax.Array,
    v_scale: jax.Array,
    mask: jax.Array,      # (B, S) f32 additive (0 valid / -1e30 invalid)
    *,
    k_bits: int,
    v_bits: int,
    hd: int,
    block: int,
    interpret: bool = False,
) -> jax.Array:
    b, n_kv, g, _ = q.shape
    s = k_packed.shape[2]
    nb = s // block
    lk, lv = LANES[k_bits], LANES[v_bits]
    hk, hv = k_packed.shape[-1], v_packed.shape[-1]
    out = pl.pallas_call(
        functools.partial(_attn_kernel, k_bits=k_bits, v_bits=v_bits, hd=hd),
        grid=(b, n_kv),
        in_specs=[
            _head_spec((lk, g, hk)),
            _head_spec((nb, block, hk)), _head_spec((nb, 1, 1)),
            _head_spec((nb, block, hv)), _head_spec((nb, 1, 1)),
            _mask_spec(s),
        ],
        out_specs=_head_spec((lv, g, hv)),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, lv, g, hv), _F32),
        compiler_params=_compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(deinterleave(q.astype(_F32), lk, 2), *_blocks(k_packed, k_scale, block),
      *_blocks(v_packed, v_scale, block), mask[:, None, :])
    return interleave(out, 2)


# ---------------------------------------------------------------------------
# quantized append (one sequence block touched per slot)
# ---------------------------------------------------------------------------


def _append_kernel(pos_ref, *refs, bits: int, block: int, n_prefetch: int):
    """Shared by the dense and the paged append: the index maps already
    picked the touched block and its scale (``n_prefetch`` scalar refs
    precede ``pos_ref``'s companions)."""
    new_ref, blk_ref, scale_ref, out_ref, sc_ref = refs[n_prefetch - 1:]
    pos = pos_ref[pl.program_id(0)]
    out_ref[0], sc_ref[0] = _requant(
        blk_ref[0, :, 0], scale_ref[0, :, 0],
        _new_planes(new_ref[0], LANES[bits]), pos % block, bits=bits)


def _append_call(index_map, n_prefetch, prefetch, new, packed, scale, *,
                 bits: int, block: int, interpret: bool):
    """One program per slot over all H heads; ``index_map(i, *prefetch)``
    gives the (B or P)-axis and block-axis index of the touched block in
    the ``(·, H, nb, block, hdp)`` bytes view."""
    b, h = new.shape[:2]
    lanes = LANES[bits]
    hdp = packed.shape[-1]
    at = lambda i, *p: (index_map(i, *p)[0], 0, index_map(i, *p)[1], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, lanes, 1, hdp), lambda i, *_: (i, 0, 0, 0, 0)),
            pl.BlockSpec((1, h, 1, block, hdp), at),
            pl.BlockSpec((1, h, 1, 1, 1), at),
        ],
        out_specs=[
            pl.BlockSpec((1, h, block, hdp), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, h, 1, 1), lambda i, *_: (i, 0, 0, 0)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_append_kernel, bits=bits, block=block,
                          n_prefetch=n_prefetch),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, block, hdp), jnp.int8),
                   jax.ShapeDtypeStruct((b, h, 1, 1), _F32)],
        interpret=interpret,
    )(*prefetch, _rows_as_planes(new, lanes), packed, scale)


@functools.partial(jax.jit, static_argnames=("bits", "hd", "block", "interpret"))
def quant_kv_append_pallas(
    pos: jax.Array,      # (B,) int32 per-slot write positions
    new: jax.Array,      # (B, H, hd) float — the new token's K (or V)
    packed: jax.Array,   # (B, H, S, hd/lanes) int8
    scale: jax.Array,    # (B, H, S/block, 1) f32
    *,
    bits: int,
    hd: int,
    block: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Requantized ``(B, H, block, hd/lanes)`` block + ``(B, H, 1, 1)`` scale.

    The scalar-prefetched ``pos`` selects which sequence block each program
    DMAs — the only cache bytes the append ever touches.  The caller places
    the block back (ops.place_block, shared with the jnp reference path).
    """
    return _append_call(lambda i, p: (i, p[i] // block), 1,
                        (jnp.asarray(pos, jnp.int32),), new,
                        *_blocks(packed, scale, block), bits=bits, block=block,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "hd", "block", "interpret"))
def quant_kv_append_paged_pallas(
    pos: jax.Array,      # (B,) int32 per-slot write positions
    table: jax.Array,    # (B, S/block) int32 block table
    new: jax.Array,      # (B, H, hd) float — the new token's K (or V)
    packed: jax.Array,   # (P, H, block, hd/lanes) int8 — the pool
    scale: jax.Array,    # (P, H, 1, 1) f32
    *,
    bits: int,
    hd: int,
    block: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Paged variant of the append: the scalar-prefetched (pos, table) pair
    selects the ONE physical pool block each slot's write lands in; the
    kernel body (the dense append's) dequantizes it, inserts the row, and
    requantizes.  The caller scatters the emitted block + scale back into
    the pool at the same physical ids (ops.place_paged_block)."""
    return _append_call(
        lambda i, p, t: (jnp.maximum(t[i, p[i] // block], 0), 0), 2,
        (jnp.asarray(pos, jnp.int32), jnp.asarray(table, jnp.int32)), new,
        packed[:, :, None], scale[:, :, None], bits=bits, block=block,
        interpret=interpret)


# ---------------------------------------------------------------------------
# paged variants: block-table gather (DESIGN.md §12)
# ---------------------------------------------------------------------------


def _gather_block(mapped, blk, p_ref, s_ref, buf, col):
    """Copy one pool block (and its scale) into the dense-layout scratch;
    unmapped entries (clamped to the trash block) zero-fill so the gathered
    view matches a dense cache's never-written regions."""
    buf[blk] = jnp.where(mapped, p_ref[0, 0].astype(jnp.int32), 0)
    col[blk] = jnp.where(mapped, s_ref[0, 0], 1e-12)


def _paged_attn_kernel(tbl_ref, q_ref, kp_ref, ks_ref, vp_ref, vs_ref,
                       mask_ref, out_ref, kbuf, kcol, vbuf, vcol, *,
                       k_bits: int, v_bits: int, hd: int):
    i, blk = pl.program_id(0), pl.program_id(2)
    mapped = tbl_ref[i, blk] >= 0
    _gather_block(mapped, blk, kp_ref, ks_ref, kbuf, kcol)
    _gather_block(mapped, blk, vp_ref, vs_ref, vbuf, vcol)

    @pl.when(blk == pl.num_programs(2) - 1)
    def _():
        _store_planes(out_ref, _attend(
            q_ref[0, 0], kbuf[...], kcol[...], vbuf[...], vcol[...],
            mask_ref[0], k_bits=k_bits, v_bits=v_bits, hd=hd))


def _paged_scratch(nb, block, hk, hv):
    return [pltpu.VMEM((nb, block, hk), jnp.int32), pltpu.VMEM((nb, 1, 1), _F32),
            pltpu.VMEM((nb, block, hv), jnp.int32), pltpu.VMEM((nb, 1, 1), _F32)]


def _pool_spec(shape):
    """Pool block ``(1, 1, *shape)`` that the slot's table maps at the
    grid's block index (unmapped entries clamp to the trash block)."""
    return pl.BlockSpec(
        (1, 1, *shape),
        lambda i, j, blk, *pf: (jnp.maximum(pf[-1][i, blk], 0), j, 0, 0))


@functools.partial(jax.jit, static_argnames=("k_bits", "v_bits", "hd", "block",
                                             "interpret"))
def quant_kv_attention_paged_pallas(
    table: jax.Array,     # (B, S/block) int32 block table; -1 = unmapped
    q: jax.Array,         # (B, n_kv, g, hd) float
    k_packed: jax.Array,  # (P, n_kv, block, hd/lanes_k) int8 — the pool
    k_scale: jax.Array,   # (P, n_kv, 1, 1) f32
    v_packed: jax.Array,
    v_scale: jax.Array,
    mask: jax.Array,      # (B, S) f32 additive (0 valid / -1e30 invalid)
    *,
    k_bits: int,
    v_bits: int,
    hd: int,
    block: int,
    interpret: bool = False,
) -> jax.Array:
    """Fused dequant-attention over a block-table-mapped pool.

    The scalar-prefetched table row drives the K/V BlockSpec index maps, so
    each (slot, head) program DMAs exactly the pool blocks its table maps —
    never the whole pool — then runs the SAME attention math as the dense
    kernel on the gathered (nb, block, ·) scratch.
    """
    b, n_kv, g, _ = q.shape
    nb = table.shape[1]
    lk, lv = LANES[k_bits], LANES[v_bits]
    hk, hv = k_packed.shape[-1], v_packed.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_kv, nb),
        in_specs=[
            _head_spec((lk, g, hk)),
            _pool_spec((block, hk)), _pool_spec((1, 1)),
            _pool_spec((block, hv)), _pool_spec((1, 1)),
            _mask_spec(nb * block),
        ],
        out_specs=_head_spec((lv, g, hv)),
        scratch_shapes=_paged_scratch(nb, block, hk, hv),
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, k_bits=k_bits, v_bits=v_bits,
                          hd=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, lv, g, hv), _F32),
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(jnp.asarray(table, jnp.int32), deinterleave(q.astype(_F32), lk, 2),
      k_packed, k_scale, v_packed, v_scale, mask[:, None, :])
    return interleave(out, 2)


# ---------------------------------------------------------------------------
# fused decode step: dequant + append/requant + attend in ONE kernel
# ---------------------------------------------------------------------------


def _step_side(new, p_ref, s_ref, t_ref, ts_ref, blk_ref, sc_ref, buf, bidx,
               off, *, bits: int):
    """One side (K or V) of the dense fused step: requantize the touched
    block, emit it, and build the post-append view attention reads — the
    slot's bytes with the new block spliced in (exactly what append +
    ``place_block`` would leave in HBM) and the updated scales."""
    blk, sc = _requant(t_ref[0, 0, 0], ts_ref[0, 0, 0], new, off, bits=bits)
    blk_ref[0, 0] = blk
    sc_ref[0, 0] = sc
    buf[...] = p_ref[0, 0].astype(jnp.int32)
    buf[bidx] = blk.astype(jnp.int32)
    return buf[...], _set_block(s_ref[0, 0], bidx, sc)


def _fused_step_kernel(pos_ref, q_ref, kn_ref, vn_ref, kp_ref, ks_ref, vp_ref,
                       vs_ref, kt_ref, kts_ref, vt_ref, vts_ref, mask_ref,
                       out_ref, kblk_ref, ksc_ref, vblk_ref, vsc_ref, kbuf,
                       vbuf, *, k_bits: int, v_bits: int, hd: int, block: int):
    pos = pos_ref[pl.program_id(0)]
    bidx, off = pos // block, pos % block
    kbytes, kcol = _step_side(
        _new_planes(kn_ref[0, 0], LANES[k_bits]), kp_ref, ks_ref, kt_ref,
        kts_ref, kblk_ref, ksc_ref, kbuf, bidx, off, bits=k_bits)
    vbytes, vcol = _step_side(
        _new_planes(vn_ref[0, 0], LANES[v_bits]), vp_ref, vs_ref, vt_ref,
        vts_ref, vblk_ref, vsc_ref, vbuf, bidx, off, bits=v_bits)
    _store_planes(out_ref, _attend(q_ref[0, 0], kbytes, kcol, vbytes, vcol,
                                   mask_ref[0], k_bits=k_bits, v_bits=v_bits,
                                   hd=hd))


def _dense_step_specs(nb, block, hk, hv):
    """Whole-slot bytes + scales, then the touched block + scale, per side
    (the same arrays enter twice: the touched-block specs DMA the append
    target on their own)."""
    touched = lambda shape: pl.BlockSpec(
        (1, 1, 1, *shape), lambda i, j, p: (i, j, p[i] // block, 0, 0))
    return [_head_spec((nb, block, hk)), _head_spec((nb, 1, 1)),
            _head_spec((nb, block, hv)), _head_spec((nb, 1, 1)),
            touched((block, hk)), touched((1, 1)),
            touched((block, hv)), touched((1, 1))]


def _dense_step_args(k_packed, k_scale, v_packed, v_scale, block):
    kv = (*_blocks(k_packed, k_scale, block), *_blocks(v_packed, v_scale, block))
    return kv + kv


def _step_outputs(b, n_kv, g, block, lv, hk, hv):
    return dict(
        out_specs=[_head_spec((lv, g, hv)), _head_spec((block, hk)),
                   _head_spec((1, 1)), _head_spec((block, hv)),
                   _head_spec((1, 1))],
        out_shape=[jax.ShapeDtypeStruct((b, n_kv, lv, g, hv), _F32),
                   jax.ShapeDtypeStruct((b, n_kv, block, hk), jnp.int8),
                   jax.ShapeDtypeStruct((b, n_kv, 1, 1), _F32),
                   jax.ShapeDtypeStruct((b, n_kv, block, hv), jnp.int8),
                   jax.ShapeDtypeStruct((b, n_kv, 1, 1), _F32)])


def _step_call(kernel, grid, n_prefetch, in_specs, scratch, args, *, b, n_kv,
               g, block, lv, hk, hv, interpret):
    outs = _step_outputs(b, n_kv, g, block, lv, hk, hv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch, grid=grid, in_specs=in_specs,
        out_specs=outs["out_specs"], scratch_shapes=scratch)
    semantics = ("parallel", "parallel") + ("arbitrary",) * (len(grid) - 2)
    o, kb, ks, vb, vs = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=outs["out_shape"],
        compiler_params=_compiler_params(*semantics), interpret=interpret,
    )(*args)
    return interleave(o, 2), kb, ks, vb, vs


@functools.partial(jax.jit, static_argnames=("k_bits", "v_bits", "hd", "block",
                                             "interpret"))
def quant_kv_decode_step_pallas(
    pos: jax.Array,       # (B,) int32 per-slot write positions
    q: jax.Array,         # (B, n_kv, g, hd) float
    k_new: jax.Array,     # (B, n_kv, hd) float — the new token's K rows
    v_new: jax.Array,
    k_packed: jax.Array,  # (B, n_kv, S, hd/lanes_k) int8
    k_scale: jax.Array,   # (B, n_kv, S/block, 1) f32
    v_packed: jax.Array,
    v_scale: jax.Array,
    mask: jax.Array,      # (B, S) f32 additive (0 valid / -1e30 invalid)
    *,
    k_bits: int,
    v_bits: int,
    hd: int,
    block: int,
    interpret: bool = False,
):
    """ONE kernel per (slot, head): dequant + append/requant + attend.

    The packed cache bytes cross HBM->VMEM once per decode step (plus the
    touched block, read again through its own BlockSpec); the post-append
    view attention needs is built in VMEM by splicing the requantized block
    into the slot's bytes.  Emits the attention output plus the touched
    block + scale per side — the caller scatters them back with the same
    ``ops.place_block`` the sequential path uses, so the updated cache is
    bit-identical to append-then-attend.
    """
    b, n_kv, g, _ = q.shape
    nb = k_packed.shape[2] // block
    lk, lv = LANES[k_bits], LANES[v_bits]
    hk, hv = k_packed.shape[-1], v_packed.shape[-1]
    return _step_call(
        functools.partial(_fused_step_kernel, k_bits=k_bits, v_bits=v_bits,
                          hd=hd, block=block),
        (b, n_kv), 1,
        [_head_spec((lk, g, hk)), _head_spec((lk, 1, hk)),
         _head_spec((lv, 1, hv)), *_dense_step_specs(nb, block, hk, hv),
         _mask_spec(nb * block)],
        [pltpu.VMEM((nb, block, hk), jnp.int32),
         pltpu.VMEM((nb, block, hv), jnp.int32)],
        (jnp.asarray(pos, jnp.int32), deinterleave(q.astype(_F32), lk, 2),
         _rows_as_planes(k_new, lk), _rows_as_planes(v_new, lv),
         *_dense_step_args(k_packed, k_scale, v_packed, v_scale, block),
         mask[:, None, :]),
        b=b, n_kv=n_kv, g=g, block=block, lv=lv, hk=hk, hv=hv,
        interpret=interpret)


def _fused_step_paged_kernel(pos_ref, tbl_ref, q_ref, kn_ref, vn_ref, kp_ref,
                             ks_ref, vp_ref, vs_ref, kt_ref, kts_ref, vt_ref,
                             vts_ref, mask_ref, out_ref, kblk_ref, ksc_ref,
                             vblk_ref, vsc_ref, kbuf, kcol, vbuf, vcol, *,
                             k_bits: int, v_bits: int, hd: int, block: int):
    i, blk = pl.program_id(0), pl.program_id(2)
    mapped = tbl_ref[i, blk] >= 0
    _gather_block(mapped, blk, kp_ref, ks_ref, kbuf, kcol)
    _gather_block(mapped, blk, vp_ref, vs_ref, vbuf, vcol)

    @pl.when(blk == pl.num_programs(2) - 1)
    def _():
        pos = pos_ref[i]
        bidx, off = pos // block, pos % block
        # The touched *physical* block was DMA'd separately, so idle slots
        # requantize the real trash-block contents — exactly what the
        # sequential paged append emits.  The attention view takes the
        # update only where the slot's table maps the block (trash writes
        # stay invisible, as in the sequential gather over the new pool).
        mapped_t = tbl_ref[i, bidx] >= 0

        def side(new, t_ref, ts_ref, blk_ref, sc_ref, buf, col, bits):
            nblk, sc = _requant(t_ref[0, 0], ts_ref[0, 0], new, off, bits=bits)
            blk_ref[0, 0] = nblk
            sc_ref[0, 0] = sc

            @pl.when(mapped_t)
            def _():
                buf[bidx] = nblk.astype(jnp.int32)
                col[bidx] = sc

        side(_new_planes(kn_ref[0, 0], LANES[k_bits]), kt_ref, kts_ref,
             kblk_ref, ksc_ref, kbuf, kcol, k_bits)
        side(_new_planes(vn_ref[0, 0], LANES[v_bits]), vt_ref, vts_ref,
             vblk_ref, vsc_ref, vbuf, vcol, v_bits)
        _store_planes(out_ref, _attend(
            q_ref[0, 0], kbuf[...], kcol[...], vbuf[...], vcol[...],
            mask_ref[0], k_bits=k_bits, v_bits=v_bits, hd=hd))


@functools.partial(jax.jit, static_argnames=("k_bits", "v_bits", "hd", "block",
                                             "interpret"))
def quant_kv_decode_step_paged_pallas(
    pos: jax.Array,       # (B,) int32 per-slot write positions
    table: jax.Array,     # (B, S/block) int32 block table; -1 = unmapped
    q: jax.Array,         # (B, n_kv, g, hd) float
    k_new: jax.Array,     # (B, n_kv, hd) float
    v_new: jax.Array,
    k_packed: jax.Array,  # (P, n_kv, block, hd/lanes_k) int8 — the pool
    k_scale: jax.Array,   # (P, n_kv, 1, 1) f32
    v_packed: jax.Array,
    v_scale: jax.Array,
    mask: jax.Array,      # (B, S) f32 additive
    *,
    k_bits: int,
    v_bits: int,
    hd: int,
    block: int,
    interpret: bool = False,
):
    """Paged fused decode step: gather + append/requant + attend, one kernel.

    The scalar-prefetched (pos, table) pair drives every DMA: the grid's
    inner axis gathers the slot's mapped pool blocks into dense-layout
    scratch (as the paged attention kernel does), plus ONE extra block — the
    physical block the append touches — which is requantized with the new
    row and spliced into the gathered view before the shared attention math
    runs.  Emits out + per-side (block, scale); the caller scatters them
    with ``ops.place_paged_block``, identical to the sequential path.

    Assumes the engine's CoW exclusivity (a live slot's touched block is
    mapped by that slot alone) — the same precondition the sequential
    append+attend pair already relies on for step-order independence.
    """
    b, n_kv, g, _ = q.shape
    nb = table.shape[1]
    lk, lv = LANES[k_bits], LANES[v_bits]
    hk, hv = k_packed.shape[-1], v_packed.shape[-1]
    touched = lambda shape: pl.BlockSpec(
        (1, 1, *shape),
        lambda i, j, blk, p, t: (jnp.maximum(t[i, p[i] // block], 0), j, 0, 0))
    pool = (k_packed, k_scale, v_packed, v_scale)
    return _step_call(
        functools.partial(_fused_step_paged_kernel, k_bits=k_bits,
                          v_bits=v_bits, hd=hd, block=block),
        (b, n_kv, nb), 2,
        [_head_spec((lk, g, hk)), _head_spec((lk, 1, hk)),
         _head_spec((lv, 1, hv)),
         _pool_spec((block, hk)), _pool_spec((1, 1)),
         _pool_spec((block, hv)), _pool_spec((1, 1)),
         touched((block, hk)), touched((1, 1)),
         touched((block, hv)), touched((1, 1)),
         _mask_spec(nb * block)],
        _paged_scratch(nb, block, hk, hv),
        # the pool buffers enter twice: the touched-block specs DMA the
        # append target separately from the gather stream
        (jnp.asarray(pos, jnp.int32), jnp.asarray(table, jnp.int32),
         deinterleave(q.astype(_F32), lk, 2), _rows_as_planes(k_new, lk),
         _rows_as_planes(v_new, lv), *pool, *pool, mask[:, None, :]),
        b=b, n_kv=n_kv, g=g, block=block, lv=lv, hk=hk, hv=hv,
        interpret=interpret)

