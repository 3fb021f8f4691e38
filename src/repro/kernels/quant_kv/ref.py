"""Pure-jnp oracle for the quantized-KV decode attention.

Unpacks the cache's int lanes (``kvcache/cache.py`` layout) and runs the
masked softmax attention a single decode token needs.  This is both the
CPU/SPMD-analyzable serving fallback (``impl="xla"``) and the parity
oracle the Pallas kernel is tested against.

The fallback stays close to the roofline the fused kernel hits: it keeps
the head-major ``(B, H, S, ·)`` storage layout end to end (no transposed
float copy of the cache) and folds the per-block scales into the small
``(·, S)``-shaped scores/probabilities instead of materializing dequantized
``(S, hd)`` K/V — the only full-size work on the cache is the integer
unpack.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.kvcache.cache import (QuantizedKVLayer, append_token,
                                 requantize_block_levels)


def _scale_per_pos(scale: jax.Array, block: int) -> jax.Array:
    """(B, H, S/block, 1) block scales -> (B, H, 1, S) per-position factors.

    Broadcast + reshape rather than ``jnp.repeat`` (same values, same
    layout, one fewer gather on the fallback path).
    """
    b, h, nb, _ = scale.shape
    per = jnp.broadcast_to(scale, (b, h, nb, block)).reshape(b, h, nb * block)
    return per[:, :, None, :]


def _attention_from_levels(qg: jax.Array, klev: jax.Array, k_scale: jax.Array,
                           vlev: jax.Array, v_scale: jax.Array,
                           kv_valid: jax.Array, *, block: int,
                           hd: int) -> jax.Array:
    """Masked decode attention over already-unpacked int levels.

    ``qg``: f32 (B, H, g, hd); ``klev``/``vlev``: int (B, H, S, hd);
    scales (B, H, S/block, 1).  Shared by the standalone attention oracle
    and the fused decode-step fallback so the two stay op-for-op identical.
    """
    scores = jnp.einsum("bkgh,bkth->bkgt", qg, klev.astype(jnp.float32))
    scores = scores * (_scale_per_pos(k_scale, block) * (1.0 / math.sqrt(hd)))
    scores = jnp.where(kv_valid[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    p = p * _scale_per_pos(v_scale, block)                    # fold V scales
    return jnp.einsum("bkgt,bkth->bkgh", p, vlev.astype(jnp.float32))


def quant_kv_attention_ref(
    q: jax.Array,                 # (B, hq, hd) float — one decode token/slot
    layer: QuantizedKVLayer,
    kv_valid: jax.Array,          # (B, S) bool — positions to attend over
    *,
    out_dtype=None,
) -> jax.Array:
    """softmax(q @ dequant(K).T / sqrt(hd), masked) @ dequant(V) -> (B, hq, hd)."""
    b, s, n_kv, hd = layer.shape
    hq = q.shape[1]
    g = hq // n_kv
    qg = q.astype(jnp.float32).reshape(b, n_kv, g, hd)
    klev = packing.unpack(layer.k_packed, layer.k_bits, hd)   # (B, H, S, hd)
    vlev = packing.unpack(layer.v_packed, layer.v_bits, hd)
    o = _attention_from_levels(qg, klev, layer.k_scale, vlev, layer.v_scale,
                               kv_valid, block=layer.block, hd=hd)
    return o.reshape(b, hq, hd).astype(out_dtype or q.dtype)


def quant_kv_append_ref(layer: QuantizedKVLayer, pos: jax.Array,
                        k_new: jax.Array, v_new: jax.Array) -> QuantizedKVLayer:
    """One-token append: requantize exactly the block containing ``pos``."""
    return append_token(layer, pos, k_new, v_new)


def quant_kv_decode_step_ref(
    q: jax.Array,                 # (B, hq, hd) float — one decode token/slot
    layer: QuantizedKVLayer,
    pos: jax.Array,               # (B,) or scalar int32
    k_new: jax.Array,             # (B, 1, H, hd) float
    v_new: jax.Array,
    kv_valid: jax.Array,          # (B, S) bool (already includes pos)
    *,
    out_dtype=None,
):
    """Fused append+attend fallback: one gather/requant feeds both halves.

    Bitwise-identical to ``quant_kv_append_ref`` → ``quant_kv_attention_ref``
    (the parity harness pins it): the requant math is
    :func:`requantize_block_levels` (THE single source), the touched block
    is placed by a full-width select, and attention splices the *pre-pack*
    levels into the unpacked old cache — exact because pack→unpack
    round-trips on the clipped signed grid.  The touched block is gathered
    and requantized once instead of once per op, and attention does not
    serialize behind the packed-cache writeback.

    Returns ``(out (B, hq, hd), updated layer)``.
    """
    b, s, n_kv, hd = layer.shape
    hq = q.shape[1]
    g = hq // n_kv
    block = layer.block
    nb = s // block
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    bidx = pos // block
    off = pos % block
    kh = jnp.swapaxes(k_new, 1, 2)[:, :, 0]                    # (B, H, hd)
    vh = jnp.swapaxes(v_new, 1, 2)[:, :, 0]
    at_block = (jnp.arange(nb) == bidx[:, None])[:, None, :, None, None]

    def side(packed, scale, new, bits):
        hdp = packed.shape[-1]
        view = packed.reshape(b, n_kv, nb, block, hdp)
        blk = jnp.take_along_axis(view, bidx[:, None, None, None, None], axis=2)
        lev = packing.unpack(blk, bits, hd)[:, :, 0]           # (B, H, block, hd)
        sc_b = jnp.take_along_axis(scale, bidx[:, None, None, None], axis=2)
        fp = lev.astype(jnp.float32) * sc_b
        lev_new, sc_new = requantize_block_levels(fp, new, off, bits)
        blk_new = packing.pack(lev_new, bits)                  # (B, H, block, hdp)
        packed2 = jnp.where(at_block, blk_new[:, :, None],
                            view).reshape(b, n_kv, s, hdp)
        scale2 = jnp.where(at_block[..., 0], sc_new, scale)
        lev_old = packing.unpack(packed, bits, hd).reshape(
            b, n_kv, nb, block, hd)
        lev_att = jnp.where(at_block, lev_new[:, :, None],
                            lev_old).reshape(b, n_kv, s, hd)
        return packed2, scale2, lev_att

    kp2, ks2, klev = side(layer.k_packed, layer.k_scale, kh, layer.k_bits)
    vp2, vs2, vlev = side(layer.v_packed, layer.v_scale, vh, layer.v_bits)
    qg = q.astype(jnp.float32).reshape(b, n_kv, g, hd)
    o = _attention_from_levels(qg, klev, ks2, vlev, vs2, kv_valid,
                               block=block, hd=hd)
    new_layer = dataclasses.replace(layer, k_packed=kp2, k_scale=ks2,
                                    v_packed=vp2, v_scale=vs2)
    return o.reshape(b, hq, hd).astype(out_dtype or q.dtype), new_layer


# ---------------------------------------------------------------------------
# paged variants (DESIGN.md §12)
# ---------------------------------------------------------------------------


def quant_kv_attention_paged_ref(q: jax.Array, layer, kv_valid: jax.Array, *,
                                 out_dtype=None) -> jax.Array:
    """Oracle for the paged attention: gather the table-mapped blocks into
    the dense layout (``kvcache.paged.to_dense``) and run the dense oracle —
    bitwise-identical to a dense cache holding the same contents."""
    from repro.kvcache.paged import to_dense

    return quant_kv_attention_ref(q, to_dense(layer), kv_valid,
                                  out_dtype=out_dtype)


def quant_kv_append_paged_ref(layer, pos: jax.Array, k_new: jax.Array,
                              v_new: jax.Array):
    """Oracle for the paged append: requantize each slot's mapped block."""
    from repro.kvcache.paged import append_token_paged

    return append_token_paged(layer, pos, k_new, v_new)


def quant_kv_decode_step_paged_ref(
    q: jax.Array,                 # (B, hq, hd)
    layer,                        # PagedKVLayer
    pos: jax.Array,
    k_new: jax.Array,             # (B, 1, H, hd)
    v_new: jax.Array,
    kv_valid: jax.Array,          # (B, S)
    *,
    out_dtype=None,
):
    """Fused paged decode step: one pool gather + requant feeds both halves.

    Bitwise-identical to ``append_token_paged`` → paged attention.  It
    gathers the *old* pool and splices each slot's pre-pack levels into its
    own mapped touched block, so attention does not serialize behind the
    pool scatter.

    Substitution relies on the engine's copy-on-write exclusivity: the
    block a live slot appends into is mapped by that slot alone, so no
    other slot's dense view can see the write.  Idle slots clamp to the
    trash block, which is never table-mapped, so their writes are invisible
    either way.
    """
    from repro.kvcache.paged import TRASH_BLOCK, to_dense

    b, s, n_kv, hd = layer.shape
    hq = q.shape[1]
    g = hq // n_kv
    block = layer.block
    nb = s // block
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    bidx = pos // block
    off = pos % block
    raw = jnp.take_along_axis(layer.block_table, bidx[:, None], axis=1)[:, 0]
    phys = jnp.maximum(raw, TRASH_BLOCK)                       # (B,)
    kh = jnp.swapaxes(k_new, 1, 2)[:, :, 0].astype(jnp.float32)
    vh = jnp.swapaxes(v_new, 1, 2)[:, :, 0].astype(jnp.float32)

    def side(pool, scale, new, bits):
        blk = jnp.take(pool, phys, axis=0)                     # (B, H, block, hdp)
        sc = jnp.take(scale, phys, axis=0)                     # (B, H, 1, 1)
        lev = packing.unpack(blk, bits, hd)
        fp = lev.astype(jnp.float32) * sc
        lev_new, sc_new = requantize_block_levels(fp, new, off, bits)
        blk_new = packing.pack(lev_new, bits)
        return (pool.at[phys].set(blk_new), scale.at[phys].set(sc_new),
                lev_new, sc_new)

    kp2, ks2, klev_new, ksc_new = side(layer.k_packed, layer.k_scale, kh,
                                       layer.k_bits)
    vp2, vs2, vlev_new, vsc_new = side(layer.v_packed, layer.v_scale, vh,
                                       layer.v_bits)
    new_layer = dataclasses.replace(layer, k_packed=kp2, k_scale=ks2,
                                    v_packed=vp2, v_scale=vs2)
    dense = to_dense(layer)                                    # OLD contents
    sel = ((jnp.arange(nb) == bidx[:, None])
           & (raw >= 0)[:, None])[:, None, :, None, None]      # (B,1,nb,1,1)

    def splice(packed, scale, lev_new, sc_new, bits):
        lev_old = packing.unpack(packed, bits, hd).reshape(
            b, n_kv, nb, block, hd)
        lev = jnp.where(sel, lev_new[:, :, None],
                        lev_old).reshape(b, n_kv, s, hd)
        sc = jnp.where(sel[..., 0], sc_new, scale)
        return lev, sc

    klev, ks_att = splice(dense.k_packed, dense.k_scale, klev_new, ksc_new,
                          layer.k_bits)
    vlev, vs_att = splice(dense.v_packed, dense.v_scale, vlev_new, vsc_new,
                          layer.v_bits)
    qg = q.astype(jnp.float32).reshape(b, n_kv, g, hd)
    o = _attention_from_levels(qg, klev, ks_att, vlev, vs_att, kv_valid,
                               block=block, hd=hd)
    return o.reshape(b, hq, hd).astype(out_dtype or q.dtype), new_layer
