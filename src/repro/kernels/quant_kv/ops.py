"""Public dispatch for the quantized-KV decode ops (mirrors quant_gemv):

  "xla"        dequantize -> masked softmax attention / jnp block requant
               (reference path; SPMD-analyzable, CPU-friendly)
  "pallas"     the fused TPU kernels (kernel.py)
  "interpret"  the Pallas kernel bodies interpreted on CPU (tests)
  "auto"       pallas on TPU backends, xla elsewhere

Both ops take/return the cache container — the dense
``kvcache.cache.QuantizedKVLayer`` or the paged
``kvcache.paged.PagedKVLayer`` (block-pool + block-table layout); the op
dispatches on the container type, so ``models/layers.attention_decode_quant``
is the only call site that needs to know the dispatch surface exists.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kvcache.paged import PagedKVLayer, TRASH_BLOCK

from .kernel import (quant_kv_append_paged_pallas, quant_kv_append_pallas,
                     quant_kv_attention_paged_pallas, quant_kv_attention_pallas,
                     quant_kv_decode_step_paged_pallas,
                     quant_kv_decode_step_pallas)
from .ref import (quant_kv_append_paged_ref, quant_kv_append_ref,
                  quant_kv_attention_paged_ref, quant_kv_attention_ref,
                  quant_kv_decode_step_paged_ref, quant_kv_decode_step_ref)


def _backend() -> str:
    return jax.default_backend()


def resolve_impl(impl: str) -> str:
    """The impl a request actually dispatches to (``"auto"`` resolved).

    Public so benchmarks can stamp the *dispatched* impl into their config
    blocks instead of echoing the requested string.
    """
    if impl == "auto":
        return "pallas" if _backend() == "tpu" else "xla"
    return impl


_resolve = resolve_impl


def quant_kv_attention(
    q: jax.Array,                # (B, 1, hq, hd) or (B, hq, hd)
    layer,                       # QuantizedKVLayer | PagedKVLayer
    kv_valid: jax.Array,         # (B, S) bool
    *,
    impl: str = "auto",
    out_dtype=None,
) -> jax.Array:
    """One decode token per slot attends over the packed (dense or paged) cache."""
    impl = _resolve(impl)
    paged = isinstance(layer, PagedKVLayer)
    lead4 = q.ndim == 4
    q3 = q[:, 0] if lead4 else q                      # (B, hq, hd)
    if impl == "xla":
        ref = quant_kv_attention_paged_ref if paged else quant_kv_attention_ref
        o = ref(q3, layer, kv_valid, out_dtype=out_dtype)
    elif impl in ("pallas", "interpret"):
        b, s, n_kv, hd = layer.shape
        g = q3.shape[1] // n_kv
        qg = q3.reshape(b, n_kv, g, hd)
        mask = jnp.where(kv_valid, 0.0, -1e30).astype(jnp.float32)
        if paged:
            o = quant_kv_attention_paged_pallas(
                layer.block_table, qg, layer.k_packed, layer.k_scale,
                layer.v_packed, layer.v_scale, mask, k_bits=layer.k_bits,
                v_bits=layer.v_bits, hd=hd, block=layer.block,
                interpret=impl == "interpret")
        else:
            o = quant_kv_attention_pallas(
                qg, layer.k_packed, layer.k_scale, layer.v_packed, layer.v_scale,
                mask, k_bits=layer.k_bits, v_bits=layer.v_bits, hd=hd,
                block=layer.block, interpret=impl == "interpret")
        o = o.reshape(b, n_kv * g, hd).astype(out_dtype or q.dtype)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return o[:, None] if lead4 else o


def place_block(packed: jax.Array, scale: jax.Array, blk: jax.Array,
                sc: jax.Array, pos: jax.Array, block: int):
    """Scatter a requantized ``(B, H, block, ·)`` block + scale back at ``pos``."""

    def one(pk, s_, b_, sn, p):
        bidx = p // block
        pk2 = jax.lax.dynamic_update_slice_in_dim(pk, b_, bidx * block, axis=1)
        s2 = jax.lax.dynamic_update_slice_in_dim(s_, sn, bidx, axis=1)
        return pk2, s2

    return jax.vmap(one)(packed, scale, blk, sc, jnp.asarray(pos, jnp.int32))


def place_paged_block(pool: jax.Array, scale: jax.Array, blk: jax.Array,
                      sc: jax.Array, phys: jax.Array):
    """Scatter per-slot requantized blocks back into the pool at ``phys``.

    Active slots own their target block exclusively (the engine's CoW
    guarantee), so real ids never collide; idle slots all clamp to the
    trash block, where last-write-wins is harmless by construction.
    """
    return pool.at[phys].set(blk), scale.at[phys].set(sc)


def quant_kv_append(
    layer,                       # QuantizedKVLayer | PagedKVLayer
    pos: jax.Array,              # (B,) or scalar int32
    k_new: jax.Array,            # (B, 1, H, hd) float
    v_new: jax.Array,
    *,
    impl: str = "auto",
):
    """Write one decode token's K/V; requantizes only the touched block."""
    impl = _resolve(impl)
    paged = isinstance(layer, PagedKVLayer)
    if impl == "xla":
        ref = quant_kv_append_paged_ref if paged else quant_kv_append_ref
        return ref(layer, pos, k_new, v_new)
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    interp = impl == "interpret"
    b = k_new.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    kh = jnp.swapaxes(k_new, 1, 2)[:, :, 0]           # (B, H, hd)
    vh = jnp.swapaxes(v_new, 1, 2)[:, :, 0]
    hd = layer.head_dim
    if paged:
        tbl = layer.block_table
        kb, ks = quant_kv_append_paged_pallas(
            pos, tbl, kh, layer.k_packed, layer.k_scale, bits=layer.k_bits,
            hd=hd, block=layer.block, interpret=interp)
        vb, vs = quant_kv_append_paged_pallas(
            pos, tbl, vh, layer.v_packed, layer.v_scale, bits=layer.v_bits,
            hd=hd, block=layer.block, interpret=interp)
        phys = jnp.maximum(
            jnp.take_along_axis(tbl, (pos // layer.block)[:, None], axis=1)[:, 0],
            TRASH_BLOCK)
        kp, ksc = place_paged_block(layer.k_packed, layer.k_scale, kb, ks, phys)
        vp, vsc = place_paged_block(layer.v_packed, layer.v_scale, vb, vs, phys)
    else:
        kb, ks = quant_kv_append_pallas(pos, kh, layer.k_packed, layer.k_scale,
                                        bits=layer.k_bits, hd=hd,
                                        block=layer.block, interpret=interp)
        vb, vs = quant_kv_append_pallas(pos, vh, layer.v_packed, layer.v_scale,
                                        bits=layer.v_bits, hd=hd,
                                        block=layer.block, interpret=interp)
        kp, ksc = place_block(layer.k_packed, layer.k_scale, kb, ks, pos,
                              layer.block)
        vp, vsc = place_block(layer.v_packed, layer.v_scale, vb, vs, pos,
                              layer.block)
    return dataclasses.replace(layer, k_packed=kp, k_scale=ksc,
                               v_packed=vp, v_scale=vsc)


def quant_kv_decode_step(
    q: jax.Array,                # (B, 1, hq, hd) or (B, hq, hd)
    layer,                       # QuantizedKVLayer | PagedKVLayer
    pos: jax.Array,              # (B,) or scalar int32 write positions
    k_new: jax.Array,            # (B, 1, H, hd) float
    v_new: jax.Array,
    kv_valid: jax.Array,         # (B, S) bool (already includes pos)
    *,
    impl: str = "auto",
    out_dtype=None,
):
    """ONE fused dispatch per layer per decode step: append + attend.

    The one decode step of each cache container: the dense and the paged
    Pallas kernels, with ``ref.quant_kv_decode_step{,_paged}_ref`` as the
    XLA fallback.  Bitwise-identical to ``quant_kv_append`` followed by
    ``quant_kv_attention`` on every impl (the parity harness pins it); the
    packed cache bytes are read once instead of once per op.  Returns
    ``(o, updated layer)`` with ``o`` shaped like ``q``.
    """
    impl = _resolve(impl)
    paged = isinstance(layer, PagedKVLayer)
    lead4 = q.ndim == 4
    q3 = q[:, 0] if lead4 else q                      # (B, hq, hd)
    if impl == "xla":
        ref = quant_kv_decode_step_paged_ref if paged else quant_kv_decode_step_ref
        o, layer = ref(q3, layer, pos, k_new, v_new, kv_valid,
                       out_dtype=out_dtype or q.dtype)
    elif impl in ("pallas", "interpret"):
        interp = impl == "interpret"
        b, s, n_kv, hd = layer.shape
        g = q3.shape[1] // n_kv
        qg = q3.reshape(b, n_kv, g, hd)
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
        kh = jnp.swapaxes(k_new, 1, 2)[:, :, 0]       # (B, H, hd)
        vh = jnp.swapaxes(v_new, 1, 2)[:, :, 0]
        mask = jnp.where(kv_valid, 0.0, -1e30).astype(jnp.float32)
        if paged:
            o, kb, ks, vb, vs = quant_kv_decode_step_paged_pallas(
                pos, layer.block_table, qg, kh, vh, layer.k_packed,
                layer.k_scale, layer.v_packed, layer.v_scale, mask,
                k_bits=layer.k_bits, v_bits=layer.v_bits, hd=hd,
                block=layer.block, interpret=interp)
            phys = jnp.maximum(
                jnp.take_along_axis(layer.block_table,
                                    (pos // layer.block)[:, None],
                                    axis=1)[:, 0], TRASH_BLOCK)
            kp, ksc = place_paged_block(layer.k_packed, layer.k_scale, kb, ks,
                                        phys)
            vp, vsc = place_paged_block(layer.v_packed, layer.v_scale, vb, vs,
                                        phys)
        else:
            o, kb, ks, vb, vs = quant_kv_decode_step_pallas(
                pos, qg, kh, vh, layer.k_packed, layer.k_scale,
                layer.v_packed, layer.v_scale, mask, k_bits=layer.k_bits,
                v_bits=layer.v_bits, hd=hd, block=layer.block,
                interpret=interp)
            kp, ksc = place_block(layer.k_packed, layer.k_scale, kb, ks, pos,
                                  layer.block)
            vp, vsc = place_block(layer.v_packed, layer.v_scale, vb, vs, pos,
                                  layer.block)
        layer = dataclasses.replace(layer, k_packed=kp, k_scale=ksc,
                                    v_packed=vp, v_scale=vsc)
        o = o.reshape(b, n_kv * g, hd).astype(out_dtype or q.dtype)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return (o[:, None] if lead4 else o), layer

