"""Shared layer library: norms, RoPE/M-RoPE, quantization-aware dense,
GQA/MQA attention (direct + chunked-flash + decode-cache), and MLP variants.

Conventions
-----------
* params are nested dicts of arrays; dense kernels are (in, out).
* every dense is quantization-aware via ``qdense``: float weights pass
  through fake-quant STE when ``bits`` is given (QAT), and
  ``QuantizedTensor`` weights use the packed dequant-matmul (serving).
* per-layer bits ride through ``lax.scan`` as scalar leaves of the
  ``bits`` dict, mirroring the param dict structure.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.fake_quant.ops import fake_quant_ste
from repro.kernels.quant_matmul.ops import qt_matmul
from repro.quant.tensor import QuantizedTensor

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32) -> jax.Array:
    std = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * std).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# quantization-aware dense
# ---------------------------------------------------------------------------


def qdense(w: Any, x: jax.Array, *, bits=None, qimpl: str = "auto") -> jax.Array:
    """x @ w with optional QAT fake-quant or packed-int serving weights."""
    if isinstance(w, QuantizedTensor):
        return qt_matmul(x, w, impl=qimpl, out_dtype=x.dtype)
    if bits is not None:
        w = fake_quant_ste(w, bits, "xla" if qimpl == "auto" else qimpl)
    y = jax.lax.dot_general(
        x, w.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return y.astype(x.dtype)


def _b(bits, name):
    return None if bits is None else bits.get(name)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm(scale: jax.Array, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def norm(p: Any, x: jax.Array, kind: str, eps: float = 1e-5) -> jax.Array:
    return rmsnorm(p, x, eps) if kind == "rmsnorm" else layernorm(p, x, eps)


def norm_init(d: int, kind: str, dtype=jnp.float32):
    if kind == "rmsnorm":
        return jnp.ones((d,), dtype)
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


# ---------------------------------------------------------------------------
# rotary embeddings (default + M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    ang = positions[..., None].astype(jnp.float32) * rope_freqs(hd, theta)  # (B,S,hd/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,
    positions: jax.Array,          # (3, B, S) — (t, h, w) position ids
    sections: tuple[int, ...],     # per-section counts over hd/2, sums to hd/2
    theta: float = 10_000.0,
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: frequency bands partitioned across (t,h,w)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    # section id per frequency index
    sec_id = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                        total_repeat_length=hd // 2)
    pos_per_freq = jnp.take(positions, sec_id, axis=0)          # (hd/2, B, S) -> gather over axis0
    pos_per_freq = jnp.moveaxis(pos_per_freq, 0, -1)            # (B, S, hd/2)
    ang = pos_per_freq.astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def position_ids(batch: int, seq: int, rope_kind: str) -> jax.Array:
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))
    if rope_kind == "mrope":
        return jnp.broadcast_to(pos, (3, batch, seq))
    return pos


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

FLASH_THRESHOLD = 2_048  # direct softmax below this sequence length
Q_CHUNK = 512
KV_CHUNK = 1_024


def attention_init(key, cfg, dtype=jnp.float32) -> dict:
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _split_heads(x, n_heads, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, hd)


def _qkv(p, x, cfg, positions, *, bits=None, qimpl="auto"):
    hd = cfg.resolved_head_dim
    if "wqkv" in p:
        # pack-time fused projection group (quant.apply.fuse_projections):
        # one packed buffer, one kernel launch, split on the N-contiguous out
        nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
        qkv = qdense(p["wqkv"], x, bits=_b(bits, "wqkv"), qimpl=qimpl)
        qf, kf, vf = jnp.split(qkv, [nq, nq + nkv], axis=-1)
        q, k, v = (_split_heads(qf, cfg.n_heads, hd),
                   _split_heads(kf, cfg.n_kv_heads, hd),
                   _split_heads(vf, cfg.n_kv_heads, hd))
        return _qkv_post(p, q, k, v, cfg, positions)
    q = _split_heads(qdense(p["wq"], x, bits=_b(bits, "wq"), qimpl=qimpl), cfg.n_heads, hd)
    k = _split_heads(qdense(p["wk"], x, bits=_b(bits, "wk"), qimpl=qimpl), cfg.n_kv_heads, hd)
    v = _split_heads(qdense(p["wv"], x, bits=_b(bits, "wv"), qimpl=qimpl), cfg.n_kv_heads, hd)
    return _qkv_post(p, q, k, v, cfg, positions)


def _q_proj(p, x, cfg, *, bits=None, qimpl="auto"):
    """Q projection only (cross-attention query path); fused-tree aware.

    On a fused tree this computes the full wqkv product and slices — the
    K/V columns are wasted, but cross-attention is off the decode hot path
    and correctness on any fuse_projections output matters more."""
    if "wqkv" in p:
        nq = cfg.n_heads * cfg.resolved_head_dim
        return qdense(p["wqkv"], x, bits=_b(bits, "wqkv"), qimpl=qimpl)[..., :nq]
    return qdense(p["wq"], x, bits=_b(bits, "wq"), qimpl=qimpl)


def _kv_proj(p, x, cfg, *, bits=None, qimpl="auto"):
    """K/V projections only (cross-attention KV precompute); fused-aware."""
    hd = cfg.resolved_head_dim
    if "wqkv" in p:
        nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
        kvf = qdense(p["wqkv"], x, bits=_b(bits, "wqkv"), qimpl=qimpl)[..., nq:]
        return kvf[..., :nkv], kvf[..., nkv:]
    return (qdense(p["wk"], x, bits=_b(bits, "wk"), qimpl=qimpl),
            qdense(p["wv"], x, bits=_b(bits, "wv"), qimpl=qimpl))


def _qkv_post(p, q, k, v, cfg, positions):
    hd = cfg.resolved_head_dim
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if cfg.rope == "default":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope == "mrope":
        hd_half = hd // 2
        sections = (hd_half - 2 * (hd_half // 3), hd_half // 3, hd_half // 3)
        if positions.ndim == 2:  # text-only path: (t,h,w) positions coincide
            positions = jnp.broadcast_to(positions, (3, *positions.shape))
        q = apply_mrope(q, positions, sections, cfg.rope_theta)
        k = apply_mrope(k, positions, sections, cfg.rope_theta)
    return q, k, v


def _direct_attention(q, k, v, n_kv, *, causal, window=0, kv_valid=None,
                      q_offset=None):
    """Materialized-scores path (short sequences / decode).

    ``q_offset``: absolute position of q row 0 (chunked prefill attends a
    chunk of queries against a longer scratch KV whose row 0 is position 0);
    default ``skv - sq`` — queries are the suffix of the keys.
    """
    b, sq, hq, hd = q.shape
    skv = k.shape[1]
    g = hq // n_kv
    qg = q.reshape(b, sq, n_kv, g, hd)
    # keep K/V in storage dtype; accumulate in f32 on the MXU.  Upcasting the
    # cache materializes f32 transposed copies of the whole 32k KV per layer
    # (observed: 16.8 GiB/token on yi-6b decode — EXPERIMENTS.md §Perf cell 3).
    s = jnp.einsum("bqkgh,btkh->bkgqt", qg, k,
                   preferred_element_type=jnp.float32)
    s *= 1.0 / math.sqrt(hd)
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    off = (skv - sq) if q_offset is None else q_offset
    if causal:
        mask &= k_pos <= (q_pos + off)  # query i sits at absolute off + i
    if window:
        mask &= k_pos > (q_pos + off - window)
    if kv_valid is not None and kv_valid.ndim == 2:   # per-slot validity (B, skv)
        full = mask[None, None, None] & kv_valid[:, None, None, None, :]
        s = jnp.where(full, s, -1e30)
    else:
        if kv_valid is not None:
            mask &= kv_valid[None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkh->bqkgh", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, sq, hq, hd).astype(q.dtype)


def _largest_divisor_leq(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (chunk sizes must tile exactly)."""
    for d in range(min(target, n), 0, -1):
        if n % d == 0:
            return d
    return 1


def _pair_mask(q_pos, k_pos, causal: bool, window: int):
    mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= k_pos[None, :] > (q_pos[:, None] - window)
    return mask


def _flash_forward(q, k, v, n_kv, causal, window, q_chunk, kv_chunk, q_offset=None):
    """Chunked online-softmax attention -> (out, lse).

    Memory: O(q_chunk * kv_chunk) scores per step instead of O(S^2); the
    returned logsumexp (b, n_kv, g, sq) is the flash-2 backward residual.
    """
    b, sq, hq, hd = q.shape
    skv = k.shape[1]
    g = hq // n_kv
    nq, nk = sq // q_chunk, skv // kv_chunk
    scale = 1.0 / math.sqrt(hd)

    # storage dtype stays (bf16 on the serve/train path); MXU accumulates f32
    qg = q.reshape(b, nq, q_chunk, n_kv, g, hd)
    kc = k.reshape(b, nk, kv_chunk, n_kv, hd)
    vc = v.reshape(b, nk, kv_chunk, n_kv, hd)
    off = (skv - sq) if q_offset is None else q_offset

    def q_step(_, qi):
        qblk, qidx = qi  # (b, q_chunk, n_kv, g, hd), scalar chunk index
        q_pos = qidx * q_chunk + jnp.arange(q_chunk) + off

        def kv_step(carry, ki):
            acc, m, l = carry
            kblk, vblk, kidx = ki
            k_pos = kidx * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqkgh,btkh->bkgqt", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            mask = _pair_mask(q_pos, k_pos, causal, window)
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkh->bkgqh", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, n_kv, g, q_chunk, hd), jnp.float32)
        m0 = jnp.full((b, n_kv, g, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, n_kv, g, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.arange(nk)),
        )
        l = jnp.maximum(l, 1e-30)
        return None, (acc / l[..., None], m + jnp.log(l))

    _, (outs, lses) = jax.lax.scan(q_step, None,
                                   (jnp.moveaxis(qg, 1, 0), jnp.arange(nq)))
    # outs: (nq, b, n_kv, g, q_chunk, hd) -> (b, sq, hq, hd)
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 1, 4, 2, 3, 5).reshape(b, sq, hq, hd)
    # lses: (nq, b, n_kv, g, q_chunk) -> (b, n_kv, g, sq)
    lse = jnp.moveaxis(lses, 0, 3).reshape(b, n_kv, g, sq)
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash_cvjp(n_kv, causal, window, q_chunk, kv_chunk, q, k, v, q_off):
    out, _ = _flash_forward(q, k, v, n_kv, causal, window, q_chunk, kv_chunk,
                            q_offset=q_off)
    return out


def _flash_cvjp_fwd(n_kv, causal, window, q_chunk, kv_chunk, q, k, v, q_off):
    out, lse = _flash_forward(q, k, v, n_kv, causal, window, q_chunk, kv_chunk,
                              q_offset=q_off)
    return out, (q, k, v, out, lse, q_off)


def _flash_cvjp_bwd(n_kv, causal, window, q_chunk, kv_chunk, res, do):
    """Flash-2 backward: recompute probabilities per kv chunk from the saved
    logsumexp — residual memory O(S·h), never O(S^2).

    Without this, differentiating the forward scan stacks every (q,kv) chunk
    pair's probabilities: a 515 GB f32 tensor per layer on llama4 train_4k
    (EXPERIMENTS.md §Perf).
    """
    q, k, v, out, lse, q_off = res
    b, sq, hq, hd = q.shape
    skv = k.shape[1]
    g = hq // n_kv
    nk = skv // kv_chunk
    scale = 1.0 / math.sqrt(hd)
    off = (skv - sq) if q_off is None else q_off

    qg = q.reshape(b, sq, n_kv, g, hd)
    dog = do.reshape(b, sq, n_kv, g, hd)
    # delta_i = sum_h do_i * out_i  (rowwise, f32)
    delta = jnp.einsum("bqkgh,bqkgh->bkgq", dog.astype(jnp.float32),
                       out.reshape(b, sq, n_kv, g, hd).astype(jnp.float32))
    q_pos = jnp.arange(sq) + off
    kc = k.reshape(b, nk, kv_chunk, n_kv, hd)
    vc = v.reshape(b, nk, kv_chunk, n_kv, hd)

    def kv_step(dq_acc, ki):
        kblk, vblk, kidx = ki                       # (b, kv_chunk, n_kv, hd)
        k_pos = kidx * kv_chunk + jnp.arange(kv_chunk)
        s = jnp.einsum("bqkgh,btkh->bkgqt", qg, kblk,
                       preferred_element_type=jnp.float32) * scale
        mask = _pair_mask(q_pos, k_pos, causal, window)
        p = jnp.where(mask[None, None, None],
                      jnp.exp(s - lse[..., None]), 0.0)      # (b,k,g,sq,t)
        pb = p.astype(v.dtype)
        dv_blk = jnp.einsum("bkgqt,bqkgh->btkh", pb, dog,
                            preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqkgh,btkh->bkgqt", dog, vblk,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[..., None]) * scale).astype(q.dtype)
        dq_acc += jnp.einsum("bkgqt,btkh->bqkgh", ds, kblk,
                             preferred_element_type=jnp.float32)
        dk_blk = jnp.einsum("bkgqt,bqkgh->btkh", ds, qg,
                            preferred_element_type=jnp.float32)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, sq, n_kv, g, hd), jnp.float32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        kv_step, dq0,
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.arange(nk)))
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(b, skv, n_kv, hd)
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(b, skv, n_kv, hd)
    return (dq.reshape(b, sq, hq, hd).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype), None)


_flash_cvjp.defvjp(_flash_cvjp_fwd, _flash_cvjp_bwd)


def _flash_attention(q, k, v, n_kv, *, causal, window=0,
                     q_chunk=Q_CHUNK, kv_chunk=KV_CHUNK, q_offset=None):
    """Flash attention with an O(S·h)-residual custom VJP (flash-2 backward).

    ``q_offset``: global position of q row 0 (sequence-parallel prefill passes
    the rank offset; default assumes q is the trailing window of the KV).
    """
    sq, skv = q.shape[1], k.shape[1]
    q_chunk = _largest_divisor_leq(sq, q_chunk)
    kv_chunk = _largest_divisor_leq(skv, kv_chunk)
    return _flash_cvjp(n_kv, causal, window, q_chunk, kv_chunk, q, k, v, q_offset)


def attention(
    p: dict,
    x: jax.Array,
    cfg,
    positions: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    kv: tuple[jax.Array, jax.Array] | None = None,  # cross-attention K/V override
    bits=None,
    qimpl: str = "auto",
) -> jax.Array:
    """Full-sequence attention (train / prefill / encoder / cross)."""
    hd = cfg.resolved_head_dim
    if kv is None:
        q, k, v = _qkv(p, x, cfg, positions, bits=bits, qimpl=qimpl)
    else:
        q = _split_heads(_q_proj(p, x, cfg, bits=bits, qimpl=qimpl), cfg.n_heads, hd)
        if cfg.qk_norm:
            q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        if cfg.rope == "default":
            q = apply_rope(q, positions, cfg.rope_theta)
        k, v = kv
    skv = k.shape[1]
    if max(x.shape[1], skv) > FLASH_THRESHOLD and x.shape[1] > 1:
        o = _flash_attention(q, k, v, cfg.n_kv_heads, causal=causal, window=window)
    else:
        o = _direct_attention(q, k, v, cfg.n_kv_heads, causal=causal, window=window)
    b, s, _, _ = o.shape
    return qdense(p["wo"], o.reshape(b, s, -1), bits=_b(bits, "wo"), qimpl=qimpl)


def cross_kv(p: dict, ctx: jax.Array, cfg, *, bits=None, qimpl: str = "auto"):
    """Precompute cross-attention K/V from encoder output (whisper decode)."""
    hd = cfg.resolved_head_dim
    kf, vf = _kv_proj(p, ctx, cfg, bits=bits, qimpl=qimpl)
    k = _split_heads(kf, cfg.n_kv_heads, hd)
    v = _split_heads(vf, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    return k, v


def decode_attend_one(
    cache,                        # {"k","v"} dict | QuantizedKVLayer | PagedKVLayer
    q: jax.Array,                 # (B, 1, hq, hd) post-RoPE query
    k_new: jax.Array,             # (B, 1, n_kv, hd) post-RoPE key
    v_new: jax.Array,
    pos: jax.Array,               # () or (B,) int32 — write/attend position
    cfg,
    *,
    window: int = 0,
    qimpl: str = "auto",
):
    """Write ONE position's K/V at ``pos`` and attend over cache[: pos+1].

    The append+attend core shared by the per-token decode step
    (:func:`attention_decode` / :func:`attention_decode_quant`) and the
    speculative verify burst (models/decoder.decode_verify) — one code path,
    so a burst position is bitwise the decode step it replaces (DESIGN.md
    §13).  Returns ``(o (B, 1, hq, hd), cache)``.
    """
    from repro.kernels.quant_kv.ops import quant_kv_decode_step

    b = q.shape[0]
    if isinstance(cache, dict):
        cache_k, cache_v = cache["k"], cache["v"]
        skv = cache_k.shape[1]
        if jnp.ndim(pos) == 0:
            cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new.astype(cache_k.dtype), pos, axis=1)
            cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new.astype(cache_v.dtype), pos, axis=1)
            kv_valid = jnp.arange(skv) <= pos
            if window:
                kv_valid &= jnp.arange(skv) > pos - window
        else:  # per-slot positions
            upd = jax.vmap(lambda c, n, p_: jax.lax.dynamic_update_slice_in_dim(c, n, p_, axis=0))
            cache_k = upd(cache_k, k_new.astype(cache_k.dtype), pos)
            cache_v = upd(cache_v, v_new.astype(cache_v.dtype), pos)
            kv_valid = jnp.arange(skv)[None, :] <= pos[:, None]
            if window:
                kv_valid &= jnp.arange(skv)[None, :] > (pos[:, None] - window)
        o = _direct_attention(q, cache_k, cache_v, cfg.n_kv_heads,
                              causal=False, kv_valid=kv_valid)
        return o, {"k": cache_k, "v": cache_v}
    skv = cache.seq
    posv = jnp.asarray(pos, jnp.int32).reshape(-1)[:, None]   # (B or 1, 1)
    kv_valid = jnp.broadcast_to(jnp.arange(skv)[None, :] <= posv, (b, skv))
    if window:
        kv_valid &= jnp.broadcast_to(jnp.arange(skv)[None, :] > (posv - window),
                                     (b, skv))
    # ONE fused dispatch per layer: dequant + append/requant + attend —
    # bitwise-identical to the quant_kv_append -> quant_kv_attention pair
    # (parity-pinned), but the packed cache bytes move once per step.
    o, cache = quant_kv_decode_step(q, cache, pos, k_new, v_new, kv_valid,
                                    impl=qimpl, out_dtype=q.dtype)
    return o, cache


def attention_decode(
    p: dict,
    x: jax.Array,                 # (B, 1, d) — one new token
    cache_k: jax.Array,           # (B, S, n_kv, hd)
    cache_v: jax.Array,
    pos: jax.Array,               # () int32 — write/attend position
    cfg,
    *,
    window: int = 0,
    bits=None,
    qimpl: str = "auto",
):
    """One decode step: write K/V at ``pos``, attend over cache[: pos+1].

    ``pos`` may be a scalar (lockstep batch — the dry-run serve_step) or a
    (B,) vector (continuous batching: every slot at its own position).
    """
    b = x.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1, 1), (b, 1))
    q, k_new, v_new = _qkv(p, x, cfg, positions, bits=bits, qimpl=qimpl)
    o, cache = decode_attend_one({"k": cache_k, "v": cache_v}, q, k_new, v_new,
                                 pos, cfg, window=window, qimpl=qimpl)
    y = qdense(p["wo"], o.reshape(b, 1, -1), bits=_b(bits, "wo"), qimpl=qimpl)
    return y, (cache["k"], cache["v"])


def attention_decode_quant(
    p: dict,
    x: jax.Array,                 # (B, 1, d) — one new token
    cache,                        # kvcache.QuantizedKVLayer
    pos: jax.Array,               # () or (B,) int32 — write/attend position
    cfg,
    *,
    window: int = 0,
    bits=None,
    qimpl: str = "auto",
):
    """One decode step over a *packed* KV cache (DESIGN.md §11).

    Mirrors :func:`attention_decode` but the cache is a quantized
    ``QuantizedKVLayer``: the new K/V requantize exactly one sequence block
    (append), and attention dequantizes inside the kernel — the packed
    lanes are the only state bytes the step moves.  ``qimpl`` carries over:
    "xla" runs the jnp reference, "pallas"/"interpret" the fused kernels.
    """
    b = x.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1, 1), (b, 1))
    q, k_new, v_new = _qkv(p, x, cfg, positions, bits=bits, qimpl=qimpl)
    o, cache = decode_attend_one(cache, q, k_new, v_new, pos, cfg,
                                 window=window, qimpl=qimpl)
    o = o.astype(x.dtype)
    y = qdense(p["wo"], o.reshape(b, 1, -1), bits=_b(bits, "wo"), qimpl=qimpl)
    return y, cache


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------


def mlp_init(key, cfg, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 3)
    if cfg.mlp in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], cfg.d_model, cfg.d_ff, dtype),
            "w_up": dense_init(ks[1], cfg.d_model, cfg.d_ff, dtype),
            "w_down": dense_init(ks[2], cfg.d_ff, cfg.d_model, dtype),
        }
    return {  # plain gelu (whisper)
        "w_up": dense_init(ks[0], cfg.d_model, cfg.d_ff, dtype),
        "w_down": dense_init(ks[1], cfg.d_ff, cfg.d_model, dtype),
    }


def mlp(p: dict, x: jax.Array, kind: str, *, bits=None, qimpl: str = "auto") -> jax.Array:
    if kind in ("swiglu", "geglu"):
        if "w_gu" in p:  # pack-time fused gate|up group (one launch, halve)
            gu = qdense(p["w_gu"], x, bits=_b(bits, "w_gu"), qimpl=qimpl)
            g, u = jnp.split(gu, 2, axis=-1)
        else:
            g = qdense(p["w_gate"], x, bits=_b(bits, "w_gate"), qimpl=qimpl)
            u = qdense(p["w_up"], x, bits=_b(bits, "w_up"), qimpl=qimpl)
        act = jax.nn.silu(g) if kind == "swiglu" else jax.nn.gelu(g, approximate=True)
        return qdense(p["w_down"], act * u, bits=_b(bits, "w_down"), qimpl=qimpl)
    h = jax.nn.gelu(qdense(p["w_up"], x, bits=_b(bits, "w_up"), qimpl=qimpl), approximate=True)
    return qdense(p["w_down"], h, bits=_b(bits, "w_down"), qimpl=qimpl)
