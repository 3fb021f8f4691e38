"""Plain float32 reference of a served dense decoder, for the check that
decides ``correct``.

It imports nothing of the program.  From the seed it draws the same
bfloat16 weights the benchmark gave the program (``weights.make``),
quantizes them itself as the configuration states (symmetric, one scale
per output channel, ``2**(b-1) - 1`` levels each side, per-layer bits from
the configuration file), and runs the model in float32 at highest matmul
precision, one layer at a time.

Served tokens come from decode steps that read a 4-bit KV cache, so the
reference keeps that cache as the configuration states it: the prompt's
K/V rows are quantized per 16-position block and head when the prompt is
admitted; every decode step then writes one row, dequantizes the block it
lands in, puts the row in, zeroes the positions after it, and requantizes
the block under a fresh scale; attention reads the cache dequantized,
the new row included.  Prompt positions attend over float K/V, as a
prefill does.  Teacher-forced over the prompt and the served tokens, one
pass gives the logits behind every served token.

``control=True`` is the control: the same pass with every matmul input
(the activations entering Q/K/V, the output projection, the MLP and the
LM head) rounded to float8 e4m3 under one scale per row -- the W4A8 step
below the bfloat16 the configuration states.

The weights are drawn one layer at a time, and every sampled request (and
its control) passes through a layer before the next is drawn, so a whole
model in float never sits on the chip.  Every request is padded to the
cell's ``max_seq`` positions, so one program serves them all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 256


def _qmax(bits):
    return jnp.exp2(jnp.asarray(bits, jnp.float32) - 1.0) - 1.0


def dequant_weight(w, bits, axis: int = 0):
    """Per-output-channel symmetric quantization of ``w`` (reduced over
    ``axis``), returned dequantized in float32."""
    w = w.astype(jnp.float32)
    q = _qmax(bits)
    scale = (jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-12)
             / q).astype(jnp.float32)
    return jnp.clip(jnp.round(w / scale), -q, q) * scale


def _act(x, control: bool):
    """Matmul input as the control computes it: float8 (e4m3, four
    significant bits, subnormal below 2**-6) under one scale per row that
    maps the row's largest magnitude to 448, the format's largest; as is
    for the reference itself."""
    if not control:
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 448.0
    y = x / s
    _, e = jnp.frexp(y)
    ulp = jnp.exp2(jnp.maximum(e, -5).astype(jnp.float32) - 4.0)
    return jnp.round(y / ulp) * ulp * s


def _mm(x, w, control):
    return jnp.matmul(_act(x, control), w, precision=HIGHEST)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """Rotate-half rotary embedding at positions 0..T-1; x (T, H, hd)."""
    t, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block_quant(x, bits, block):
    """(T, H, hd) -> levels (nb, H, block, hd), scales (nb, H)."""
    t, h, hd = x.shape
    xb = jnp.swapaxes(x.reshape(t // block, block, h, hd), 1, 2)
    q = _qmax(bits)
    sc = jnp.maximum(jnp.max(jnp.abs(xb), axis=(2, 3)), 1e-12) / q
    return jnp.clip(jnp.round(xb / sc[..., None, None]), -q, q), sc


def kv_cache_views(x, bits, block, w, total):
    """The quantized cache one side (K or V) holds, as decode reads it.

    ``x`` (T, H, hd) float rows; positions < ``w`` are admitted as the
    prompt, positions ``w .. total-1`` appended one by one.  Returns
    ``(final, current)``: ``final`` (T, H, hd) is each block's content
    once the writes have moved past it, ``current`` (T, H, block, hd) the
    block that position t lands in, right after t was appended."""
    t_pad, h, hd = x.shape
    pos = jnp.arange(t_pad)
    pre_lev, pre_sc = _block_quant(
        jnp.where((pos < w)[:, None, None], x, 0.0), bits, block)
    q = _qmax(bits)
    idx = jnp.arange(block)[None, :, None]

    def step(carry, t):
        lev, sc = carry
        b, off = t // block, t % block
        start = (t == w) | (off == 0)
        lev = jnp.where(start, pre_lev[b], lev)
        sc = jnp.where(start, pre_sc[b], sc)
        fp = lev * sc[:, None, None]
        fp = jnp.where(idx < off, fp, 0.0)
        fp = jnp.where(idx == off, x[t][:, None, :], fp)
        sc_new = jnp.maximum(jnp.max(jnp.abs(fp), axis=(1, 2)), 1e-12) / q
        lev_new = jnp.clip(jnp.round(fp / sc_new[:, None, None]), -q, q)
        live = (t >= w) & (t < total)
        lev = jnp.where(live, lev_new, lev)
        sc = jnp.where(live, sc_new, sc)
        return (lev, sc), lev_new * sc_new[:, None, None]

    init = (jnp.zeros((h, block, hd), jnp.float32), jnp.ones((h,), jnp.float32))
    _, current = jax.lax.scan(step, init, pos)                # (T, H, blk, hd)
    nb = t_pad // block
    last = jnp.minimum(jnp.arange(nb) * block + block - 1, total - 1)
    appended = (last >= w) & (jnp.arange(nb) * block <= total - 1)
    pre = pre_lev * pre_sc[..., None, None]                    # (nb, H, blk, hd)
    final = jnp.where(appended[:, None, None, None], current[last], pre)
    return jnp.swapaxes(final, 1, 2).reshape(t_pad, h, hd), current


def _attention(q, k, v, kc, vc, kf, vf, w, n_kv, block):
    """Causal attention for every position: float K/V below ``w`` (the
    prompt, as prefilled), the quantized cache from ``w`` on (decode)."""
    t_pad, hq, hd = q.shape
    g = hq // n_kv
    scale = 1.0 / np.sqrt(hd)
    jpos = jnp.arange(t_pad)
    n = max(c for c in range(1, min(Q_CHUNK, t_pad) + 1) if t_pad % c == 0)

    def chunk(c0):
        tq = c0 + jnp.arange(n)
        qc = jax.lax.dynamic_slice_in_dim(q, c0, n).reshape(
            n, n_kv, g, hd) * scale
        # prompt positions: float K/V, causal
        s = jnp.einsum("tkgh,jkh->tkgj", qc, k, precision=HIGHEST)
        s = jnp.where((jpos[None, :] <= tq[:, None])[:, None, None, :], s,
                      -jnp.inf)
        o_pre = jnp.einsum("tkgj,jkh->tkgh", jax.nn.softmax(s, -1), v,
                           precision=HIGHEST)
        # decode positions: finished blocks, then the block being written
        bstart = (tq // block) * block
        sf = jnp.einsum("tkgh,jkh->tkgj", qc, kf, precision=HIGHEST)
        sf = jnp.where((jpos[None, :] < bstart[:, None])[:, None, None, :],
                       sf, -jnp.inf)
        kcc = jax.lax.dynamic_slice_in_dim(kc, c0, n)          # (C, H, blk, hd)
        vcc = jax.lax.dynamic_slice_in_dim(vc, c0, n)
        sc = jnp.einsum("tkgh,tkbh->tkgb", qc, kcc, precision=HIGHEST)
        sc = jnp.where((jnp.arange(block)[None, :] <= (tq % block)[:, None])
                       [:, None, None, :], sc, -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([sf, sc], -1), -1)
        o_dec = (jnp.einsum("tkgj,jkh->tkgh", p[..., :t_pad], vf,
                            precision=HIGHEST)
                 + jnp.einsum("tkgb,tkbh->tkgh", p[..., t_pad:], vcc,
                              precision=HIGHEST))
        o = jnp.where((tq < w)[:, None, None, None], o_pre, o_dec)
        return o.reshape(n, hq * hd)

    return jax.lax.map(chunk, jnp.arange(0, t_pad, n)).reshape(
        t_pad, hq * hd)


@functools.partial(jax.jit, static_argnames=("dims", "control"))
def _layer(x, lp, bits, w, total, *, dims, control):
    hq, n_kv, hd, theta, eps, kv_bits, block = dims
    t = x.shape[0]
    deq = lambda name: dequant_weight(lp[name], bits)
    xn = _rmsnorm(x, lp["ln1"].astype(jnp.float32), eps)
    q = _rope(_mm(xn, deq("wq"), control).reshape(t, hq, hd), theta)
    k = _rope(_mm(xn, deq("wk"), control).reshape(t, n_kv, hd), theta)
    v = _mm(xn, deq("wv"), control).reshape(t, n_kv, hd)
    kf, kc = kv_cache_views(k, kv_bits[0], block, w, total)
    vf, vc = kv_cache_views(v, kv_bits[1], block, w, total)
    o = _attention(q, k, v, kc, vc, kf, vf, w, n_kv, block)
    h = x + _mm(o, deq("wo"), control)
    hn = _rmsnorm(h, lp["ln2"].astype(jnp.float32), eps)
    gate = _mm(hn, deq("w_gate"), control)
    up = _mm(hn, deq("w_up"), control)
    return h + _mm(jax.nn.silu(gate) * up, deq("w_down"), control)


@functools.partial(jax.jit, static_argnames=("bits",))
def _embed(table, tokens, *, bits):
    rows = jnp.take(table, tokens, axis=0)
    return dequant_weight(rows, bits, axis=1)


@functools.partial(jax.jit, static_argnames=("bits", "vocab", "n_out", "eps",
                                             "control"))
def _logits(x, gain, head, start, *, bits, vocab, n_out, eps, control):
    x = jnp.pad(x, ((0, n_out), (0, 0)))
    rows = jax.lax.dynamic_slice_in_dim(x, start, n_out)
    hn = _rmsnorm(rows, gain.astype(jnp.float32), eps)
    return _mm(hn, dequant_weight(head, bits)[:, :vocab], control)


@jax.jit
def _gaps(ref, tokens):
    """Per row, how far the reference logit of ``tokens`` lies below the
    row's best."""
    got = jnp.take_along_axis(ref, tokens[:, None], axis=1)[:, 0]
    return ref.max(axis=1) - got


class Reference:
    """The reference model of one configuration at one seed."""

    def __init__(self, conf: dict, seed: int, *, max_seq: int, max_out: int):
        self.conf, self.seed = conf, seed
        self.max_seq = max_seq
        s = conf["serving"]
        self.bits = s["weight_bits"]
        self.max_out = max_out
        self.dims = (conf["num_attention_heads"], conf["num_key_value_heads"],
                     conf["head_dim"], float(conf["rope_theta"]),
                     float(conf["rms_norm_eps"]), tuple(s["kv_bits"]),
                     s["kv_block"])

    def gaps(self, requests: list[tuple[list[int], list[int]]],
             control: bool = False) -> list[tuple[np.ndarray, np.ndarray]]:
        """For each ``(prompt, served)``, teacher-forced: the gap of every
        served token (inf for an id outside the vocabulary) and, with
        ``control``, the gap of the token the control puts first at the
        same position (else an empty array)."""
        conf, vocab = self.conf, self.conf["vocab_size"]
        variants = (False, True) if control else (False,)
        top = weights.top(conf, self.seed)
        shapes, xs = [], {}
        for r, (prompt, served) in enumerate(requests):
            toks = list(prompt) + list(served[:-1])
            total = len(toks)
            if total > self.max_seq or len(served) > self.max_out:
                raise ValueError(f"request of {total} positions, "
                                 f"{len(served)} served, exceeds the "
                                 f"reference's shapes")
            padded = np.zeros(self.max_seq, np.int32)
            padded[:total] = toks
            shapes.append((jnp.int32(len(prompt) - 1), jnp.int32(total)))
            x = _embed(top["embed"], jnp.asarray(padded),
                       bits=self.bits["embed"])
            for v in variants:
                xs[r, v] = x
        for i in range(conf["num_hidden_layers"]):
            lp = weights.layer(conf, self.seed, i)
            lp = {"ln1": lp["ln1"], "ln2": lp["ln2"], **lp["attn"],
                  **lp["mlp"]}
            bits = self.bits["layers"][i]
            for r, v in list(xs):
                xs[r, v] = _layer(xs[r, v], lp, bits, *shapes[r],
                                  dims=self.dims, control=v)
            del lp

        def head(r, v):
            return _logits(
                xs[r, v], top["final_norm"], top["lm_head"], shapes[r][0],
                bits=self.bits["lm_head"], vocab=vocab, n_out=self.max_out,
                eps=self.dims[4], control=v)

        out = []
        for r, (_, served) in enumerate(requests):
            ref = head(r, False)
            tok = np.zeros(self.max_out, np.int32)
            tok[:len(served)] = np.clip(served, 0, vocab - 1)
            gap = np.asarray(_gaps(ref, jnp.asarray(tok)))[:len(served)]
            gap = np.where((np.asarray(served) < 0)
                           | (np.asarray(served) >= vocab), np.inf, gap)
            low = (np.asarray(_gaps(ref, head(r, True).argmax(axis=1).astype(
                jnp.int32)))[:len(served)] if control else np.zeros(0))
            out.append((gap.astype(np.float64), low.astype(np.float64)))
        return out
