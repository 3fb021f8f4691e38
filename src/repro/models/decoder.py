"""Decoder-only LM covering the dense / moe / vlm families.

Two parameter layouts:
  * train:  per-layer params stacked (L, ...) and run under lax.scan with
            remat — compile time O(1) in depth, per-layer bits ride as (L,)
            scan inputs (QAT).
  * serve:  per-layer list (unstacked) run unrolled — heterogeneous packed
            int shapes per layer (mixed bitwidths) make stacking impossible;
            real mixed-precision engines unroll too (DESIGN.md §5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.dist.sharding import shard_batch_act
from repro.quant.tensor import QuantizedTensor
from . import layers, moe


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def init(cfg, key) -> dict:
    dt = _dtype(cfg)
    keys = jax.random.split(key, cfg.n_layers + 3)

    def layer(k):
        ka, km = jax.random.split(k)
        p = {
            "attn": layers.attention_init(ka, cfg, dt),
            "ln1": layers.norm_init(cfg.d_model, cfg.norm, dt),
            "ln2": layers.norm_init(cfg.d_model, cfg.norm, dt),
        }
        p["mlp"] = moe.moe_init(km, cfg, dt) if cfg.family == "moe" else layers.mlp_init(km, cfg, dt)
        return p

    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *[layer(keys[i]) for i in range(cfg.n_layers)])
    params = {
        "embed": layers.embed_init(keys[-3], cfg.vocab_size, cfg.d_model, dt),
        "layers": stacked,
        "final_norm": layers.norm_init(cfg.d_model, cfg.norm, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.dense_init(keys[-2], cfg.d_model, cfg.vocab_size, dt)
    return params


def embed_tokens(params, tokens, cfg, *, bits=None):
    emb = params["embed"]
    if isinstance(emb, QuantizedTensor):
        # emb stored in lm_head layout (d, V): packed (V, d/lanes), scale (1, V)
        rows = jnp.take(emb.packed, tokens, axis=0)
        lev = packing.unpack(rows, emb.bits, emb.k)
        scale = jnp.take(emb.scale[0], tokens)[..., None]
        return (lev.astype(jnp.float32) * scale).astype(_dtype(cfg))
    if bits is not None:
        from repro.kernels.fake_quant.ops import fake_quant_ste
        emb = fake_quant_ste(emb, bits, "xla")
    return jnp.take(emb, tokens, axis=0)


def _layer_body(x, lp, cfg, positions, lb, qimpl):
    x = shard_batch_act(x)  # pin batch sharding on the scan carry
    h = x + layers.attention(lp["attn"], layers.norm(lp["ln1"], x, cfg.norm, cfg.norm_eps),
                             cfg, positions, causal=True,
                             bits=None if lb is None else lb.get("attn"), qimpl=qimpl)
    hn = layers.norm(lp["ln2"], h, cfg.norm, cfg.norm_eps)
    if cfg.family == "moe":
        ff = moe.moe_mlp(lp["mlp"], hn, cfg, bits=None if lb is None else lb.get("mlp"),
                         qimpl=qimpl)
    else:
        ff = layers.mlp(lp["mlp"], hn, cfg.mlp, bits=None if lb is None else lb.get("mlp"),
                        qimpl=qimpl)
    return h + ff


def forward(params, cfg, tokens=None, embeds=None, *, bits=None, qimpl="auto",
            remat: bool = True) -> jax.Array:
    """Full-sequence forward -> final hidden states (B, S, d).

    ``bits``: None, or {"embed": scalar, "layers": pytree of (L,) arrays,
    "lm_head": scalar} (QAT per-layer bitwidths).
    """
    if embeds is None:
        x = embed_tokens(params, tokens, cfg, bits=None if bits is None else bits.get("embed"))
    else:
        x = embeds.astype(_dtype(cfg))
    x = shard_batch_act(x)
    b, s = x.shape[:2]
    positions = layers.position_ids(b, s, cfg.rope)

    layer_bits = None if bits is None else bits["layers"]

    def body(h, xs):
        lp, lb = xs
        return _layer_body(h, lp, cfg, positions, lb, qimpl), None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    xs = (params["layers"], layer_bits)
    if layer_bits is None:
        # scan needs a concrete pytree; replace None with per-layer dummy
        xs = (params["layers"], jnp.zeros((cfg.n_layers,)))

        def body2(h, xs):  # noqa: ANN001
            lp, _ = xs
            return _layer_body(h, lp, cfg, positions, None, qimpl), None

        body2 = jax.checkpoint(body2, policy=jax.checkpoint_policies.nothing_saveable) if remat else body2
        x, _ = jax.lax.scan(body2, x, xs)
    else:
        x, _ = jax.lax.scan(body, x, xs)
    return layers.norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)


def logits_fn(params, hidden, cfg, *, bits=None, qimpl="auto") -> jax.Array:
    if cfg.tie_embeddings and "lm_head" not in params:
        emb = params["embed"]
        if isinstance(emb, QuantizedTensor):
            w = emb.dequantize(hidden.dtype)  # (d, V)
            return layers.qdense(w, hidden, qimpl=qimpl)
        return layers.qdense(emb.T, hidden, bits=None if bits is None else bits.get("embed"),
                             qimpl=qimpl)
    return layers.qdense(params["lm_head"], hidden,
                         bits=None if bits is None else bits.get("lm_head"), qimpl=qimpl)


def lm_loss(params, cfg, tokens=None, labels=None, embeds=None, *, bits=None,
            qimpl="auto", loss_chunk: int = 2048) -> jax.Array:
    """Chunked-over-sequence softmax cross-entropy (full logits never live)."""
    hidden = forward(params, cfg, tokens=tokens, embeds=embeds, bits=bits, qimpl=qimpl)
    b, s, d = hidden.shape
    chunk = min(loss_chunk, s)
    assert s % chunk == 0, (s, chunk)
    n = s // chunk
    hid = hidden.reshape(b, n, chunk, d).swapaxes(0, 1)      # (n, b, chunk, d)
    lab = labels.reshape(b, n, chunk).swapaxes(0, 1)

    @jax.checkpoint  # recompute chunk logits in backward: O(chunk*V) live, not O(S*V)
    def step(acc, xs):
        h, y = xs
        logits = logits_fn(params, h, cfg, bits=bits, qimpl=qimpl).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (hid, lab))
    return total / (b * s)


# ---------------------------------------------------------------------------
# serving layout
# ---------------------------------------------------------------------------


def unstack_layers(params, cfg) -> dict:
    """(L, ...)-stacked train params -> per-layer list for the serve path."""
    out = dict(params)
    out["layers"] = [
        jax.tree.map(lambda a: a[i], params["layers"]) for i in range(cfg.n_layers)
    ]
    return out


def init_cache(cfg, batch: int, seq: int, dtype=jnp.bfloat16, *,
               state_bits=None, block: int | None = None, paged: bool = False,
               pool_blocks: int | None = None) -> list[dict]:
    """Decode KV cache: fp ``{"k","v"}`` dicts, packed ``QuantizedKVLayer``
    containers when ``state_bits`` (per-layer ``[(k_bits, v_bits), ...]``)
    is given (DESIGN.md §11), or block-pool ``PagedKVLayer`` containers when
    additionally ``paged`` (DESIGN.md §12; ``pool_blocks`` usable physical
    blocks, default the dense-equivalent ``batch * seq / block``)."""
    hd = cfg.resolved_head_dim
    if paged:
        from repro.kvcache.cache import DEFAULT_BLOCK, resolve_block
        from repro.kvcache.paged import init_paged_layer

        if state_bits is None:
            raise ValueError("paged KV cache requires state_bits (the pool "
                             "stores packed lanes only)")
        if len(state_bits) != cfg.n_layers:
            raise ValueError(f"state_bits has {len(state_bits)} entries for "
                             f"{cfg.n_layers} layers")
        blk = resolve_block(seq, block or DEFAULT_BLOCK)
        n_blocks = pool_blocks or (batch * seq) // blk
        return [
            init_paged_layer(n_blocks, batch, seq, cfg.n_kv_heads, hd,
                             k_bits=kb, v_bits=vb, block=blk)
            for kb, vb in state_bits
        ]
    if state_bits is not None:
        from repro.kvcache.cache import DEFAULT_BLOCK, init_kv_layer

        if len(state_bits) != cfg.n_layers:
            raise ValueError(f"state_bits has {len(state_bits)} entries for "
                             f"{cfg.n_layers} layers")
        return [
            init_kv_layer(batch, seq, cfg.n_kv_heads, hd, k_bits=kb, v_bits=vb,
                          block=block or DEFAULT_BLOCK)
            for kb, vb in state_bits
        ]
    return [
        {
            "k": jnp.zeros((batch, seq, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, seq, cfg.n_kv_heads, hd), dtype),
        }
        for _ in range(cfg.n_layers)
    ]


def abstract_cache(cfg, batch: int, seq: int, dtype=jnp.bfloat16) -> list[dict]:
    hd = cfg.resolved_head_dim
    kv = jax.ShapeDtypeStruct((batch, seq, cfg.n_kv_heads, hd), dtype)
    return [{"k": kv, "v": kv} for _ in range(cfg.n_layers)]


def prefill(params, cfg, tokens=None, embeds=None, *, qimpl="auto", lengths=None):
    """Full-sequence forward that also returns the KV cache (serve prefill).

    Layers run unrolled (params may be per-layer heterogeneous quantized).
    ``lengths`` (per-row valid prompt lengths) is accepted for API symmetry
    with the SSM/hybrid prefills and ignored: causal attention already makes
    valid positions independent of right-padding, and pad-position cache
    rows are masked at decode by the per-slot ``kv_valid``.
    """
    del lengths
    if embeds is None:
        x = embed_tokens(params, tokens, cfg)
    else:
        x = embeds.astype(_dtype(cfg))
    x = shard_batch_act(x)
    b, s = x.shape[:2]
    positions = layers.position_ids(b, s, cfg.rope)
    caches = []
    for lp in params["layers"]:
        xn = layers.norm(lp["ln1"], x, cfg.norm, cfg.norm_eps)
        hd = cfg.resolved_head_dim
        q, k, v = layers._qkv(lp["attn"], xn, cfg, positions, qimpl=qimpl)
        caches.append({"k": k, "v": v})
        if s > layers.FLASH_THRESHOLD:
            o = layers._flash_attention(q, k, v, cfg.n_kv_heads, causal=True)
        else:
            o = layers._direct_attention(q, k, v, cfg.n_kv_heads, causal=True)
        o = layers.qdense(lp["attn"]["wo"], o.reshape(b, s, -1), qimpl=qimpl)
        h = x + o
        hn = layers.norm(lp["ln2"], h, cfg.norm, cfg.norm_eps)
        if cfg.family == "moe":
            x = h + moe.moe_mlp(lp["mlp"], hn, cfg, qimpl=qimpl)
        else:
            x = h + layers.mlp(lp["mlp"], hn, cfg.mlp, qimpl=qimpl)
    hidden = layers.norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = logits_fn(params, hidden[:, -1:], cfg, qimpl=qimpl)
    return logits, caches


def init_prefill_scratch(cfg, seq: int, dtype=None) -> list[dict]:
    """Per-layer fp K/V scratch carried across prefill chunks (one slot).

    The chunked prefill never reads the (possibly quantized) decode state:
    each chunk writes its fp K/V rows here and attends over the scratch, so
    the rows that finally insert into the cache are computed from exactly
    the same fp values a whole-prompt prefill would have produced — which is
    what keeps chunked admission token-identical across fp / quantized /
    paged caches (the quantizer runs once, at insert, on full fp rows).
    """
    hd = cfg.resolved_head_dim
    dt = _dtype(cfg) if dtype is None else dtype
    kv = lambda: jnp.zeros((1, seq, cfg.n_kv_heads, hd), dt)
    return [{"k": kv(), "v": kv()} for _ in range(cfg.n_layers)]


def prefill_chunk(params, cfg, scratch, tokens, offset, *, qimpl="auto"):
    """One prefill chunk: tokens ``(1, C)`` at absolute positions
    ``offset .. offset + C - 1`` -> updated scratch (see
    :func:`init_prefill_scratch`).

    Per layer: the chunk's K/V rows land in the scratch at ``offset`` and
    the chunk's queries attend causally over the whole scratch with
    ``q_offset=offset`` — rows below ``offset`` hold earlier chunks' K/V
    bitwise, rows at/after ``offset + C`` are causally masked, so chunk
    boundaries never change what any valid query position sees.  Logits are
    not computed: the engine's first sampled token comes from the decode
    step that replays the last prompt token (serve/engine.py admission).

    ``offset`` may be a traced scalar — one compilation per (C, scratch
    seq) shape pair, reused across chunks and requests.
    """
    x = embed_tokens(params, tokens, cfg)
    b, c = x.shape[:2]
    positions = layers.position_ids(b, c, cfg.rope) + offset
    seq = scratch[0]["k"].shape[1]
    # masked row write instead of dynamic_update_slice: the final (short)
    # chunk of a prompt near the scratch end would otherwise be start-index
    # CLAMPED onto earlier rows; here out-of-range rows simply keep the
    # scratch value (and rows past the head zero at the engine's insert)
    src = jnp.arange(seq) - offset                          # (S,)
    take = ((src >= 0) & (src < c))[None, :, None, None]
    src = jnp.clip(src, 0, c - 1)
    new_scratch = []
    for lp, buf in zip(params["layers"], scratch):
        xn = layers.norm(lp["ln1"], x, cfg.norm, cfg.norm_eps)
        q, k, v = layers._qkv(lp["attn"], xn, cfg, positions, qimpl=qimpl)
        sk = jnp.where(take, k.astype(buf["k"].dtype)[:, src], buf["k"])
        sv = jnp.where(take, v.astype(buf["v"].dtype)[:, src], buf["v"])
        new_scratch.append({"k": sk, "v": sv})
        o = layers._direct_attention(q, sk, sv, cfg.n_kv_heads, causal=True,
                                     q_offset=offset)
        o = layers.qdense(lp["attn"]["wo"], o.reshape(b, c, -1), qimpl=qimpl)
        h = x + o
        hn = layers.norm(lp["ln2"], h, cfg.norm, cfg.norm_eps)
        if cfg.family == "moe":
            x = h + moe.moe_mlp(lp["mlp"], hn, cfg, qimpl=qimpl)
        else:
            x = h + layers.mlp(lp["mlp"], hn, cfg.mlp, qimpl=qimpl)
    return new_scratch


def prefill_sp(params, cfg, tokens, *, mesh, qimpl="auto"):
    """Sequence-parallel prefill (EXPERIMENTS.md §Perf cell 2).

    Rationale: 1-D tensor parallelism pays two all-reduces of the full
    (B_loc, S, d) activations per layer — at 32k prefill that term dominates
    the roofline.  Instead: replicate the weights (SigmaQuant-packed weights
    are small enough to afford this — the paper's compression is what buys
    the layout), shard batch over data and *sequence over model*.  Then
    projections and the MLP run with zero collectives, and attention
    all-gathers only the GQA-small K/V per layer.

    Per-device collective bytes/layer: 2·B_loc·S·n_kv·hd (K+V gather)
    vs 2·2·B_loc·S·d (TP all-reduce wire bytes) — a d/(n_kv·hd) ≈ 4-16x
    reduction for GQA archs before even counting the removed MLP collective.
    """
    from jax.sharding import PartitionSpec as P

    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def body(p, toks):
        r = jax.lax.axis_index("model")
        b, s_loc = toks.shape
        x = embed_tokens(p, toks, cfg)
        positions = r * s_loc + layers.position_ids(b, s_loc, cfg.rope)
        caches = []
        for lp in p["layers"]:
            xn = layers.norm(lp["ln1"], x, cfg.norm, cfg.norm_eps)
            q, k, v = layers._qkv(lp["attn"], xn, cfg, positions, qimpl=qimpl)
            caches.append({"k": k, "v": v})  # cache stays sequence-sharded
            kg = jax.lax.all_gather(k, "model", axis=1, tiled=True)
            vg = jax.lax.all_gather(v, "model", axis=1, tiled=True)
            o = layers._flash_attention(q, kg, vg, cfg.n_kv_heads, causal=True,
                                        q_offset=r * s_loc)
            o = layers.qdense(lp["attn"]["wo"], o.reshape(b, s_loc, -1), qimpl=qimpl)
            h = x + o
            hn = layers.norm(lp["ln2"], h, cfg.norm, cfg.norm_eps)
            if cfg.family == "moe":
                x = h + moe.moe_mlp(lp["mlp"], hn, cfg, qimpl=qimpl)
            else:
                x = h + layers.mlp(lp["mlp"], hn, cfg.mlp, qimpl=qimpl)
        hidden = layers.norm(p["final_norm"], x, cfg.norm, cfg.norm_eps)
        logits = logits_fn(p, hidden[:, -1:], cfg, qimpl=qimpl)  # rank-local last
        return logits, caches

    n_layers = len(params["layers"])
    kv_spec = {"k": P(batch_axes, "model", None, None),
               "v": P(batch_axes, "model", None, None)}
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(jax.tree.map(lambda _: P(), params),
                                 P(batch_axes, "model")),
                       out_specs=(P(batch_axes, "model", None),
                                  [kv_spec] * n_layers),
                       check_vma=False)
    logits_all, caches = fn(params, tokens)
    # dim1 stacks each rank's local-last logits; the global last is rank -1
    return logits_all[:, -1:], caches


def decode_verify(params, cfg, caches, tokens, pos, *, qimpl="auto"):
    """Speculative verify: T burst tokens per slot through ONE weight pass.

    ``tokens``: (B, T) — the pending token followed by T-1 draft proposals;
    ``pos``: (B,) — per-slot write position of burst index 0.  Returns
    ``(logits (B, T, V), caches, burst_kv)`` where ``burst_kv`` is the
    per-layer fp K/V of the burst (``[(k, v), ...]``, each (B, T, H, hd))
    the engine's commit pass replays for the accepted prefix (DESIGN.md §13).

    Token-exactness contract: the linear ops (projections, wo, MLP, logits)
    batch all T positions — the speculative win, the weights are read once —
    while the cache append + attend runs SEQUENTIALLY over the burst, so a
    quantized cache sees exactly the non-speculative append/requantize
    sequence (evolving block scales included) and the per-position logits
    are bitwise those of T consecutive ``decode_step`` calls.
    """
    b, t = tokens.shape
    x = embed_tokens(params, tokens, cfg)                     # (B, T, d)
    pos = jnp.asarray(pos, jnp.int32).reshape(-1)
    positions = pos[:, None] + jnp.arange(t, dtype=jnp.int32)  # (B, T)
    new_caches, burst_kv = [], []
    for lp, cache in zip(params["layers"], caches):
        xn = layers.norm(lp["ln1"], x, cfg.norm, cfg.norm_eps)
        q, k_new, v_new = layers._qkv(lp["attn"], xn, cfg, positions, qimpl=qimpl)
        burst_kv.append((k_new, v_new))
        outs = []
        for j in range(t):                                    # static unroll
            att, cache = layers.decode_attend_one(
                cache, q[:, j : j + 1], k_new[:, j : j + 1], v_new[:, j : j + 1],
                pos + j, cfg, qimpl=qimpl)
            outs.append(att.astype(x.dtype))
        o = jnp.concatenate(outs, axis=1)                     # (B, T, hq, hd)
        y = layers.qdense(lp["attn"]["wo"], o.reshape(b, t, -1), qimpl=qimpl)
        new_caches.append(cache)
        h = x + y
        hn = layers.norm(lp["ln2"], h, cfg.norm, cfg.norm_eps)
        if cfg.family == "moe":
            x = h + moe.moe_mlp(lp["mlp"], hn, cfg, qimpl=qimpl)
        else:
            x = h + layers.mlp(lp["mlp"], hn, cfg.mlp, qimpl=qimpl)
    hidden = layers.norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = logits_fn(params, hidden, cfg, qimpl=qimpl)
    return logits, new_caches, burst_kv


def decode_step(params, cfg, caches, token, pos, *, embeds=None, qimpl="auto"):
    """One token through unrolled layers with cache update at ``pos``.

    Each layer's cache is an fp ``{"k","v"}`` dict, a packed
    ``QuantizedKVLayer``, or a block-pool ``PagedKVLayer`` (heterogeneous
    per-layer state bitwidths) — the forms may mix freely within one model.
    """
    from repro.kvcache.cache import QuantizedKVLayer
    from repro.kvcache.paged import PagedKVLayer

    if embeds is None:
        x = embed_tokens(params, token, cfg)  # (B, 1, d)
    else:
        x = embeds.astype(_dtype(cfg))
    new_caches = []
    for lp, cache in zip(params["layers"], caches):
        xn = layers.norm(lp["ln1"], x, cfg.norm, cfg.norm_eps)
        if isinstance(cache, (QuantizedKVLayer, PagedKVLayer)):
            att, ncache = layers.attention_decode_quant(
                lp["attn"], xn, cache, pos, cfg, qimpl=qimpl)
        else:
            att, (ck, cv) = layers.attention_decode(
                lp["attn"], xn, cache["k"], cache["v"], pos, cfg, qimpl=qimpl)
            ncache = {"k": ck, "v": cv}
        new_caches.append(ncache)
        h = x + att
        hn = layers.norm(lp["ln2"], h, cfg.norm, cfg.norm_eps)
        if cfg.family == "moe":
            x = h + moe.moe_mlp(lp["mlp"], hn, cfg, qimpl=qimpl)
        else:
            x = h + layers.mlp(lp["mlp"], hn, cfg.mlp, qimpl=qimpl)
    hidden = layers.norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = logits_fn(params, hidden, cfg, qimpl=qimpl)
    return logits, new_caches
