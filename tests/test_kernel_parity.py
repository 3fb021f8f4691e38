"""Unified cross-impl kernel parity harness + packing property tests.

ONE parametrized sweep covers every (kernel family, impl, bits) cell:

    family ∈ quant_matmul / quant_gemv / quant_kv_attention / quant_kv_append
             / quant_kv_attention_paged / quant_kv_append_paged
             / quant_kv_decode_step / quant_kv_decode_step_paged
             / quant_kv_decode_step_proj
    impl   ∈ interpret (the Pallas kernel body on CPU) / xla (the fallback)
    bits   ∈ VALID_BITS (2, 4, 6, 8)

Every cell goes through the family's public *ops dispatch* and is checked
against the family's ``ref.py`` oracle — so a new dispatch branch or a new
bitwidth cannot land untested.  This replaces the per-family ad-hoc parity
tests that used to live in test_kernels/test_quant_gemv/test_quant_kv
(whose family-specific semantic tests remain in place).

The second half property-tests ``core/packing`` round-trips across odd row
counts and lane-boundary shapes (the deterministic hypothesis stand-in from
conftest.py supplies the sweep).
"""
import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing
from repro.core.packing import LANES, VALID_BITS
from repro.kernels.quant_gemv.ops import quant_gemv
from repro.kernels.quant_gemv.ref import quant_gemv_ref
from repro.kernels.quant_kv import ops as kv_ops
from repro.kernels.quant_kv.ref import (quant_kv_append_ref,
                                        quant_kv_attention_ref)
from repro.kernels.quant_matmul.ops import qt_matmul, quant_matmul
from repro.kernels.quant_matmul.ref import quant_matmul_ref
from repro.kvcache import paged as pg
from repro.kvcache.cache import init_kv_layer, insert_rows
from repro.quant.tensor import quantize_tensor

IMPLS = ("interpret", "xla")

# -- shared fixtures --------------------------------------------------------

B, S, H, HD, BLOCK = 3, 32, 2, 16, 8
HQ = 4
LENS = (12, 7, 3)


def _rel(out, ref):
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / (np.max(np.abs(ref)) + 1e-12))


def _dense_layer(bits, seed=0):
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(B, max(LENS), H, HD)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, max(LENS), H, HD)), jnp.float32)
    layer = init_kv_layer(B, S, H, HD, k_bits=bits, v_bits=bits, block=BLOCK)
    return insert_rows(layer, jnp.arange(B), k, v, jnp.asarray(LENS))


def _paged_layer(bits, seed=0):
    """Paged cache holding the SAME contents as :func:`_dense_layer`."""
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(B, max(LENS), H, HD)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, max(LENS), H, HD)), jnp.float32)
    layer = pg.init_paged_layer(3 * (S // BLOCK), B, S, H, HD, k_bits=bits,
                                v_bits=bits, block=BLOCK)
    pool = pg.BlockPool(3 * (S // BLOCK))
    npb = -(-max(LENS) // BLOCK)
    table = np.full((B, S // BLOCK), -1, np.int32)
    rows = np.full((B, npb), -1, np.int32)
    for b, length in enumerate(LENS):
        for j in range(-(-(length + 1) // BLOCK)):  # cover the append at pos=len
            table[b, j] = pool.alloc()
            if j < npb:
                rows[b, j] = table[b, j]
    layer = pg.with_table(layer, table)
    return pg.insert_prefill_rows(layer, rows, k, v, jnp.asarray(LENS))


def _query(seed=4):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(B, HQ, HD)), jnp.float32)


def _new_token(seed=1):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(B, 1, H, HD)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, 1, H, HD)), jnp.float32))


KV_VALID = jnp.arange(S)[None, :] < jnp.asarray(LENS)[:, None]


# -- one runner per kernel family ------------------------------------------


def _run_quant_matmul(impl, bits):
    # (48, 256, 128): one k block; (130, 512, 128): an M tail in a padded
    # block; (600, 256, 128): the M tail across two M blocks of 320 rows;
    # K/lanes = 2944 = 128 x 23: a whole-K block walked in 23 sub-slices
    lanes = LANES[bits]
    for m, k, n in ((48, 256, 128), (130, 512, 128), (600, 256, 128),
                    (40, 2944 * lanes, 256)):
        key = jax.random.key(bits * 1000 + m)
        w = jax.random.normal(jax.random.fold_in(key, 0), (k, n)) * 0.05
        x = jax.random.normal(jax.random.fold_in(key, 1), (m, k))
        qt = quantize_tensor(w, bits)
        scale = qt.scale.reshape(1, -1)
        out = quant_matmul(x, qt.packed, scale, bits, qt.k, impl=impl)
        ref = quant_matmul_ref(x, qt.packed, scale, bits, qt.k)
        assert _rel(out, ref) <= 1e-4, (m, k, n)
    # a stacked (expert) weight: the kernel vmapped over the leading dim
    key = jax.random.key(bits * 1000 + 7)
    w = jax.random.normal(jax.random.fold_in(key, 0), (3, 256, 384)) * 0.05
    x = jax.random.normal(jax.random.fold_in(key, 1), (3, 24, 256))
    qt = quantize_tensor(w, bits)
    out = qt_matmul(x, qt, impl=impl)
    for e in range(3):
        ref = quant_matmul_ref(x[e], qt.packed[e], qt.scale[e].reshape(1, -1),
                               bits, qt.k)
        assert _rel(out[e], ref) <= 1e-4, e


def _run_quant_gemv(impl, bits):
    key = jax.random.key(100 + bits)
    w = jax.random.normal(jax.random.fold_in(key, 0), (256, 128)) * 0.05
    x = jax.random.normal(jax.random.fold_in(key, 1), (3, 256))
    qt = quantize_tensor(w, bits)
    scale = qt.scale.reshape(1, -1)
    out = quant_gemv(x, qt.packed, scale, bits, qt.k, impl=impl)
    ref = quant_gemv_ref(x, qt.packed, scale, bits, qt.k)
    assert _rel(out, ref) <= 1e-5


def _run_kv_attention(impl, bits):
    layer = _dense_layer(bits)
    out = kv_ops.quant_kv_attention(_query(), layer, KV_VALID, impl=impl)
    ref = quant_kv_attention_ref(_query(), layer, KV_VALID)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def _run_kv_append(impl, bits):
    layer = _dense_layer(bits)
    kn, vn = _new_token()
    pos = jnp.asarray(LENS, jnp.int32)
    out = kv_ops.quant_kv_append(layer, pos, kn, vn, impl=impl)
    ref = quant_kv_append_ref(layer, pos, kn, vn)
    # levels are bit-exact; scales agree to float rounding
    assert jnp.array_equal(out.k_packed, ref.k_packed)
    assert jnp.array_equal(out.v_packed, ref.v_packed)
    assert jnp.allclose(out.k_scale, ref.k_scale, rtol=1e-6)
    assert jnp.allclose(out.v_scale, ref.v_scale, rtol=1e-6)


def _run_kv_attention_paged(impl, bits):
    """Paged attention on identical contents: BITWISE-equal to the dense
    path at the same impl (the block-table gather must be invisible,
    DESIGN.md §12), and allclose to the dense jnp oracle."""
    dense, paged = _dense_layer(bits), _paged_layer(bits)
    out = kv_ops.quant_kv_attention(_query(), paged, KV_VALID, impl=impl)
    same = kv_ops.quant_kv_attention(_query(), dense, KV_VALID, impl=impl)
    assert jnp.array_equal(out, same), f"paged {impl} attention != dense {impl}"
    ref = quant_kv_attention_ref(_query(), dense, KV_VALID)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def _run_kv_append_paged(impl, bits):
    dense, paged = _dense_layer(bits), _paged_layer(bits)
    kn, vn = _new_token()
    pos = jnp.asarray(LENS, jnp.int32)
    out = kv_ops.quant_kv_append(paged, pos, kn, vn, impl=impl)
    ref = quant_kv_append_ref(dense, pos, kn, vn)
    got = pg.to_dense(out)
    # the mapped region carries bit-identical levels to the dense append;
    # scales agree to float rounding (kernel-vs-jnp requant, same contract
    # as the dense append parity) except at never-written dense pad blocks,
    # which stay masked out of every read
    assert jnp.array_equal(got.k_packed, ref.k_packed)
    assert jnp.array_equal(got.v_packed, ref.v_packed)
    written = np.asarray(ref.k_scale) != 1e-12 / (2 ** (bits - 1) - 1)
    mapped = np.asarray(got.k_scale) != 1e-12
    np.testing.assert_allclose(np.asarray(got.k_scale)[written & mapped],
                               np.asarray(ref.k_scale)[written & mapped],
                               rtol=1e-6)
    valid = jnp.arange(S)[None, :] <= pos[:, None]
    a = kv_ops.quant_kv_attention(_query(), out, valid, impl=impl)
    b = kv_ops.quant_kv_attention(_query(), ref, valid, impl=impl)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def _step_configs(family: str, impl: str):
    """Every tuned layout the autotuner could install for this cell, plus
    None (the dispatcher default) — a config the parity sweep has not pinned
    must never be enumerable."""
    from repro.kernels import autotune

    key = autotune.KernelKey(family=family, k_bits=4, v_bits=4, heads=H,
                             head_dim=HD, block=BLOCK, impl=impl)
    return [None, *autotune.enumerate_candidates(key)]


def _assert_same_cache(got, want, tag):
    """Fused-vs-sequential caches must match BITWISE: packed levels AND
    scales (both paths run the identical requantize float sequence)."""
    for f in ("k_packed", "v_packed", "k_scale", "v_scale"):
        assert jnp.array_equal(getattr(got, f), getattr(want, f)), (tag, f)


def _run_kv_decode_step(impl, bits):
    """Fused append+attend == sequential append -> attend, bitwise, for
    every tuned layout candidate (kernels/autotune) at this impl."""
    layer = _dense_layer(bits)
    kn, vn = _new_token()
    pos = jnp.asarray(LENS, jnp.int32)
    valid = jnp.arange(S)[None, :] <= pos[:, None]
    seq = kv_ops.quant_kv_append(layer, pos, kn, vn, impl=impl)
    o_seq = kv_ops.quant_kv_attention(_query(), seq, valid, impl=impl,
                                      out_dtype=jnp.float32)
    for cfg in _step_configs("decode_step", impl):
        o, new = kv_ops.quant_kv_decode_step(
            _query(), layer, pos, kn, vn, valid, impl=impl,
            out_dtype=jnp.float32, config=cfg)
        assert jnp.array_equal(o, o_seq), cfg
        _assert_same_cache(new, seq, cfg)


def _run_kv_decode_step_paged(impl, bits):
    """Paged fused step vs sequential on the same pool — including an IDLE
    slot (fully unmapped table row): its append lands in the trash block in
    both paths, byte-for-byte (the engine parks free slots this way)."""
    layer = _paged_layer(bits)
    tbl = np.asarray(layer.block_table).copy()
    tbl[1, :] = -1                      # slot 1 idle: every write -> trash
    layer = pg.with_table(layer, jnp.asarray(tbl))
    kn, vn = _new_token()
    pos = jnp.asarray(LENS, jnp.int32)
    valid = jnp.arange(S)[None, :] <= pos[:, None]
    seq = kv_ops.quant_kv_append(layer, pos, kn, vn, impl=impl)
    o_seq = kv_ops.quant_kv_attention(_query(), seq, valid, impl=impl,
                                      out_dtype=jnp.float32)
    for cfg in _step_configs("decode_step_paged", impl):
        o, new = kv_ops.quant_kv_decode_step(
            _query(), layer, pos, kn, vn, valid, impl=impl,
            out_dtype=jnp.float32, config=cfg)
        assert jnp.array_equal(o, o_seq), cfg
        _assert_same_cache(new, seq, cfg)


class _ProjCfg:
    n_heads = HQ
    n_kv_heads = H
    resolved_head_dim = HD
    rope = "default"
    rope_theta = 10_000.0
    qk_norm = False


def _run_kv_decode_step_proj(impl, bits):
    """Proj-fused step (gemv Q/K/V + rope in the same dispatch) against the
    gemv -> rope -> sequential append/attend composition.

    Cache buffers must be BITWISE equal (the K/V written through the fused
    path feed every later step).  The attention output is allclose rather
    than bitwise: the in-kernel projection dots a 1-row M block where
    quant_gemv pads M to 8 rows, which can move the f32 dot by ~1 ulp
    before the (exactly quantized) cache write.  The xla fallback has no
    proj-fused kernel — the cell checks the dispatch gate refuses it.
    """
    from repro.models import layers as L

    d_model = 64
    if impl == "xla":
        lyr = _dense_layer(bits)
        assert not kv_ops.can_fuse_qkv(lyr, d_model, 4, impl)
        return
    key = jax.random.key(17 + bits)
    x = jax.random.normal(jax.random.fold_in(key, 0), (B, d_model), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1),
                          (d_model, (HQ + 2 * H) * HD), jnp.float32) * 0.05
    wqkv = quantize_tensor(w, 4)
    layer = _dense_layer(bits)
    assert kv_ops.can_fuse_qkv(layer, d_model, wqkv.bits, impl)
    pos = jnp.asarray(LENS, jnp.int32)
    valid = jnp.arange(S)[None, :] <= pos[:, None]
    cfg = _ProjCfg()
    q4, kn, vn = L._qkv({"wqkv": wqkv}, x[:, None, :], cfg, pos[:, None],
                        qimpl=impl)
    seq = kv_ops.quant_kv_append(layer, pos, kn, vn, impl=impl)
    o_seq = kv_ops.quant_kv_attention(q4[:, 0], seq, valid, impl=impl,
                                      out_dtype=jnp.float32)
    ang = pos[:, None].astype(jnp.float32) * L.rope_freqs(HD, cfg.rope_theta)
    o, new = kv_ops.quant_kv_decode_step_proj(
        x, wqkv.packed, wqkv.scale, jnp.cos(ang), jnp.sin(ang), layer, pos,
        valid, w_bits=wqkv.bits, n_heads=HQ, impl=impl,
        out_dtype=jnp.float32)
    _assert_same_cache(new, seq, "proj")
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_seq),
                               rtol=1e-6, atol=1e-6)


FAMILIES = {
    "quant_matmul": _run_quant_matmul,
    "quant_gemv": _run_quant_gemv,
    "quant_kv_attention": _run_kv_attention,
    "quant_kv_append": _run_kv_append,
    "quant_kv_attention_paged": _run_kv_attention_paged,
    "quant_kv_append_paged": _run_kv_append_paged,
    "quant_kv_decode_step": _run_kv_decode_step,
    "quant_kv_decode_step_paged": _run_kv_decode_step_paged,
    "quant_kv_decode_step_proj": _run_kv_decode_step_proj,
}


@pytest.mark.parametrize("bits", VALID_BITS)
@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kernel_parity(family, impl, bits):
    """Every (family, impl, bits) cell against the family's ref oracle."""
    FAMILIES[family](impl, bits)


def test_sweep_is_exhaustive():
    """The harness really covers every family the kernels package ships."""
    import repro.kernels.quant_gemv  # noqa: F401
    import repro.kernels.quant_kv  # noqa: F401
    covered = set(FAMILIES)
    assert {"quant_matmul", "quant_gemv", "quant_kv_attention",
            "quant_kv_append", "quant_kv_attention_paged",
            "quant_kv_append_paged", "quant_kv_decode_step",
            "quant_kv_decode_step_paged",
            "quant_kv_decode_step_proj"} == covered


# ---------------------------------------------------------------------------
# core/packing round-trip properties
# ---------------------------------------------------------------------------


class TestPackingRoundTrip:
    @hypothesis.given(
        bits=st.sampled_from(VALID_BITS),
        rows=st.integers(1, 9),
        k=st.integers(1, 33),
        seed=st.integers(0, 10_000),
    )
    @hypothesis.settings(max_examples=40, deadline=None)
    def test_unpack_pack_roundtrip(self, bits, rows, k, seed):
        """unpack(pack(q)) == q for any level grid, odd rows, any K."""
        q = 2 ** (bits - 1) - 1
        rng = np.random.default_rng(seed)
        lev = jnp.asarray(rng.integers(-q, q + 1, (rows, k)), jnp.int32)
        back = packing.unpack(packing.pack(lev, bits), bits, k)
        assert back.shape == lev.shape
        assert jnp.array_equal(back, lev), (bits, rows, k)

    @pytest.mark.parametrize("bits", VALID_BITS)
    def test_lane_boundary_shapes(self, bits):
        """K exactly at / one off a container-byte boundary round-trips."""
        lanes = LANES[bits]
        q = 2 ** (bits - 1) - 1
        for k in {1, lanes, lanes + 1, 2 * lanes - 1, 2 * lanes, 2 * lanes + 1}:
            lev = jnp.asarray(
                np.random.default_rng(k).integers(-q, q + 1, (3, k)), jnp.int32)
            packed = packing.pack(lev, bits)
            assert packed.shape[-1] == -(-k // lanes)  # tight container
            assert jnp.array_equal(packing.unpack(packed, bits, k), lev)

    @hypothesis.given(
        bits=st.sampled_from(VALID_BITS),
        lead=st.tuples(st.integers(1, 3), st.integers(1, 4)),
        k=st.integers(1, 17),
    )
    @hypothesis.settings(max_examples=20, deadline=None)
    def test_nd_leading_dims(self, bits, lead, k):
        """Packing only ever touches the last axis."""
        q = 2 ** (bits - 1) - 1
        rng = np.random.default_rng(k * bits)
        lev = jnp.asarray(rng.integers(-q, q + 1, (*lead, 5, k)), jnp.int32)
        back = packing.unpack(packing.pack(lev, bits), bits, k)
        assert jnp.array_equal(back, lev)

    @hypothesis.given(bits=st.sampled_from(VALID_BITS), k=st.integers(1, 16))
    @hypothesis.settings(max_examples=16, deadline=None)
    def test_extreme_levels_survive(self, bits, k):
        """The signed extremes of the b-bit grid are exactly representable."""
        q = 2 ** (bits - 1) - 1
        lev = jnp.asarray([[-q] * k, [q] * k, [0] * k], jnp.int32)
        assert jnp.array_equal(
            packing.unpack(packing.pack(lev, bits), bits, k), lev)

    def test_container_bytes_consistent_with_pack(self):
        """The analytic container accounting matches the packed buffer."""
        for bits in VALID_BITS:
            for shape in [(4, 7), (2, 3, 16), (1, 1)]:
                lev = jnp.zeros(shape, jnp.int32)
                packed = packing.pack(lev, bits)
                assert packed.size == packing.container_bytes(shape, bits)
