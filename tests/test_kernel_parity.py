"""Unified cross-impl kernel parity harness + packing property tests.

ONE parametrized sweep covers every (kernel family, impl, bits) cell:

    family ∈ quant_matmul / quant_gemv / quant_kv_attention / quant_kv_append
             / quant_kv_attention_paged / quant_kv_append_paged
             / quant_kv_decode_step / quant_kv_decode_step_paged
    impl   ∈ interpret (the Pallas kernel body on CPU) / xla (the fallback)
    bits   ∈ VALID_BITS (2, 4, 6, 8)

The two fused decode-step families also run at the served head layouts
(``GEOMS``: head_dim 128, block 16, Yi-6B's and Phi-3-medium's KV heads
and query groups), since the small base layout has head_dim 16.

Every cell goes through the family's public *ops dispatch* and is checked
against the family's ``ref.py`` oracle — so a new dispatch branch or a new
bitwidth cannot land untested.  This replaces the per-family ad-hoc parity
tests that used to live in test_kernels/test_quant_gemv/test_quant_kv
(whose family-specific semantic tests remain in place).

The second half property-tests ``core/packing`` round-trips across odd row
counts and lane-boundary shapes (the deterministic hypothesis stand-in from
conftest.py supplies the sweep).
"""
import dataclasses

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


def _rows(g, seed):
    rng = np.random.default_rng(seed)
    shape = (g.b, max(g.lens), g.h, g.hd)
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


def _dense_layer(bits, g=None, seed=0):
    g = g or BASE
    k, v = _rows(g, seed)
    layer = init_kv_layer(g.b, g.s, g.h, g.hd, k_bits=bits, v_bits=bits,
                          block=g.block)
    return insert_rows(layer, jnp.arange(g.b), k, v, jnp.asarray(g.lens))


def _paged_layer(bits, g=None, seed=0):
    """Paged cache holding the SAME contents as :func:`_dense_layer`."""
    g = g or BASE
    k, v = _rows(g, seed)
    nbs = g.s // g.block
    layer = pg.init_paged_layer(g.b * nbs, g.b, g.s, g.h, g.hd, k_bits=bits,
                                v_bits=bits, block=g.block)
    pool = pg.BlockPool(g.b * nbs)
    npb = -(-max(g.lens) // g.block)
    table = np.full((g.b, nbs), -1, np.int32)
    rows = np.full((g.b, npb), -1, np.int32)
    for b, length in enumerate(g.lens):
        for j in range(-(-(length + 1) // g.block)):  # cover the append at pos=len
            table[b, j] = pool.alloc()
            if j < npb:
                rows[b, j] = table[b, j]
    layer = pg.with_table(layer, table)
    return pg.insert_prefill_rows(layer, rows, k, v, jnp.asarray(g.lens))


def _query(g=None, seed=4):
    g = g or BASE
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(g.b, g.hq, g.hd)), jnp.float32)


def _new_token(g=None, seed=1):
    g = g or BASE
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(g.b, 1, g.h, g.hd)), jnp.float32),
            jnp.asarray(rng.normal(size=(g.b, 1, g.h, g.hd)), jnp.float32))


KV_VALID = jnp.arange(S)[None, :] < jnp.asarray(LENS)[:, None]


@dataclasses.dataclass(frozen=True)
class Geom:
    """A cache layout: slots, positions, KV heads, query heads, head_dim,
    block, and each slot's filled length (where its append lands)."""
    b: int
    s: int
    h: int
    hq: int
    hd: int
    block: int
    lens: tuple


BASE = Geom(B, S, H, HQ, HD, BLOCK, LENS)
#: the served layouts at a small slot count and length: Yi-6B (4 KV heads,
#: groups of 8) and Phi-3-medium (10 KV heads, groups of 4); the appends
#: land mid-block and on a block's last row
GEOMS = {"yi6b": Geom(2, 64, 4, 32, 128, 16, (37, 15)),
         "phi3m": Geom(2, 64, 10, 40, 128, 16, (50, 31))}


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


def _assert_same_cache(got, want, tag):
    """Fused-vs-sequential caches must match BITWISE: packed levels AND
    scales (both paths run the identical requantize float sequence)."""
    for f in ("k_packed", "v_packed", "k_scale", "v_scale"):
        assert jnp.array_equal(getattr(got, f), getattr(want, f)), (tag, f)


def _run_step_vs_sequential(layer, g, impl):
    """The fused decode step against append -> attend at the same impl."""
    kn, vn = _new_token(g)
    pos = jnp.asarray(g.lens, jnp.int32)
    valid = jnp.arange(g.s)[None, :] <= pos[:, None]
    seq = kv_ops.quant_kv_append(layer, pos, kn, vn, impl=impl)
    o_seq = kv_ops.quant_kv_attention(_query(g), seq, valid, impl=impl,
                                      out_dtype=jnp.float32)
    o, new = kv_ops.quant_kv_decode_step(_query(g), layer, pos, kn, vn, valid,
                                         impl=impl, out_dtype=jnp.float32)
    assert jnp.array_equal(o, o_seq), impl
    _assert_same_cache(new, seq, impl)


def _run_kv_decode_step(impl, bits, g=None):
    """Fused append+attend == sequential append -> attend, bitwise."""
    g = g or BASE
    _run_step_vs_sequential(_dense_layer(bits, g), g, impl)


def _run_kv_decode_step_paged(impl, bits, g=None):
    """Paged fused step vs sequential on the same pool — including an IDLE
    slot (fully unmapped table row): its append lands in the trash block in
    both paths, byte-for-byte (the engine parks free slots this way)."""
    g = g or BASE
    layer = _paged_layer(bits, g)
    tbl = np.asarray(layer.block_table).copy()
    tbl[1, :] = -1                      # slot 1 idle: every write -> trash
    _run_step_vs_sequential(pg.with_table(layer, jnp.asarray(tbl)), g, impl)


FAMILIES = {
    "quant_matmul": _run_quant_matmul,
    "quant_gemv": _run_quant_gemv,
    "quant_kv_attention": _run_kv_attention,
    "quant_kv_append": _run_kv_append,
    "quant_kv_attention_paged": _run_kv_attention_paged,
    "quant_kv_append_paged": _run_kv_append_paged,
    "quant_kv_decode_step": _run_kv_decode_step,
    "quant_kv_decode_step_paged": _run_kv_decode_step_paged,
}

#: (family, geometry) cases: every family at the base layout, and the fused
#: decode steps again at each served layout
CASES = ([pytest.param(f, None, id=f) for f in sorted(FAMILIES)]
         + [pytest.param(f, geom, id=f"{f}-{geom}")
            for f in ("quant_kv_decode_step", "quant_kv_decode_step_paged")
            for geom in GEOMS])


@pytest.mark.parametrize("bits", VALID_BITS)
@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("family,geom", CASES)
def test_kernel_parity(family, geom, impl, bits):
    """Every (family, impl, bits) cell against the family's ref oracle."""
    if geom is None:
        FAMILIES[family](impl, bits)
    else:
        FAMILIES[family](impl, bits, GEOMS[geom])


def test_sweep_is_exhaustive():
    """The harness really covers every family the kernels package ships."""
    import repro.kernels.quant_gemv  # noqa: F401
    import repro.kernels.quant_kv  # noqa: F401
    covered = set(FAMILIES)
    assert {"quant_matmul", "quant_gemv", "quant_kv_attention",
            "quant_kv_append", "quant_kv_attention_paged",
            "quant_kv_append_paged", "quant_kv_decode_step",
            "quant_kv_decode_step_paged"} == covered


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
