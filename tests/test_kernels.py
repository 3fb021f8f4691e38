"""Pallas kernel validation (interpret mode on CPU) vs pure-jnp oracles.

Per assignment: sweep shapes/dtypes and assert_allclose against ref.py.
"""
import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantizer
from repro.kernels.fake_quant.ops import fake_quant as fq_op
from repro.kernels.fake_quant.ref import fake_quant_ref
from repro.core.packing import LANES
from repro.kernels.quant_matmul.kernel import (VMEM_BUDGET, gemm_blocks,
                                               padded_m, quant_matmul_pallas,
                                               vmem_bytes)
from repro.kernels.quant_matmul.ref import quant_matmul_ref
from repro.quant.tensor import quantize_tensor

BITS = [2, 4, 6, 8]


#: (m, k, n, bits, forced (bm, bn, bk) or None for the chooser's).  Forced
#: blocks give 3 M blocks (one a tail), 3 N blocks and 2 K steps of 1024
#: packed columns; K/lanes = 2944 = 128 x 23 has no legal tile between 128
#: and the whole K, so the chooser's whole-K block runs the sub-slice loop
#: 23 times.
GRID_CASES = (
    [(300, 2048 * LANES[b], 384, b, (128, 128, 1024 * LANES[b])) for b in BITS]
    + [(40, 2944 * LANES[b], 256, b, None) for b in BITS]
)

# Cell call shapes (m, k, n, bits): Phi-3-medium prefill, Yi-6B prefill
# and Yi-6B decode at 32 slots, with Yi's 8-bit LM head.
_PHI3_KN = [(5120, 7680), (5120, 5120), (5120, 35840), (17920, 5120)]
_YI_KN = [(4096, 5120), (4096, 4096), (4096, 22016), (11008, 4096)]
CELL_SHAPES = (
    [(m, k, n, b) for m in (1024, 1536, 2048) for k, n in _PHI3_KN for b in (4, 6, 8)]
    + [(2048, k, n, b) for k, n in _YI_KN for b in (4, 6, 8)]
    + [(32, k, n, b) for k, n in _YI_KN for b in (4, 6, 8)]
    + [(32, 4096, 64000, 8)]
)
#: packed weight bytes a grid step must stream at every cell shape
STEP_BYTES_FLOOR = 512 << 10


def _round_up(v, m):
    return -(-v // m) * m


class TestQuantMatmul:
    # the plain (bits x shape) ref-vs-interpret sweep moved to the unified
    # cross-family harness (tests/test_kernel_parity.py); what stays here
    # are the matmul-specific semantics the sweep does not exercise.

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        key = jax.random.key(7)
        k, n, m = 256, 128, 16
        w = jax.random.normal(jax.random.fold_in(key, 0), (k, n)) * 0.05
        x = (jax.random.normal(jax.random.fold_in(key, 1), (m, k))).astype(dtype)
        qt = quantize_tensor(w, 4)
        ref = quant_matmul_ref(x, qt.packed, qt.scale.reshape(1, -1), 4, k)
        out = quant_matmul_pallas(x, qt.packed, qt.scale.reshape(1, -1),
                                  bits=4, k=k, interpret=True)
        assert out.dtype == dtype
        np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                                   rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("m,k,n,bits,blocks", GRID_CASES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_blocks_match_ref(self, dtype, m, k, n, bits, blocks):
        """Forced and chosen blocks: multi-step grids on every axis, the M
        tail and the in-kernel K sub-slice loop, against the oracle."""
        key = jax.random.key(7)
        w = jax.random.normal(jax.random.fold_in(key, 0), (k, n)) * 0.05
        x = (jax.random.normal(jax.random.fold_in(key, 1), (m, k))).astype(dtype)
        qt = quantize_tensor(w, bits)
        ref = quant_matmul_ref(x, qt.packed, qt.scale.reshape(1, -1), bits, k)
        bm, bn, bk = blocks or (None, None, None)
        out = quant_matmul_pallas(x, qt.packed, qt.scale.reshape(1, -1),
                                  bits=bits, k=k, bm=bm, bn=bn, bk=bk,
                                  interpret=True)
        assert out.dtype == dtype
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("m,k,n,bits", CELL_SHAPES)
    def test_gemm_blocks_at_cell_shapes(self, m, k, n, bits):
        """The chooser's blocks at every cell's call shape: legal tiles of
        the padded dims, no more M padding than one 128-row block, within
        the VMEM budget, and a weight block per step above the floor."""
        bm, bn, bk = gemm_blocks(m, n, k, bits)
        lanes = LANES[bits]
        m_pad, bkp = padded_m(m), bk // lanes
        assert m_pad == _round_up(m, min(128, _round_up(m, 8)))
        assert m_pad % bm == 0 and n % bn == 0 and k % bk == 0 and bk % lanes == 0
        # (8, 128) rule: x (lanes, bm, bkp), w (bn, bkp), out (bm, bn)
        assert bm % 16 == 0 or bm == m_pad
        assert bn % 128 == 0 or bn == n
        assert bkp % 128 == 0 or bkp == k // lanes
        assert bn * bkp >= STEP_BYTES_FLOOR
        assert vmem_bytes(bm, bn, bkp, bits) <= VMEM_BUDGET

    def test_ref_equals_dequant_matmul(self):
        key = jax.random.key(8)
        w = jax.random.normal(jax.random.fold_in(key, 0), (512, 256)) * 0.1
        x = jax.random.normal(jax.random.fold_in(key, 1), (32, 512))
        for bits in BITS:
            qt = quantize_tensor(w, bits)
            ref = quant_matmul_ref(x, qt.packed, qt.scale.reshape(1, -1), bits, 512)
            np.testing.assert_allclose(np.asarray(ref), np.asarray(x @ qt.dequantize()),
                                       rtol=1e-5, atol=1e-5)

    @hypothesis.given(
        bits=st.sampled_from(BITS),
        m=st.integers(1, 40),
        seed=st.integers(0, 1000),
    )
    @hypothesis.settings(max_examples=12, deadline=None)
    def test_property_any_m(self, bits, m, seed):
        """The kernel must mask/pad any M (decode batches are odd-sized)."""
        key = jax.random.key(seed)
        k, n = 256, 128
        w = jax.random.normal(jax.random.fold_in(key, 0), (k, n)) * 0.1
        x = jax.random.normal(jax.random.fold_in(key, 1), (m, k))
        qt = quantize_tensor(w, bits)
        ref = quant_matmul_ref(x, qt.packed, qt.scale.reshape(1, -1), bits, k)
        out = quant_matmul_pallas(x, qt.packed, qt.scale.reshape(1, -1),
                                  bits=bits, k=k, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)

    def test_quantization_error_scales_with_bits(self):
        """End-to-end: W2 matmul error >> W8 error (sanity of the whole path)."""
        key = jax.random.key(9)
        w = jax.random.normal(jax.random.fold_in(key, 0), (512, 256)) * 0.1
        x = jax.random.normal(jax.random.fold_in(key, 1), (16, 512))
        exact = x @ w
        errs = []
        for bits in BITS:
            qt = quantize_tensor(w, bits)
            out = quant_matmul_ref(x, qt.packed, qt.scale.reshape(1, -1), bits, 512)
            errs.append(float(jnp.mean((out - exact) ** 2)))
        assert errs == sorted(errs, reverse=True)
        assert errs[0] > 30 * errs[-1]


class TestFakeQuantKernel:
    @pytest.mark.parametrize("bits", BITS)
    @pytest.mark.parametrize("k,n", [(64, 64), (300, 200), (128, 1024)])
    def test_kernel_matches_ref(self, bits, k, n):
        w = jax.random.normal(jax.random.key(k + n + bits), (k, n)) * 0.2
        scale = quantizer.weight_scale(w, bits, channel_axis=-1)
        ref = fake_quant_ref(w, scale.reshape(1, -1), bits)
        out = fq_op(w, bits, impl="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)

    def test_matches_core_quantizer(self):
        w = jax.random.normal(jax.random.key(3), (100, 50))
        for bits in BITS:
            np.testing.assert_allclose(
                np.asarray(fq_op(w, bits, impl="interpret")),
                np.asarray(quantizer.quantize_dequantize(w, bits)),
                rtol=1e-6, atol=1e-6)

    @hypothesis.given(seed=st.integers(0, 100), bits=st.sampled_from(BITS))
    @hypothesis.settings(max_examples=10, deadline=None)
    def test_idempotent(self, seed, bits):
        """fake_quant(fake_quant(w)) == fake_quant(w) (projection property)."""
        w = jax.random.normal(jax.random.key(seed), (32, 16))
        once = fq_op(w, bits, impl="interpret")
        twice = fq_op(once, bits, impl="interpret")
        np.testing.assert_allclose(np.asarray(twice), np.asarray(once), rtol=1e-5, atol=1e-6)
