"""Decode fast path: GEMV kernel vs oracle, auto-dispatch rule, batched
qt_matmul, and pack-time projection fusion.

Bitwidths sweep the packable set {2, 4, 6, 8} — TPU vector loads are byte
granular, so non-power-of-two lane packings (e.g. 3-bit) are not viable and
3-bit rides in a 4-bit container upstream of this layer (DESIGN.md §2).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.quant_matmul.ops as qops
from repro.core.packing import LANES
from repro.kernels.quant_gemv.kernel import GEMV_MAX_M, k_tile, quant_gemv_pallas
from repro.kernels.quant_gemv.ref import quant_gemv_ref
from repro.kernels.quant_matmul.kernel import quant_matmul_pallas
from repro.kernels.quant_matmul.ops import qt_matmul, quant_matmul, resolve_kernel
from repro.quant import apply as qapply
from repro.quant.tensor import concat_quantized, quantize_tensor

BITS = [2, 4, 6, 8]
MS = [1, 3, 8]


def _case(bits, m, k=512, n=256, dtype=jnp.float32):
    key = jax.random.key(bits * 100 + m)
    w = jax.random.normal(jax.random.fold_in(key, 0), (k, n)) * 0.05
    x = jax.random.normal(jax.random.fold_in(key, 1), (m, k)).astype(dtype)
    return x, quantize_tensor(w, bits)


def _rel(out, ref):
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / (np.max(np.abs(ref)) + 1e-12))


class TestQuantGemvKernel:
    # the plain (bits x M) ref-vs-interpret sweep moved to the unified
    # cross-family harness (tests/test_kernel_parity.py); what stays here
    # are the GEMV-specific semantics the sweep does not exercise.

    @pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("out_dtype", [None, jnp.float32, jnp.bfloat16])
    def test_out_dtype_variants(self, x_dtype, out_dtype):
        x, qt = _case(4, 4, dtype=x_dtype)
        scale = qt.scale.reshape(1, -1)
        out = quant_gemv_pallas(x, qt.packed, scale, bits=4, k=qt.k,
                                interpret=True, out_dtype=out_dtype)
        assert out.dtype == (out_dtype or x_dtype)
        ref = quant_gemv_ref(x, qt.packed, scale, 4, qt.k)
        tol = 2e-2 if jnp.bfloat16 in (x_dtype, out_dtype) else 1e-5
        assert _rel(out, ref) <= tol

    @pytest.mark.parametrize("n", [384, 72])  # not multiples of the 256 block
    def test_odd_n_falls_back_to_divisor_blocks(self, n):
        """Any N the GEMM path accepted must work here too (fused wqkv
        buffers are often not 256-multiples)."""
        x, qt = _case(4, 4, k=256, n=n)
        scale = qt.scale.reshape(1, -1)
        out = quant_gemv_pallas(x, qt.packed, scale, bits=4, k=256, interpret=True)
        ref = quant_gemv_ref(x, qt.packed, scale, 4, 256)
        assert _rel(out, ref) <= 1e-5

    @pytest.mark.parametrize("bits", BITS)
    def test_k_tiles_divide_k(self, bits):
        """K tiles are divisors of K whose packed width is a multiple of 128
        lanes, or all of K: Yi-6B's w_down (K = 11008 = 2^8 * 43) tiles at
        256 wherever 128 lanes fit a divisor, and whole at 2 bits."""
        bk = k_tile(11_008, bits, 1024)
        assert 11_008 % bk == 0
        assert bk == 11_008 if bits == 2 else bk == 256
        assert k_tile(4096, bits, 1024) == 1024

    @pytest.mark.parametrize("kernel", ["gemv", "gemm"])
    @pytest.mark.parametrize("bits", BITS)
    def test_k_not_a_power_of_two(self, kernel, bits):
        """K = 3 * 128 * lanes runs as three K steps (the accumulate-then-
        scale loop over a non-power-of-two K) and matches the reference."""
        lanes = LANES[bits]
        k = 3 * 128 * lanes
        x, qt = _case(bits, 4 if kernel == "gemv" else 24, k=k, n=128)
        scale = qt.scale.reshape(1, -1)
        fn = quant_gemv_pallas if kernel == "gemv" else quant_matmul_pallas
        out = fn(x, qt.packed, scale, bits=bits, k=k, bk=128 * lanes,
                 interpret=True)
        ref = quant_gemv_ref(x, qt.packed, scale, bits, k)
        assert _rel(out, ref) <= 1e-5

    def test_rejects_wide_m(self):
        x, qt = _case(4, 8)
        x = jnp.concatenate([x, x])  # M = 16 > sublane
        with pytest.raises(ValueError, match="GEMV fast path"):
            quant_gemv_pallas(x, qt.packed, qt.scale.reshape(1, -1), bits=4,
                              k=qt.k, interpret=True)


class TestDispatch:
    def test_resolve_rule(self):
        # the acceptance contract: auto on TPU -> GEMV for M <= 8, GEMM above
        assert resolve_kernel("auto", 1, backend="tpu") == "gemv"
        assert resolve_kernel("auto", GEMV_MAX_M, backend="tpu") == "gemv"
        assert resolve_kernel("auto", GEMV_MAX_M + 1, backend="tpu") == "gemm"
        assert resolve_kernel("auto", 1, backend="cpu") == "xla"
        assert resolve_kernel("pallas", 4) == "gemv"
        assert resolve_kernel("interpret", 4) == "gemv"
        assert resolve_kernel("xla", 4) == "xla"

    @pytest.mark.parametrize("m", MS)
    def test_quant_matmul_routes_small_m_through_gemv(self, m, monkeypatch):
        """impl="interpret" (the CPU stand-in for the pallas path) must hit
        the GEMV kernel for small M and still match the oracle <= 1e-5."""
        calls = []
        real = qops.quant_gemv_pallas

        def spy(*args, **kw):
            calls.append(kw.get("interpret"))
            return real(*args, **kw)

        monkeypatch.setattr(qops, "quant_gemv_pallas", spy)
        x, qt = _case(4, m)
        scale = qt.scale.reshape(1, -1)
        out = quant_matmul(x, qt.packed, scale, 4, qt.k, impl="interpret")
        ref = qops.quant_matmul_ref(x, qt.packed, scale, 4, qt.k)
        assert calls == [True]
        assert _rel(out, ref) <= 1e-5

    def test_leading_dims_collapse_into_m(self, monkeypatch):
        """Decode calls arrive as (B, 1, K); B*1 <= 8 must take the GEMV."""
        calls = []
        real = qops.quant_gemv_pallas
        monkeypatch.setattr(qops, "quant_gemv_pallas",
                            lambda *a, **k: calls.append(1) or real(*a, **k))
        x, qt = _case(4, 4)
        out = quant_matmul(x.reshape(4, 1, -1), qt.packed,
                           qt.scale.reshape(1, -1), 4, qt.k, impl="interpret")
        assert out.shape == (4, 1, qt.n) and calls


class TestBatchedQtMatmul:
    def test_vmap_path_matches_per_expert(self):
        e, c, d, f = 4, 16, 64, 96
        key = jax.random.key(5)
        w = jax.random.normal(jax.random.fold_in(key, 0), (e, d, f)) * 0.05
        x = jax.random.normal(jax.random.fold_in(key, 1), (e, c, d))
        qt = quantize_tensor(w, 4)
        out = qt_matmul(x, qt, impl="xla")
        assert out.shape == (e, c, f)
        wd = qt.dequantize(jnp.float32)  # (e, d, f), the einsum-path weights
        for i in range(e):
            ref = x[i] @ wd[i]
            np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref),
                                       rtol=1e-5, atol=1e-6)

    def test_mismatched_leading_dims_raise(self):
        w = jax.random.normal(jax.random.key(0), (4, 64, 96)) * 0.05
        qt = quantize_tensor(w, 4)
        with pytest.raises(ValueError, match="leading dims"):
            qt_matmul(jnp.zeros((3, 16, 64)), qt)


class TestProjectionFusion:
    def test_concat_quantized_exact(self):
        key = jax.random.key(7)
        k = 128
        ws = [jax.random.normal(jax.random.fold_in(key, i), (k, n)) * 0.05
              for i, n in enumerate([96, 32, 32])]
        qts = [quantize_tensor(w, 4) for w in ws]
        fused = concat_quantized(qts)
        assert fused.shape == (k, 160)
        x = jax.random.normal(jax.random.fold_in(key, 9), (2, k))
        out = qt_matmul(x, fused, impl="xla")
        parts = jnp.split(out, [96, 128], axis=-1)
        for part, qt in zip(parts, qts):
            ref = qt_matmul(x, qt, impl="xla")
            np.testing.assert_allclose(np.asarray(part), np.asarray(ref),
                                       rtol=0, atol=0)  # no requantization

    def test_concat_rejects_mixed_bits(self):
        w = jax.random.normal(jax.random.key(0), (64, 32))
        with pytest.raises(ValueError, match="mixed bitwidths"):
            concat_quantized([quantize_tensor(w, 4), quantize_tensor(w, 8)])

    def test_fuse_projections_skips_heterogeneous_groups(self):
        w = jax.random.normal(jax.random.key(1), (64, 32)) * 0.1
        tree = {"attn": {"wq": quantize_tensor(w, 4),
                         "wk": quantize_tensor(w, 8),   # mixed: stays unfused
                         "wv": quantize_tensor(w, 4)},
                "mlp": {"w_gate": quantize_tensor(w, 4),
                        "w_up": quantize_tensor(w, 4),
                        "w_down": quantize_tensor(w, 4)}}
        fused = qapply.fuse_projections(tree)
        assert set(fused["attn"]) == {"wq", "wk", "wv"}
        assert set(fused["mlp"]) == {"w_gu", "w_down"}
        assert fused["mlp"]["w_gu"].shape == (64, 64)

    def test_fuse_projections_leaves_floats_alone(self):
        w = jnp.ones((64, 32))
        tree = {"wq": w, "wk": w, "wv": w}
        assert set(qapply.fuse_projections(tree)) == {"wq", "wk", "wv"}
