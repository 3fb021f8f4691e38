"""The main-path Pallas kernels compile for a TPU v5e at the cells' widths.

No chip is attached: the TPU compiler compiles for a described ``v5e:2x2``
topology, one ``SingleDeviceSharding`` on its first device, from shapes
alone.  This catches what interpret mode cannot: blocks that break the
(8, 128) tiling rule and kernels that overrun VMEM.  Nothing runs, so these
tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and each test worker imports every file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.packing import LANES
from repro.kernels.quant_gemv.kernel import quant_gemv_pallas
from repro.kernels.quant_kv.kernel import (quant_kv_decode_step_paged_pallas,
                                           quant_kv_decode_step_pallas)
from repro.kernels.quant_matmul.kernel import quant_matmul_pallas

# Yi-6B: d_model 4096, d_ff 11008, 32 query / 4 KV heads of 128, vocab 64000
D, FF, NQ, NKV, HD, VOCAB = 4096, 11_008, 32, 4, 128, 64_000
# Phi-3-medium: d_model 5120, d_ff 17920 (gate and up fused: N 35840)
PHI_D, PHI_FF = 5120, 17_920
SLOTS, SEQ, BLOCK = 4, 2048, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _gemv(bits, n, k):
    lanes = LANES[bits]
    return (lambda x, p, s: quant_gemv_pallas(x, p, s, bits=bits, k=k),
            [((SLOTS, k), jnp.bfloat16), ((n, k // lanes), jnp.int8),
             ((1, n), jnp.float32)])


def _gemm(bits, m, n, k):
    lanes = LANES[bits]
    return (lambda x, p, s: quant_matmul_pallas(x, p, s, bits=bits, k=k),
            [((m, k), jnp.bfloat16), ((n, k // lanes), jnp.int8),
             ((1, n), jnp.float32)])


def _step(bits, paged):
    hp, nb = HD // LANES[bits], SEQ // BLOCK
    kw = dict(k_bits=bits, v_bits=bits, hd=HD, block=BLOCK)
    row = ((SLOTS, NKV, HD), jnp.bfloat16)
    q = ((SLOTS, NKV, NQ // NKV, HD), jnp.bfloat16)
    mask = ((SLOTS, SEQ), jnp.float32)
    pos = ((SLOTS,), jnp.int32)
    if paged:
        pool = SLOTS * nb + 1
        cache = [((pool, NKV, BLOCK, hp), jnp.int8),
                 ((pool, NKV, 1, 1), jnp.float32)] * 2
        return (lambda *a: quant_kv_decode_step_paged_pallas(*a, **kw),
                [pos, ((SLOTS, nb), jnp.int32), q, row, row, *cache, mask])
    cache = [((SLOTS, NKV, SEQ, hp), jnp.int8),
             ((SLOTS, NKV, nb, 1), jnp.float32)] * 2
    return (lambda *a: quant_kv_decode_step_pallas(*a, **kw),
            [pos, q, row, row, *cache, mask])


CASES = {
    "gemv_wqkv_4bit": lambda: _gemv(4, (NQ + 2 * NKV) * HD, D),
    "gemv_wdown_2bit": lambda: _gemv(2, D, FF),
    "gemm_wdown_m128_4bit": lambda: _gemm(4, 128, D, FF),
    # the cells' calls whose chosen blocks fill the most VMEM
    "gemm_phi3_gate_up_m2048_4bit": lambda: _gemm(4, 2048, 2 * PHI_FF, PHI_D),
    "gemm_phi3_gate_up_m2048_8bit": lambda: _gemm(8, 2048, 2 * PHI_FF, PHI_D),
    "gemm_phi3_down_m2048_4bit": lambda: _gemm(4, 2048, PHI_D, PHI_FF),
    "gemm_phi3_down_m2048_8bit": lambda: _gemm(8, 2048, PHI_D, PHI_FF),
    "gemm_wdown_m32_4bit": lambda: _gemm(4, 32, D, FF),
    "gemm_lm_head_m32_8bit": lambda: _gemm(8, 32, VOCAB, D),
    "decode_step_dense_4bit": lambda: _step(4, paged=False),
    "decode_step_dense_8bit": lambda: _step(8, paged=False),
    "decode_step_paged_4bit": lambda: _step(4, paged=True),
    "decode_step_paged_8bit": lambda: _step(8, paged=True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
