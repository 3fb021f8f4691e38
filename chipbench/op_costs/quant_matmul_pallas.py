"""Operations and bytes of one packed-weight GEMM call (the MXU-blocked
kernel, ``kernels/quant_matmul``; M > 8), from its HLO shapes: 2*M*K*N,
and the packed weight, its f32 scales, x and the f32 result."""
from chipbench.trace_reduce import weight_call_cost as cost  # noqa: F401
