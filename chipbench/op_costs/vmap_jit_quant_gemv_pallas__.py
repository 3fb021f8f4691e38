"""Operations and bytes of one packed-weight GEMV call over stacked
weights (``qt_matmul`` vmaps the GEMV over an expert stack, and the op is
named after the vmapped wrapper): each stacked matrix's 2-D cost."""
from chipbench.trace_reduce import weight_call_cost as cost  # noqa: F401
