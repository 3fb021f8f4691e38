"""Share of the roofline reached by the fused quantized-KV decode step
(``quant_kv_decode_step_pallas``, one call per layer per decode step):
for each traced step and layer, the least time the chip could take to
append one 4-bit row per active request and attend over its live length
(packed K and V and their block scales at the live lengths, one block
written back per side, query in and output out), over the kernel's device
time, in %."""
from chipbench import harness
from chipbench.costs import kv_attend_cost
from chipbench.peaks import roofline_s
from chipbench.trace_reduce import KV


def read(ctx):
    t = ctx.trace
    k = None if t is None else t.kernels.get(("decode", KV))
    if not k or not k["time_s"]:
        return None
    steps = harness.traced_steps(ctx)
    if k["calls"] != len(steps) * ctx.dims.n_layers:
        harness.log(f"quant_kv_decode_roofline: {k['calls']} kernel calls "
                    f"for {len(steps)} recorded steps of "
                    f"{ctx.dims.n_layers} layers; not reported")
        return None
    bound = sum(roofline_s(*kv_attend_cost(ctx.dims, live), ctx.peaks)[0]
                for live in steps) * ctx.dims.n_layers
    return 100.0 * bound / k["time_s"]
