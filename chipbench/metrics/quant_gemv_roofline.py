"""Share of the roofline reached by the decode program's packed-weight GEMV calls (M <= 8): for each call in the trace,
the least time the chip could take (the larger of 2*M*K*N over peak
FLOP/s and the packed weight, its scales, x and the f32 result over peak
HBM bandwidth, all from the call's HLO shapes), summed and divided by the
calls' device time, in %."""

PROGRAM, FAMILY = "decode", "quant_gemv_pallas"


def read(ctx):
    k = None if ctx.trace is None else ctx.trace.kernels.get((PROGRAM, FAMILY))
    if not k or not k["time_s"]:
        return None
    return 100.0 * k["roofline_s"] / k["time_s"]
