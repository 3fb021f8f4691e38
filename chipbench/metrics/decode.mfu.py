"""The decode step's share of the chip's peak: for every decode step in
the trace, the least time the chip could take for it (the larger of its
model FLOPs over peak FLOP/s and its required bytes -- every packed
weight once, the 4-bit KV at the live lengths -- over peak HBM
bandwidth), summed and divided by the device time of the decode program,
in %.  The pure FLOP share is printed beside it."""
from chipbench import harness
from chipbench.costs import decode_step_cost
from chipbench.peaks import roofline_s


def read(ctx):
    t = ctx.trace
    if t is None or not t.module_n.get("decode"):
        return None
    steps = harness.traced_steps(ctx)
    if len(steps) != t.module_n["decode"]:
        harness.log(f"decode.mfu: {len(steps)} decode turns recorded, "
                    f"{t.module_n['decode']} decode runs in the trace; "
                    f"not reported")
        return None
    flops = nbytes = bound = 0.0
    for live in steps:
        f, b = decode_step_cost(ctx.dims, live)
        flops, nbytes = flops + f, nbytes + b
        bound += roofline_s(f, b, ctx.peaks)[0]
    dev = t.module_s["decode"]
    harness.log(f"decode.mfu: {len(steps)} steps, {dev:.6f} device s, "
                f"roofline {bound:.6f} s, FLOP share "
                f"{100 * flops / (dev * ctx.peaks['bf16_flops_per_s']):.4f} %, "
                f"HBM share {100 * nbytes / (dev * ctx.peaks['hbm_bytes_per_s']):.4f} %")
    return 100.0 * bound / dev
