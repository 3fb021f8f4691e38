"""Prefill's share of the chip's peak FLOP/s: model FLOPs of every prompt
prefilled in the trace (each layer's weights for each real token, causal
attention, the LM head for the last position) over the device time of the
prefill programs times the peak, in %.  Padding to ``prefill_pad`` is work
the program chose, not model FLOPs."""
from chipbench import harness
from chipbench.costs import prefill_flops


def read(ctx):
    t = ctx.trace
    if t is None or not t.module_n.get("prefill"):
        return None
    heads = harness.traced_prefills(ctx)
    if len(heads) != t.module_n["prefill"]:
        harness.log(f"prefill.mfu: {len(heads)} admissions recorded, "
                    f"{t.module_n['prefill']} prefill runs in the trace; "
                    f"not reported")
        return None
    flops = sum(prefill_flops(ctx.dims, w) for w in heads)
    return 100.0 * flops / (t.module_s["prefill"]
                            * ctx.peaks["bf16_flops_per_s"])
