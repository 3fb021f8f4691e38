"""The trace reduction on a recorded trace: one prefill and three decode
steps of Yi-6B widths (2 layers, 8 slots) on a TPU v5e, cut from a
profiler trace and kept as JSON."""
from pathlib import Path

import pytest

from chipbench import peaks, trace_reduce
from chipbench.trace_reduce import GEMM, GEMV, KV

DATA = Path(__file__).parent / "data" / "recorded_trace.json"
V5E = peaks.peaks_for("TPU v5 lite")


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.Trace.from_json(DATA.read_text())


@pytest.fixture(scope="module")
def reduced(trace):
    return trace_reduce.reduce(trace, peaks=V5E, turns=(10, 14))


def test_programs_and_runs(reduced):
    assert reduced.module_n["prefill"] == 1
    assert reduced.module_n["decode"] == 3
    # every admitted prompt is inserted into the cache by eager array ops,
    # each its own program run
    assert reduced.module_n["convert_element_type"] > 10
    assert reduced.turns == 4


def test_kernel_families_by_program(reduced):
    k = reduced.kernels
    # prefill: GEMMs over the padded prompt; the engine keeps only the
    # cache, so the last layer's output projection and MLP and the LM head
    # are compiled out.  Decode at 8 slots: GEMV and the fused KV step
    assert k[("prefill", GEMM)]["calls"] == 4 + 1
    assert ("prefill", GEMV) not in k
    assert k[("decode", GEMV)]["calls"] == 3 * (2 * 4 + 1)
    assert k[("decode", KV)]["calls"] == 3 * 2
    assert ("decode", GEMM) not in k


@pytest.mark.parametrize("key", [("prefill", GEMM), ("decode", GEMV)])
def test_roofline_shares_are_shares(reduced, key):
    k = reduced.kernels[key]
    assert 0 < k["roofline_s"] < k["time_s"]


def test_busy_within_window(reduced, trace):
    assert 0 < reduced.busy_s <= reduced.window_s
    assert 0 <= reduced.idle_pct < 100
    # program runs do not overlap: their sum bounds the busy time
    assert reduced.busy_s <= sum(m[2] for m in trace.modules) / 1e9 * 1.001


def test_breakdown_shape(reduced):
    ops, gaps = reduced.breakdown["device_ops"], reduced.breakdown["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert ops[0][0] in ("decode/quant_gemv_pallas",
                         "prefill/quant_matmul_pallas")
    assert all(isinstance(n, str) and s > 0 for n, s in ops + gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    # idle time by what the host was doing adds up to the idle time
    assert sum(s for _, s in gaps) == pytest.approx(
        reduced.window_s - reduced.busy_s)


def test_idle_gaps_named_by_host_phase(trace):
    # a phase span covering the whole trace names every gap after it
    spans = [("X", "admission", "phase", "engine", 100.0, 1.0, {})]
    r = trace_reduce.reduce(trace, peaks=V5E, spans=spans, sync_host_s=100.0,
                            window=(100.0, 100.0 + 0.1071))
    assert {n for n, _ in r.breakdown["idle_gaps"]} == {"admission"}
    assert r.phase_s == {"admission": 1.0}


def test_weight_call_cost_by_hand():
    op = ("%quant_matmul_pallas.5 = f32[512,5120]{1,0:T(8,128)S(1)} "
          "custom-call(bf16[1,512,4096]{2,1,0} %fusion.9, "
          "s8[5120,4096]{1,0} %copy-done.6, f32[1,5120]{1,0} %copy-done.8)")
    flops, nbytes = trace_reduce.weight_call_cost(op)
    assert flops == 2 * 512 * 4096 * 5120
    assert nbytes == 5120 * 4096 + 4 * 5120 + 2 * 512 * 4096 + 4 * 512 * 5120
    # a 4-bit weight: x de-interleaved into two lane planes of K / 2
    op4 = ("%quant_gemv_pallas.2 = f32[8,11008]{1,0} custom-call("
           "bf16[2,8,2048]{2,1,0} %a, s8[11008,2048]{1,0} %b, "
           "f32[1,11008]{1,0} %c)")
    flops, nbytes = trace_reduce.weight_call_cost(op4)
    assert flops == 2 * 8 * 4096 * 11008
    assert nbytes == 11008 * 2048 + 4 * 11008 + 2 * 8 * 4096 + 4 * 8 * 11008


def test_names():
    assert trace_reduce.program("jit_decode(11876931251245265516)") == "decode"
    assert trace_reduce.op_base("%fusion.54 = s32[4]{0} fusion(") == "fusion"
    assert trace_reduce.op_base(
        "%quant_kv_decode_step_pallas.3 = (f32[8]) custom-call(") == KV
    assert trace_reduce.op_base("%copy-start.2 = (s32[1]) copy-start(") \
        == "copy-start"


def test_json_round_trip(trace):
    again = trace_reduce.Trace.from_json(trace.to_json())
    assert again == trace


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
