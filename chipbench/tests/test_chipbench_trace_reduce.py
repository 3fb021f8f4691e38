"""The trace reduction on a recorded trace: one prefill and three decode
steps of Yi-6B widths (2 layers, 8 slots) on a TPU v5e, cut from a
profiler trace and kept as JSON; and on synthetic traces, with kernel cost
files added in a temporary checkout."""
from pathlib import Path

import pytest

from chipbench import harness, peaks, trace_reduce
from chipbench.trace_reduce import GEMM, GEMV, KV

DATA = Path(__file__).parent / "data" / "recorded_trace.json"
V5E = peaks.peaks_for("TPU v5 lite")
OP_COSTS = harness.op_costs()

#: read from the recorded trace (``turns=(10, 14)``) by the code before
#: kernel costs were found by name in ``op_costs/``
PINNED_KERNELS = {
    ("decode", GEMV): {"time_s": 0.004319831,
                       "roofline_s": 0.0019315940805860806, "calls": 27},
    ("decode", KV): {"time_s": 0.00043767299999999997, "roofline_s": 0.0,
                     "calls": 6},
    ("prefill", GEMM): {"time_s": 0.005103131,
                        "roofline_s": 0.0010083362306598986, "calls": 5}}
PINNED_MODULE_S = {
    "_reduce_max": 9.732000000000001e-06, "_reduce_sum": 1.8849e-05,
    "_squeeze": 4.274e-06, "_where": 1.8049999999999998e-05,
    "abs": 1.4561999999999999e-05, "add": 7.754e-06,
    "bitwise_and": 1.6547999999999998e-05, "broadcast_in_dim": 1.218e-06,
    "clip": 1.7576e-05, "concatenate": 8.082999999999999e-06,
    "convert_element_type": 6.344899999999997e-05,
    "decode": 0.0056208379999999995, "dynamic_slice": 6.854000000000001e-06,
    "exp2": 5.132e-06, "iota": 1.4280000000000001e-06,
    "left_shift": 1.6737999999999998e-05, "less": 9.392e-06,
    "maximum": 3.652e-06, "multiply": 2.367e-06, "negative": 2.373e-06,
    "prefill": 0.005346632, "reshape": 4.808300000000001e-05,
    "round": 1.5266e-05, "scatter": 5.8244e-05,
    "select_n": 1.0568999999999998e-05, "subtract": 1.5070000000000001e-05,
    "swapaxes": 8.64e-06, "true_divide": 1.9389e-05}
PINNED_BUSY_S = 0.011276657
PINNED_BREAKDOWN = {
    "device_ops": [["prefill/quant_matmul_pallas", 0.005103131],
                   ["decode/quant_gemv_pallas", 0.004319831],
                   ["decode/while", 0.000592949],
                   ["decode/quant_kv_decode_step_pallas",
                    0.00043767299999999997],
                   ["decode/copy", 0.00023413199999999966],
                   ["decode/dynamic-update-slice", 0.00020770500000000003],
                   ["decode/broadcast_select_fusion", 0.0001669129999999999],
                   ["decode/and_reduce_fusion", 0.00011224200000000016],
                   ["decode/slice", 7.674899999999982e-05],
                   ["prefill/copy", 7.6695e-05]],
    "idle_gaps": [["host", 0.09581561799999952]]}


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.Trace.from_json(DATA.read_text())


@pytest.fixture(scope="module")
def reduced(trace):
    return trace_reduce.reduce(trace, peaks=V5E, op_costs=OP_COSTS,
                               turns=(10, 14))


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
    r = trace_reduce.reduce(trace, peaks=V5E, op_costs=OP_COSTS, spans=spans,
                            sync_host_s=100.0,
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


def _close(got, want) -> bool:
    """Equal structure, numbers within 1e-12."""
    if isinstance(want, dict):
        return set(got) == set(want) and all(_close(got[k], want[k])
                                             for k in want)
    if isinstance(want, (list, tuple)):
        return len(got) == len(want) and all(map(_close, got, want))
    if isinstance(want, str):
        return got == want
    return abs(got - want) <= 1e-12


@pytest.mark.parametrize("field, pinned", [
    ("kernels", PINNED_KERNELS), ("module_s", PINNED_MODULE_S),
    ("busy_s", PINNED_BUSY_S), ("breakdown", PINNED_BREAKDOWN)])
def test_recorded_trace_reads_as_before(reduced, field, pinned):
    assert _close(getattr(reduced, field), pinned)


#: a 4-bit expert call as the profiler named it on a TPU v5e, verbatim: one
#: jitted ``qt_matmul`` over a (64, 2304, 896) stack, at M = 32 (the GEMM)
#: and M = 8 (the GEMV)
STACKED = {
    "gemm": ('%vmap_jit_quant_matmul_pallas__.2 = '
             'f32[64,32,896]{2,1,0:T(8,128)S(1)} '
             'custom-call(bf16[64,2,32,1152]{3,2,1,0:T(8,128)(2,1)S(1)} '
             '%bitcast.3, s8[64,896,1152]{2,1,0:T(8,128)(4,1)} %qt_packed.1, '
             'f32[64,1,896]{2,1,0:T(1,128)S(1)} %broadcast_in_dim.5), '
             'custom_call_target="tpu_custom_call", '
             'operand_layout_constraints={bf16[64,2,32,1152]{3,2,1,0}, '
             's8[64,896,1152]{2,1,0}, f32[64,1,896]{2,1,0}}, '
             'frontend_attributes={kernel_metadata={}}'),
    "gemv": ('%vmap_jit_quant_gemv_pallas__.2 = '
             'f32[64,8,896]{2,1,0:T(8,128)S(1)} '
             'custom-call(bf16[64,2,8,1152]{3,2,1,0:T(8,128)(2,1)S(1)} '
             '%bitcast.3, s8[64,896,1152]{2,1,0:T(8,128)(4,1)} %qt_packed.1, '
             'f32[64,1,896]{2,1,0:T(1,128)S(1)} %broadcast_in_dim.5), '
             'custom_call_target="tpu_custom_call", '
             'operand_layout_constraints={bf16[64,2,8,1152]{3,2,1,0}, '
             's8[64,896,1152]{2,1,0}, f32[64,1,896]{2,1,0}}, '
             'frontend_attributes={kernel_metadata={}}')}
#: the 2-D call of one stacked matrix, as ``quant_matmul`` makes it
ONE = {"gemm": ("%quant_matmul_pallas.1 = f32[32,896]{1,0} custom-call("
                "bf16[2,32,1152]{2,1,0} %a, s8[896,1152]{1,0} %b, "
                "f32[1,896]{1,0} %c)"),
       "gemv": ("%quant_gemv_pallas.1 = f32[8,896]{1,0} custom-call("
                "bf16[2,8,1152]{2,1,0} %a, s8[896,1152]{1,0} %b, "
                "f32[1,896]{1,0} %c)")}


@pytest.mark.parametrize("kind, m", [("gemm", 32), ("gemv", 8)])
def test_stacked_weight_call_costs_each_matrix(kind, m):
    e, k, n = 64, 2304, 896
    flops, nbytes = trace_reduce.weight_call_cost(STACKED[kind])
    one = trace_reduce.weight_call_cost(ONE[kind])
    assert one == (2.0 * m * k * n,
                   float(n * k // 2 + 4 * n + 2 * m * k + 4 * m * n))
    assert (flops, nbytes) == (e * one[0], e * one[1])
    # found by the op's name: the vmapped family has a cost file
    family = trace_reduce.op_base(STACKED[kind])
    assert OP_COSTS[family](STACKED[kind]) == (flops, nbytes)


@pytest.mark.parametrize("op", [
    # x stacked 64 deep, w 32
    "%vmap_jit_quant_matmul_pallas__.2 = f32[64,32,896]{2,1,0} custom-call("
    "bf16[64,2,32,1152]{3,2,1,0} %a, s8[32,896,1152]{2,1,0} %b, "
    "f32[64,1,896]{2,1,0} %c)",
    # the scale not stacked
    "%vmap_jit_quant_matmul_pallas__.2 = f32[64,32,896]{2,1,0} custom-call("
    "bf16[64,2,32,1152]{3,2,1,0} %a, s8[64,896,1152]{2,1,0} %b, "
    "f32[1,896]{1,0} %c)",
    # x with the stack dim missing
    "%vmap_jit_quant_matmul_pallas__.2 = f32[64,32,896]{2,1,0} custom-call("
    "bf16[2,32,1152]{2,1,0} %a, s8[64,896,1152]{2,1,0} %b, "
    "f32[64,1,896]{2,1,0} %c)"])
def test_stacked_weight_call_with_other_leading_dims_raises(op):
    with pytest.raises(ValueError) as e:
        trace_reduce.weight_call_cost(op)
    assert op in str(e.value)


FOO_COST = '''"""A kernel family counted from its result's shape."""
from chipbench import trace_reduce


def cost(op_name):
    (_, (m, n)), = trace_reduce.shapes(op_name)[:1]
    return 2000.0 * m * n, 4.0 * m * n
'''
#: ops of a synthetic decode program: a family with a cost file of its
#: own, one with none, a non-Pallas family with a cost file, and an op
#: that is no kernel
SYNTHETIC = [
    ("%foo_pallas.1 = f32[64,128]{1,0} custom-call(bf16[64,8]{1,0} %a)",
     1100, 500),
    ("%foo_pallas.2 = f32[32,1024]{1,0} custom-call(bf16[32,8]{1,0} %a)",
     2000, 700),
    ("%bar_pallas = f32[8]{0} custom-call(f32[8]{0} %a)", 3000, 300),
    ("%baz_fusion.4 = f32[16,16]{1,0} fusion(f32[16,16]{1,0} %a)", 3400, 50),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a)", 3500, 100)]


@pytest.fixture
def cost_checkout(tmp_path):
    """A checkout's ``chipbench/`` with the repo's cost files and two added:
    ``foo_pallas.py`` and, the same count, ``baz_fusion.py``."""
    costs = tmp_path / "chipbench" / "op_costs"
    costs.mkdir(parents=True)
    for f in (Path(harness.ROOT) / "op_costs").glob("*.py"):
        (costs / f.name).write_text(f.read_text())
    (costs / "foo_pallas.py").write_text(FOO_COST)
    (costs / "baz_fusion.py").write_text(FOO_COST)
    return tmp_path / "chipbench"


def _synthetic(ops) -> trace_reduce.Trace:
    return trace_reduce.Trace(modules=[["jit_decode(1)", 1000, 9000]],
                              ops=[list(o) for o in ops], marks={})


def test_added_cost_file_counts_its_family(cost_checkout):
    op_costs = harness.op_costs(cost_checkout)
    assert {"foo_pallas", "baz_fusion", GEMM, GEMV} <= set(op_costs)
    r = trace_reduce.reduce(_synthetic(SYNTHETIC), peaks=V5E,
                            op_costs=op_costs)
    foo = r.kernels[("decode", "foo_pallas")]
    by_hand = (max(2000 * 64 * 128 / 197e12, 4 * 64 * 128 / 819e9)
               + max(2000 * 32 * 1024 / 197e12, 4 * 32 * 1024 / 819e9))
    assert foo["calls"] == 2 and foo["time_s"] == pytest.approx(1.2e-6)
    assert foo["roofline_s"] == pytest.approx(by_hand, rel=1e-12)
    baz = r.kernels[("decode", "baz_fusion")]
    assert baz["calls"] == 1 and baz["roofline_s"] == pytest.approx(
        max(2000 * 256 / 197e12, 4 * 256 / 819e9), rel=1e-12)
    # a Pallas family with no cost file: its time and calls, no roofline
    assert r.kernels[("decode", "bar_pallas")] == {
        "time_s": 3e-7, "roofline_s": 0.0, "calls": 1}
    assert ("decode", "fusion") not in r.kernels
    # the cost files are read from that checkout only
    assert "foo_pallas" not in OP_COSTS


@pytest.mark.parametrize("kind", ["gemm", "gemv"])
def test_stacked_call_in_a_trace(kind):
    op = STACKED[kind]
    r = trace_reduce.reduce(_synthetic([(op, 1100, 4000)]), peaks=V5E,
                            op_costs=OP_COSTS)
    k = r.kernels[("decode", trace_reduce.op_base(op))]
    want = 64 * peaks.roofline_s(*trace_reduce.weight_call_cost(ONE[kind]),
                                 V5E)[0]
    assert k["calls"] == 1 and k["roofline_s"] == pytest.approx(want,
                                                                rel=1e-12)


def test_cost_file_that_cannot_read_an_op_fails(cost_checkout):
    op = "%foo_pallas.9 = (f32[4]{0}, f32[4]{0}) custom-call(f32[4]{0} %a)"
    with pytest.raises(ValueError) as e:
        trace_reduce.reduce(_synthetic(SYNTHETIC + [(op, 4000, 10)]),
                            peaks=V5E,
                            op_costs=harness.op_costs(cost_checkout))
    assert "op_costs/foo_pallas.py" in str(e.value) and op in str(e.value)
