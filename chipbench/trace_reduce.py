"""Reduce a JAX profiler trace of the window to what the per-layer metrics
read.

The trace's device plane (``/device:TPU:0``) has a line of program runs
(``XLA Modules``: ``jit_decode(<fingerprint>)``, ``jit_prefill(...)`` and
one ``jit_<op>`` per eagerly dispatched array op) and a line of
operations (``XLA Ops``), each named by its HLO text:
``%quant_matmul_pallas.5 = f32[512,5120]{...} custom-call(bf16[1,512,4096]
..., s8[5120,4096] ..., f32[1,5120] ...)``.  A Pallas kernel's operation
is named after the jitted wrapper that made it, so the kernel families
are ``quant_matmul_pallas``, ``quant_gemv_pallas`` and
``quant_kv_decode_step_pallas`` (a call vmapped over stacked weights, as
an expert GEMM is, is named ``vmap_jit_quant_matmul_pallas__``).  An
operation belongs to the program whose run contains its start.

A family's operations and bytes come from ``op_costs/<family>.py`` in the
checkout's ``chipbench/``: its ``cost(op_name) -> (flops, bytes)`` reads
them from the op's HLO text alone.  The harness hands the reduction those
functions by family (``harness.op_costs``), so a kernel added to the
program is counted by adding a file.

Host spans come from the program's tracer on ``time.perf_counter``; a
``chipbench.sync`` annotation, written to the profiler at a known
``perf_counter`` reading, puts them on the trace's clock, so each idle gap
of the device is named by the engine phase the host was in.

``Trace`` holds only what the reduction needs and round-trips through
JSON, which is how the test keeps a small recorded trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import math
import re
from collections import defaultdict

from .peaks import roofline_s


GEMM, GEMV, KV = "quant_matmul_pallas", "quant_gemv_pallas", \
    "quant_kv_decode_step_pallas"
SYNC = "chipbench.sync"
_BASE = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)? = ")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "s32": 4, "u8": 1}


@dataclasses.dataclass
class Trace:
    modules: list      # [name, start_ns, dur_ns], sorted by start
    ops: list          # [name, start_ns, dur_ns]
    marks: dict        # host annotation name -> start_ns

    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        modules, ops, marks = [], [], {}
        for plane in pd.planes:
            if plane.name == "/device:TPU:0":
                for line in plane.lines:
                    if line.name == "XLA Modules":
                        modules = [[e.name, e.start_ns, e.duration_ns]
                                   for e in line.events]
                    elif line.name == "XLA Ops":
                        ops = [[e.name, e.start_ns, e.duration_ns]
                               for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == SYNC:
                            marks[SYNC] = e.start_ns
        modules.sort(key=lambda m: m[1])
        return cls(modules, ops, marks)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        return cls(**json.loads(text))


def program(module_name: str) -> str:
    """``jit_decode(123)`` -> ``decode``."""
    name = module_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def op_base(op_name: str) -> str:
    m = _BASE.match(op_name)
    return m.group(1) if m else op_name.split(" ")[0].lstrip("%")


def shapes(op_name: str) -> list[tuple[str, tuple[int, ...]]]:
    """(dtype, dims) of the result and then each operand, in order."""
    text = op_name.split(", custom_call_target")[0]
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(text)]


def weight_call_cost(op_name: str) -> tuple[float, float]:
    """(flops, bytes) of one packed-weight GEMM/GEMV call, from its HLO
    shapes: ``out (*B, M, N)``, ``x (*B, lanes, M, K / lanes)``,
    ``w (*B, N, K_packed)``, ``scale (*B, 1, N)``.  Leading dims ``B`` are
    a call over stacked weights (a vmapped expert GEMM): it does the 2-D
    call's work once per stacked matrix."""
    got = shapes(op_name)[:4]
    if len(got) < 4:
        raise ValueError(f"not a weight call (out, x, w, scale): {op_name}")
    (ot, out), (xt, x), (_, w), (_, scale) = got
    lead = out[:-2]
    nb = len(lead)
    if len(out) < 2 or len(x) != nb + 3 or len(w) != nb + 2 \
            or len(scale) != nb + 2 \
            or not lead == x[:nb] == w[:nb] == scale[:nb]:
        raise ValueError(f"weight call shapes out {out}, x {x}, w {w}, "
                         f"scale {scale} share no leading dims: {op_name}")
    (m, n), (lanes, _, kl), kp = out[nb:], x[nb:], w[-1]
    k = lanes * kl
    nbytes = (n * kp + 4 * n + _BYTES[xt] * m * k + _BYTES[ot] * m * n)
    stacked = math.prod(lead)
    return stacked * 2.0 * m * k * n, float(stacked * nbytes)


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    turn_range: tuple     # engine turns [first, stop) inside the trace
    module_s: dict        # program -> device seconds
    module_n: dict        # program -> runs
    #: (program, family) -> {"time_s", "roofline_s", "calls"}; roofline_s
    #: sums the family's cost file over its calls, 0.0 where it has none
    kernels: dict
    phase_s: dict         # engine phase -> host seconds in the window
    breakdown: dict
    notes: list

    @property
    def turns(self) -> int:
        return self.turn_range[1] - self.turn_range[0]

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def reduce(trace: Trace, *, peaks: dict, op_costs: dict, spans=(),
           sync_host_s=None, window=None, turns=(0, 0)) -> Reduced:
    """The trace of a window of host seconds ``window`` = (start, stop),
    covering engine turns ``turns`` = [first, stop).  ``op_costs`` maps an
    op family to its ``cost(op_name) -> (flops, bytes)``."""
    t_lo = trace.marks.get(SYNC)
    if t_lo is None:       # no sync mark: the trace's own extent
        t_lo = min((o[1] for o in trace.ops), default=0)
    window_s = (window[1] - window[0]) if window else (
        max((o[1] + o[2] for o in trace.ops), default=t_lo) - t_lo) / 1e9
    t_hi = t_lo + window_s * 1e9
    starts = [m[1] for m in trace.modules]

    def owner(t: int) -> str | None:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t <= trace.modules[i][1] + trace.modules[i][2]:
            return program(trace.modules[i][0])
        return None

    module_s, module_n = defaultdict(float), defaultdict(int)
    for name, s, d in trace.modules:
        module_s[program(name)] += d / 1e9
        module_n[program(name)] += 1
    kernels: dict = {}
    by_op = defaultdict(float)
    for name, s, d in trace.ops:
        prog = owner(s) or "?"
        base = op_base(name)
        by_op[f"{prog}/{base}"] += d / 1e9
        # a Pallas kernel (named after its jitted wrapper, ``..._pallas``,
        # ``..._pallas__`` under vmap) or a family with a cost file
        if not (base.rstrip("_").endswith("_pallas") or base in op_costs):
            continue
        k = kernels.setdefault((prog, base), {"time_s": 0.0, "roofline_s": 0.0,
                                              "calls": 0})
        k["time_s"] += d / 1e9
        k["calls"] += 1
        if base in op_costs:
            k["roofline_s"] += roofline_s(*_cost(op_costs[base], base, name),
                                          peaks)[0]
    busy = _union((max(s, t_lo), min(s + d, t_hi)) for _, s, d in trace.ops
                  if s + d > t_lo and s < t_hi)
    busy_s = sum(e - s for s, e in busy) / 1e9

    # host phases on the trace's clock
    phase_s = defaultdict(float)
    phase_iv = []
    if spans and sync_host_s is not None and window:
        for ph, name, cat, _track, ts, dur, _args in spans:
            if ph != "X" or cat != "phase" or not window[0] <= ts < window[1]:
                continue
            phase_s[name] += dur
            s = t_lo + (ts - sync_host_s) * 1e9
            phase_iv.append((s, s + dur * 1e9, name))
    phase_iv.sort()
    idle = defaultdict(float)
    edges = [t_lo] + [x for iv in busy for x in iv] + [t_hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            idle[_phase_at(phase_iv, (s + e) / 2)] += (e - s) / 1e9
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                               key=lambda kv: -kv[1])[:10]]
    breakdown = {"device_ops": top(by_op), "idle_gaps": top(idle)}
    notes = [f"trace: {window_s:.3f} s window, device busy {busy_s:.6f} s, "
             f"{sum(module_n.values())} program runs "
             f"({dict(sorted(module_n.items(), key=lambda kv: -kv[1])[:6])})",
             "trace: device idle by host phase (s): " + ", ".join(
                 f"{k} {v:.6f}" for k, v in breakdown["idle_gaps"])]
    return Reduced(window_s=window_s, busy_s=busy_s,
                   turn_range=tuple(turns), module_s=dict(module_s),
                   module_n=dict(module_n), kernels=kernels,
                   phase_s=dict(phase_s), breakdown=breakdown, notes=notes)


def _cost(cost, family: str, op_name: str) -> tuple[float, float]:
    """``cost(op_name)``.  An op it cannot read fails the reduction: left
    out, its calls' time would still count and the share read low unseen."""
    try:
        flops, nbytes = cost(op_name)
    except Exception as e:
        raise ValueError(f"op_costs/{family}.py cannot read the op "
                         f"{op_name!r}: {e!r}") from e
    return flops, nbytes


def _phase_at(phase_iv, t: float) -> str:
    """The innermost engine phase open at trace time ``t``, else ``host``
    (the benchmark's own loop: waiting for arrivals, recording tokens)."""
    i = bisect.bisect_right(phase_iv, (t, float("inf"), "")) - 1
    while i >= 0:
        s, e, name = phase_iv[i]
        if s <= t <= e:
            return name
        if e < t - 1e9:        # phases do not last a second
            break
        i -= 1
    return "host"


def reduce_dir(path: str, **kw) -> Reduced:
    files = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one profiler trace under {path}, "
                           f"found {files}")
    return reduce(Trace.from_xplane(files[0]), **kw)

