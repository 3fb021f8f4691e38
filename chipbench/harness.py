"""One run of one cell: set up, drive the traffic for the window, check the
served tokens against the reference, and print the result line.

Everything that belongs to a cell is found by name: the cell's entry in
``BENCHMARK.json`` names its configuration (``configs/<name>.json``) and
traffic mix (``traffic/<name>.json``); ``workloads/<cell>.json`` holds the
engine settings and the check's limit; every metric is read by
``metrics/<metric>.py``.  The configuration names its architecture
(``architectures/<architecture>.py``, reached through ``system``) and its
plain reference (``<reference>.py``).  A traced run counts each device-op
family's operations and bytes with ``op_costs/<family>.py``.  Adding a
cell, configuration, architecture, reference, mix, metric or kernel cost
is adding files and entries.

A reference module exports ``Reference(conf, seed, *, max_seq,
max_out)`` whose ``gaps(requests, control=False)`` gives, for each
``(prompt, served)``, the gap of every served token below the
reference's best logit and, with ``control``, the gap of the token the
control puts first.  It imports nothing of the program (no ``repro``, no
``system``) and takes nothing the program made: it draws its weights
from the seed itself.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import peaks, system, traffic

ROOT = Path(__file__).resolve().parent
CHECKOUT = ROOT.parent
#: seconds after the window closes by which every request due in it must
#: have finished; one that has not is cancelled and counts as failed
DRAIN_LIMIT_S = 120.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- finding things by name -----------------------------------------------

def load(kind: str, name: str, root: Path = ROOT) -> dict:
    with open(root / kind / f"{name}.json") as f:
        return json.load(f)


def benchmark(checkout: Path = CHECKOUT) -> dict:
    with open(checkout / "BENCHMARK.json") as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict          # the workload's entry in BENCHMARK.json
    cell: dict           # workloads/<name>.json
    conf: dict           # configs/<config>.json
    mix: dict            # traffic/<traffic>.json
    end_to_end: list     # BENCHMARK.json metrics this cell reports
    per_layer: list


def find_cell(name: str, bench: dict, root: Path = ROOT) -> Cell:
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = load("workloads", name, root)
    for key in ("config", "traffic"):
        if cell[key] != entry[key]:
            raise ValueError(f"{name}: workloads/{name}.json says {key} "
                             f"{cell[key]!r}, BENCHMARK.json {entry[key]!r}")
    mine = lambda m: name in m.get("workloads", [name])
    return Cell(name, entry, cell, load("configs", entry["config"], root),
                load("traffic", entry["traffic"], root),
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])


def reader(name: str, root: Path = ROOT):
    """``metrics/<name>.py``'s ``read(ctx)``."""
    return system.module(
        root / "metrics" / f"{name}.py",
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_")).read


def op_costs(root: Path = ROOT) -> dict:
    """Device-op family -> ``cost(op_name) -> (flops, bytes)``, from every
    ``op_costs/<family>.py`` under ``root``."""
    return {p.stem: system.module(p, f"chipbench.op_costs.{p.stem}").cost
            for p in sorted((root / "op_costs").glob("*.py"))}


def reference(conf: dict, root: Path = ROOT):
    """The reference module the configuration names: ``<reference>.py``
    under ``root``, whose relative imports resolve in ``chipbench``."""
    name = conf["reference"]
    return system.module(root / f"{name}.py", f"chipbench.{name}")


# -- the chip ------------------------------------------------------------------

def chips(n: int) -> list:
    """The accelerators, or NoChip: there is no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"needs {n} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s) ({devs[0].device_kind})")
    return devs


def enable_cache(checkout: Path = CHECKOUT) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever the environment says, so that a later run finds it."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(checkout / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts lowerings and backend compiles while ``on``."""

    def __init__(self):
        import jax
        self.on = False
        self.lowerings = self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, _secs: float, **_kw) -> None:
        if not self.on:
            return
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowerings += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


# -- driving the engine -----------------------------------------------------------

@dataclasses.dataclass
class Rec:
    """One request as the client saw it (host ``perf_counter`` seconds)."""

    req: traffic.Req
    due: float
    submit: float | None = None
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    turns: list = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.req.max_new


class LoadGen:
    """Offers a cell's traffic to the engine for ``seconds`` and records
    what every request saw.  The engine is driven through its public
    entry: ``submit(req, on_token=)`` and ``run(step_hook=)``; the hook
    fires once per engine turn, before admission, and is where arrivals
    that have come due are handed over."""

    def __init__(self, eng, cell: Cell, seed: int, seconds: float,
                 *, rate: float | None = None, trace=None):
        self.eng, self.cell = eng, cell
        self.seed, self.seconds, self.rate = seed, seconds, rate
        self.trace = trace
        self.recs: dict[int, Rec] = {}
        self.turn = 0
        self.t0 = self.t_end = 0.0
        self._fresh = False
        self._drained = False

    # engine callbacks
    def _on_token(self, uid: int, tok: int) -> None:
        rec = self.recs[uid]
        now = time.perf_counter()
        rec.times.append(now)
        rec.tokens.append(int(tok))
        rec.turns.append(self.turn)
        if rec.done and rec.req.client is not None:
            self._ready.append((rec.req.client, now))

    def _hook(self, eng, _step) -> None:
        now = time.perf_counter()
        self.turn += 1
        if self.trace is not None:
            self.trace.at_turn(self, now)
        self._arrivals(now)
        # a request is waited for past the close as long again as the
        # profiler held the engine loop: late, not lost
        held = self.trace.held_s if self.trace is not None else 0.0
        if not self._drained and now > self.t_end + DRAIN_LIMIT_S + held:
            self._drained = True
            for rec in self.recs.values():
                if not rec.done:
                    eng.cancel(rec.req.uid)

    def _submit(self, req: traffic.Req, due: float, now: float) -> None:
        rec = Rec(req=req, due=due, submit=now)
        self.recs[req.uid] = rec
        self._fresh = True
        self.eng.submit(system.request(req.uid, req.prompt, req.max_new),
                        on_token=self._on_token)

    def _arrivals(self, now: float) -> None:
        if self.closed:
            while self._ready:
                client, sent = self._ready.pop(0)
                if sent < self.t_end:
                    self._submit(self.loop.next(client), sent, now)
        else:
            while self._next < len(self._sched) and \
                    self.t0 + self._sched[self._next].due_s <= now:
                req = self._sched[self._next]
                self._submit(req, self.t0 + req.due_s, now)
                self._next += 1

    def run(self) -> None:
        mix, vocab = self.cell.mix, self.cell.conf["vocab_size"]
        self.closed = mix["loop"] == "closed"
        self._ready: list = []
        if self.closed:
            self.loop = traffic.ClosedLoop(mix, self.seed, vocab)
        else:
            self._sched = traffic.open_loop(mix, self.seconds, self.seed,
                                            vocab, rate=self.rate)
            self._next = 0
        self.t0 = time.perf_counter()
        self.t_end = self.t0 + self.seconds
        if self.closed:
            self._ready = [(c, self.t0) for c in range(self.loop.clients)]
        while True:
            self._arrivals(time.perf_counter())
            if self._fresh:
                # returns once every request handed over so far has ended
                self._fresh = False
                self.eng.run(step_hook=self._hook)
                continue
            if self.closed or self._next >= len(self._sched):
                break
            time.sleep(max(0.0, self.t0 + self._sched[self._next].due_s
                           - time.perf_counter()))

    # what the window saw
    def in_window(self) -> list[Rec]:
        """Requests due (open loop) or sent (closed loop) in the window."""
        return [r for r in self.recs.values() if r.due < self.t_end]


def warm_requests(cell: Cell) -> list[traffic.Req]:
    """One short request per prefill shape the mix can produce: a prompt
    head at each multiple of ``prefill_pad`` its lengths round up to."""
    pad = cell.cell["engine"]["prefill_pad"]
    lo, hi = traffic.prefill_heads(cell.mix)
    heads = sorted({min(max(lo, p), hi)
                    for p in range(-(-lo // pad) * pad, -(-hi // pad) * pad + 1,
                                   pad)})
    rng = np.random.default_rng(0)
    vocab = cell.conf["vocab_size"]
    return [traffic.Req(uid=-1 - i, prompt=rng.integers(1, vocab, h + 1).tolist(),
                        max_new=2) for i, h in enumerate(heads)]


def pctl(values, q: float) -> float | None:
    """The ``q``-th percentile (linear between order statistics)."""
    return float(np.percentile(values, q)) if len(values) else None


def log_latency(gen: LoadGen, recs: list[Rec]) -> None:
    """The spread of first-token times and of the gaps between tokens,
    beside the 95th percentiles the result reports."""
    ttft = [r.times[0] - r.due for r in recs if r.times]
    gaps = [b - a for r in gen.recs.values()
            for a, b in zip(r.times, r.times[1:]) if gen.t0 <= b < gen.t_end]
    qs = (50, 90, 95, 99)
    for name, vals, unit in (("ttft", ttft, 1.0), ("itl", gaps, 1e3)):
        got = {f"p{q}": round(pctl(vals, q) * unit, 6) for q in qs} \
            if vals else {}
        if vals:     # the share more than twice the median: stalls
            got["over_2x_p50"] = round(
                float(np.mean(np.asarray(vals) > 2 * pctl(vals, 50))), 6)
        log(f"latency {name} ({'s' if unit == 1.0 else 'ms'}): "
            f"{len(vals)} samples {got}")


# -- one run ------------------------------------------------------------------

def run(name: str, seed: int, seconds: float, trace: bool, *,
        checkout: Path = CHECKOUT, need_chip: bool = True,
        control: bool = False) -> dict:
    """One run of cell ``name``; returns the result line (a dict).
    ``control`` also reads the control's gap on the same sample (the
    calibration of the limit, ``calibrate.py``)."""
    t_start = time.perf_counter()
    bench = benchmark(checkout)
    root = checkout / "chipbench"
    cell = find_cell(name, bench, root)
    # a configuration whose architecture or reference has no file fails
    # here, before anything is set up
    system.architecture(cell.conf["architecture"], root)
    ref = reference(cell.conf, root)
    costs = op_costs(root) if trace else None
    import jax
    devs = chips(cell.entry["chips"]) if need_chip else jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")
    pk = peaks.peaks_for(dev.device_kind) if need_chip else None
    enable_cache(checkout)
    counter = CompileCounter()

    # -- set-up: weights, packing, engine, every shape the traffic uses
    conf = cell.conf
    params = system.pack(conf, seed, root)
    eng = system.engine(conf, params, cell.cell["engine"], seed, root)
    del params
    warm = warm_requests(cell)
    for req in warm:
        eng.submit(system.request(req.uid, req.prompt, req.max_new))
    eng.run()
    jax.block_until_ready(eng.state)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s (weights, packing, engine, "
        f"{len(warm)} prefill shapes + decode warmed)")

    # -- the window
    tracer = system.tracer()
    tr = _TracePlan(cell, seconds, tracer, pk, costs) if trace else None
    gen = LoadGen(eng, cell, seed, seconds, trace=tr)
    before = system.counters(eng)
    gc.collect()
    gc.freeze()
    counter.on = True
    gen.run()
    jax.block_until_ready(eng.state)
    counter.on = False
    gc.unfreeze()
    counters = {name: value - before.get(name, 0.0)
                for name, value in system.counters(eng).items()}
    log(f"window {seconds} s: {len(gen.in_window())} requests, "
        f"compilations inside the window: {counter.compiles} "
        f"(lowerings {counter.lowerings})")
    device["memory_peak_bytes"] = max(
        d.memory_stats()["peak_bytes_in_use"] for d in devs) if need_chip \
        else 0
    if need_chip:
        log(f"memory: peak {device['memory_peak_bytes']} bytes, in use after "
            f"the window {max(d.memory_stats()['bytes_in_use'] for d in devs)}")
    del eng, gen.eng
    gc.collect()

    # -- what the window produced, against the reference
    recs = gen.in_window()
    failed = [r for r in recs if not r.done]
    log_latency(gen, recs)
    stats, control_stats = check(cell, seed, recs, ref, control=control)
    checks, correct = verdict(cell, stats, len(failed))

    ctx = Context(cell=cell, gen=gen, recs=recs, seconds=seconds,
                  setup_s=setup_s, counters=counters, peaks=pk,
                  dims=system.dims(conf, root), trace=None)
    result = {"correct": bool(correct), "attempted": len(recs),
              "failed": len(failed)}
    if trace:
        ctx.trace = tr.reduce(gen)
        ctx.traced = tuple(tr.host)
        wanted = cell.per_layer
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
    else:
        wanted = cell.end_to_end
    metrics = {}
    for m in wanted:
        value = reader(m["name"], root)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = ctx.trace.breakdown
        for line in ctx.trace.notes:
            log(line)
    if control:
        # the control in the program's place, judged by the same limits
        result["gaps"] = stats
        result["control_gaps"] = control_stats
        result["control_correct"] = verdict(cell, control_stats, 0)[1]
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    return result


def verdict(cell: Cell, stats: dict, unfinished: int) -> tuple[dict, bool]:
    """Each number compared beside its limit, and whether all hold: no
    request due in the window left unfinished, and the served-token gaps
    (widest, mean) within the cell's limits."""
    checks = {"unfinished_requests": {"value": unfinished, "limit": 0}}
    for key, stat in (("served_logit_gap", "max"),
                      ("served_logit_gap_mean", "mean")):
        limit = cell.cell["check"].get(f"{stat}_logit_gap")
        if limit is not None:
            value = stats[stat]       # none to compare, or an id off the vocab
            ok = value is not None and math.isfinite(value)
            checks[key] = {"value": value if ok else None, "limit": limit}
    return checks, all(c["value"] is not None and c["value"] <= c["limit"]
                       for c in checks.values())


@dataclasses.dataclass
class Context:
    """What a metric reader reads."""

    cell: Cell
    gen: LoadGen
    recs: list
    seconds: float
    setup_s: float
    #: every engine counter's growth over the window and its drain
    counters: dict
    peaks: dict | None
    dims: object         # the architecture module's ``dims(conf)``
    trace: object
    #: host seconds (start, stop) of the profiler's span in a traced run
    traced: tuple | None = None


def traced_steps(ctx: Context) -> list[list[int]]:
    """Per decode step inside the traced turns, each active request's
    length after the step (prompt plus tokens so far): token ``k`` of a
    request came from the step that wrote position ``len(prompt) - 1 + k``
    and attended over every position up to it."""
    lo, hi = ctx.trace.turn_range
    steps: dict[int, list[int]] = {}
    for r in ctx.gen.recs.values():
        for k, t in enumerate(r.turns):
            if lo <= t < hi:
                steps.setdefault(t, []).append(len(r.req.prompt) + k)
    return [steps[t] for t in sorted(steps)]


def traced_prefills(ctx: Context) -> list[int]:
    """Prompt heads prefilled inside the traced turns: a request is
    admitted, prefilled and given its first token in one engine turn."""
    lo, hi = ctx.trace.turn_range
    return [len(r.req.prompt) - 1 for r in ctx.gen.recs.values()
            if r.turns and lo <= r.turns[0] < hi]


def check(cell: Cell, seed: int, recs: list[Rec], reference, *,
          control: bool = False):
    """Gaps of the served tokens against the configuration's ``reference``
    module, over a seeded sample of finished requests that holds the one
    with the most served tokens: the widest (``max``) and the mean over
    every sampled token (None where nothing finished).  With ``control``,
    the same of the tokens the control (the reference with float8 matmul
    inputs) puts first at the same positions.  Returns ``(stats, control
    stats or None)``."""
    t0 = time.perf_counter()
    done = [r for r in recs if r.done]
    if not done:
        return {"max": None, "mean": None}, None
    n = cell.cell["check"]["sample"]
    longest = max(done, key=lambda r: (len(r.tokens), r.req.uid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    pick = [longest] + [rest[i] for i in sorted(
        rng.choice(len(rest), min(n - 1, len(rest)), replace=False))]
    ref = reference.Reference(cell.conf, seed,
                              max_seq=cell.cell["engine"]["max_seq"],
                              max_out=cell.mix["output_tokens"]["max"])
    out = ref.gaps([(r.req.prompt, r.tokens) for r in pick], control=control)

    def stats(arrays):
        g = np.concatenate(arrays)
        return {"max": float(g.max()), "mean": float(g.mean())}

    mine = stats([g for g, _ in out])
    low = stats([c for _, c in out]) if control else None
    log(f"reference: {len(pick)} requests, {sum(len(r.tokens) for r in pick)} "
        f"served tokens, widest gap per request "
        f"{[round(float(g.max()), 6) for g, _ in out]}, gaps {mine}, "
        f"{time.perf_counter() - t0:.1f} s"
        + (f", control gaps {low}" if control else ""))
    return mine, low


class _TracePlan:
    """The traced run: the program's span tracer over the whole window,
    the JAX profiler from the first engine turn after the window opens to
    the first turn ``trace_seconds`` later.  Starting and stopping at a
    turn boundary means every program dispatched in between ran inside the
    trace: the engine waits for its decode step at the end of each turn."""

    def __init__(self, cell: Cell, seconds: float, tracer, peaks: dict,
                 op_costs: dict):
        self.cell, self.peaks, self.op_costs = cell, peaks, op_costs
        self.span = min(cell.cell["trace_seconds"], seconds)
        # the middle of the window, past the ramp from an empty engine
        self.begin = (seconds - self.span) / 2
        self.tracer = tracer
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.turns = [None, None]
        self.host = [0.0, 0.0]
        self.sync = None
        self.stalls = {}  # seconds the engine loop stood still for each
        tracer.enable()

    @property
    def held_s(self) -> float:
        """Seconds the profiler held the engine loop inside the window."""
        return self.stalls.get("start", 0.0) + self.stalls.get("stop", 0.0)

    @staticmethod
    def _start(path: str) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(path, profiler_options=opts)

    @staticmethod
    def _stop() -> None:
        import jax
        jax.profiler.stop_trace()

    def at_turn(self, gen: LoadGen, now: float) -> None:
        import jax
        if self.turns[0] is None:
            if now < gen.t0 + self.begin:
                return
            self._start(self.dir)
            with jax.profiler.TraceAnnotation("chipbench.sync"):
                self.sync = time.perf_counter()
            self.stalls["start"] = self.sync - now
            self.turns[0] = gen.turn
            self.host[0] = self.sync
        elif self.turns[1] is None and now - self.host[0] >= self.span:
            self.host[1] = time.perf_counter()
            self.turns[1] = gen.turn
            self._stop()
            self.stalls["stop"] = time.perf_counter() - self.host[1]

    def reduce(self, gen: LoadGen):
        from . import trace_reduce
        if self.turns[0] is None:
            raise RuntimeError("the engine ran no turn in the traced part "
                               "of the window")
        if self.turns[1] is None:      # the window ended first
            self.host[1] = time.perf_counter()
            self.turns[1] = gen.turn + 1
            self._stop()
        log("trace: the profiler's start and stop held the engine loop "
            + ", ".join(f"{k} {v:.3f} s" for k, v in self.stalls.items()))
        spans = self.tracer.get_tracer().events()
        self.tracer.disable()
        try:
            return trace_reduce.reduce_dir(self.dir, peaks=self.peaks,
                                           op_costs=self.op_costs,
                                           spans=spans,
                                           sync_host_s=self.sync,
                                           window=tuple(self.host),
                                           turns=tuple(self.turns))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
