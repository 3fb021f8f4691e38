"""Observability subsystem (DESIGN.md §16): tracer no-op fast path, span
nesting + Chrome/Perfetto export schema, histogram percentiles, the engine's
metrics-backed stats() view, per-request lifecycle spans for every terminal
state, step-phase attribution, and the tracing-overhead guard."""
import jax
import numpy as np
import pytest

from repro.configs import gemma_2b
from repro.models import registry
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.trace import NOOP_SPAN, Tracer, validate_chrome_trace
from repro.runtime.resilience import FailureInjector
from repro.serve import Request, RequestState, ServeEngine


@pytest.fixture(autouse=True)
def _clean_global_tracer():
    """Never leak an enabled process-wide tracer into other tests."""
    yield
    obs_trace.disable()
    obs_trace.get_tracer().clear()


@pytest.fixture(scope="module")
def setup():
    cfg = gemma_2b.CONFIG.reduced()
    api = registry.get_api(cfg)
    sp = api.unstack(api.init(cfg, jax.random.key(0)), cfg)
    return cfg, sp


def _engine(cfg, sp, **kw):
    base = dict(max_slots=2, max_seq=64, prefill_pad=8, qimpl="xla")
    base.update(kw)
    return ServeEngine(cfg, sp, **base)


def _requests(n=3, max_new=6, **kw):
    return [Request(uid=i, prompt=[3 + i + j for j in range(4 + i)],
                    max_new_tokens=max_new, **kw) for i in range(n)]


def _events_on(tracer, track):
    return [e for e in tracer.events() if e[3] == track]


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_disabled_tracer_is_noop_singleton():
    t = Tracer()
    assert not t.enabled
    # every call site gets the SAME pre-allocated object: no per-call
    # allocation on the disabled fast path
    s1 = t.span("a", args={"x": 1})
    s2 = t.span("b")
    assert s1 is NOOP_SPAN and s2 is NOOP_SPAN
    with s1:
        s1.annotate(ignored=True)
    t.instant("nope")
    t.counter("nope", 1.0)
    t.complete("nope", ts=0.0, dur=1.0)
    assert t.events() == []


def test_span_records_and_reenables_cleanly():
    t = Tracer()
    t.enable()
    with t.span("outer", cat="phase", args={"k": 1}):
        with t.span("inner"):
            pass
    t.disable()
    with t.span("after-disable"):
        pass
    evs = t.events()
    assert [e[1] for e in evs] == ["inner", "outer"]  # exit order
    outer = evs[1]
    inner = evs[0]
    # nesting: inner's interval is contained in outer's
    assert outer[4] <= inner[4]
    assert inner[4] + inner[5] <= outer[4] + outer[5] + 1e-9


def test_span_feeds_histogram():
    t = Tracer()
    t.enable()
    h = obs_metrics.Histogram()
    with t.span("timed", hist=h):
        pass
    assert h.count == 1 and h.sum > 0


def test_chrome_trace_schema_and_tracks():
    t = Tracer()
    t.enable()
    with t.span("phase_a", cat="phase", track="engine"):
        t.instant("marker", track="req/7", args={"uid": 7})
    t.counter("queue_depth", 3)
    doc = t.chrome_trace()
    validate_chrome_trace(doc)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"phase_a", "marker", "queue_depth", "process_name",
            "thread_name"} <= names
    # each distinct track becomes a named thread lane
    lanes = {e["args"]["name"] for e in doc["traceEvents"]
             if e["name"] == "thread_name"}
    assert {"engine", "req/7", "counters"} <= lanes
    # timestamps rebased to enable time: everything non-negative µs
    assert all(e.get("ts", 0) >= 0 for e in doc["traceEvents"])


def test_validate_rejects_malformed_docs():
    with pytest.raises(ValueError):
        validate_chrome_trace({"nope": []})
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "a", "pid": 0, "tid": 1,
                              "ts": 0.0}]})  # X without dur
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [{"ph": "?", "name": "a", "pid": 0, "tid": 1,
                              "ts": 0.0}]})


def test_save_roundtrip(tmp_path):
    t = Tracer()
    t.enable()
    with t.span("x"):
        pass
    path = tmp_path / "trace.json"
    doc = t.save(str(path))
    import json
    assert json.loads(path.read_text()) == doc


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_counter_and_gauge():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("done")
    c.inc()
    c.inc(2.5)
    assert reg.counter("done") is c and c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(7)
    assert g.value == 7.0
    with pytest.raises(TypeError):
        reg.gauge("done")  # kind mismatch


def test_histogram_percentiles_uniform():
    h = obs_metrics.Histogram(buckets=[float(x) for x in range(0, 1001, 10)])
    vals = np.arange(1, 1001, dtype=float)
    for v in vals:
        h.observe(v)
    # fine buckets + uniform data: interpolation lands near the exact rank
    for p in (50, 90, 99):
        exact = float(np.percentile(vals, p))
        assert abs(h.percentile(p) - exact) <= 15.0, (p, h.percentile(p))
    assert h.min == 1.0 and h.max == 1000.0
    assert h.summary()["count"] == 1000


def test_histogram_single_sample_is_exact():
    h = obs_metrics.Histogram()
    h.observe(0.003)
    for p in (0, 50, 100):
        assert h.percentile(p) == pytest.approx(0.003)
    assert h.summary()["p99"] == pytest.approx(0.003)


def test_histogram_empty_and_overflow():
    h = obs_metrics.Histogram(buckets=[1.0, 2.0])
    assert h.percentile(50) == 0.0 and h.summary()["count"] == 0
    h.observe(50.0)  # overflow bucket
    assert h.percentile(99) == pytest.approx(50.0)


def test_histogram_merge_matches_single_stream():
    buckets = [float(x) for x in range(0, 101, 5)]
    a, b, ref = (obs_metrics.Histogram(buckets=buckets) for _ in range(3))
    rng = np.random.RandomState(0)
    for i, v in enumerate(rng.uniform(0, 100, 200)):
        (a if i % 2 else b).observe(v)
        ref.observe(v)
    a.merge(b)
    # merged counts are exactly what one histogram observing both streams
    # would hold — same counts, sum, extremes, percentiles
    assert a.counts == ref.counts
    assert a.count == ref.count == 200
    assert a.sum == pytest.approx(ref.sum)
    assert (a.min, a.max) == (ref.min, ref.max)
    for p in (50, 90, 99):
        assert a.percentile(p) == pytest.approx(ref.percentile(p))


def test_histogram_merge_rejects_mismatched_edges():
    a = obs_metrics.Histogram(buckets=[1.0, 2.0])
    b = obs_metrics.Histogram(buckets=[1.0, 2.0, 3.0])
    with pytest.raises(ValueError, match="different bucket edges"):
        a.merge(b)


def test_histogram_state_roundtrip_then_merge():
    h = obs_metrics.Histogram(buckets=[1.0, 10.0])
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    back = obs_metrics.Histogram.from_state(h.state())
    assert back.counts == h.counts and back.sum == h.sum
    assert (back.min, back.max) == (h.min, h.max)
    back.merge(h)  # reconstructed histograms stay merge-compatible
    assert back.count == 6
    empty = obs_metrics.Histogram.from_state(
        obs_metrics.Histogram(buckets=[1.0, 10.0]).state())
    assert empty.count == 0 and empty.min == float("inf")


def test_registry_reset_keeps_instances():
    reg = obs_metrics.MetricsRegistry()
    c, g = reg.counter("done"), reg.gauge("depth")
    h = reg.histogram("lat", buckets=[1.0, 2.0])
    c.inc(3)
    g.set(7)
    h.observe(1.5)
    reg.reset()
    # zeroed in place: callers holding references keep observing into the
    # same objects (the warm-up exclusion contract)
    assert reg.counter("done") is c and c.value == 0.0
    assert g.value == 0.0 and h.count == 0 and h.sum == 0.0
    h.observe(0.5)
    assert h.count == 1 and reg.snapshot()["lat"]["count"] == 1


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


def test_stats_view_is_metrics_backed(setup):
    cfg, sp = setup
    eng = _engine(cfg, sp)
    out = eng.run(_requests())
    st = eng.stats()
    for key in ("prefill_tokens", "decode_steps", "loop_turns", "completed",
                "failed", "cancelled", "timed_out", "wall_s", "shed_events",
                "health"):
        assert key in st, key
    assert st["completed"] == 3 == len(out)
    assert st["loop_turns"] >= st["decode_steps"] > 0
    assert st["wall_s"] > 0
    # the registry is the source of truth behind the view
    assert st["decode_steps"] == int(eng.metrics.counter("decode_steps").value)
    # the always-on step-time histogram covers EVERY loop turn (admission
    # and prefill turns included), and feeds the health median
    h = eng.metrics.histogram("step_time_s")
    assert h.count == st["loop_turns"]
    assert st["health"]["step_time_median_s"] == pytest.approx(
        h.percentile(50))
    # TTFT/ITL land unconditionally (tracing was never enabled here)
    assert eng.metrics.histogram("ttft_s").count == 3
    assert st["latency"]["ttft_s"]["count"] == 3


def test_stats_calibration_ratios(setup):
    """stats()["calibration"] closes the predict/measure loop (DESIGN.md §18):
    the packed-tree byte measurement must agree exactly with the cost model's
    packing prediction, and the traced-latency ratio appears only once the
    phase histograms have samples."""
    from repro.core.policy import BitPolicy, PolicyArtifact
    from repro.cost import ShiftAddCostModel
    from repro.quant import apply as qapply

    cfg, sp = setup
    params = registry.get_api(cfg).init(cfg, jax.random.key(0))
    specs = qapply.layer_specs(params, cfg)
    rng = np.random.default_rng(1)
    policy = BitPolicy.from_bits(
        specs, {s.name: int(rng.choice([2, 4, 6, 8])) for s in specs})
    report = ShiftAddCostModel().report(policy).as_costs()
    artifact = PolicyArtifact.build(policy, backend="shift_add", report=report)
    qp = qapply.quantize_for_serve(sp, artifact, cfg)
    eng = _engine(cfg, qp, artifact=artifact)
    # the measurement is real packing maths, not the prediction echoed back
    assert eng.weight_container_bytes() == policy.container_bytes()
    eng.run(_requests(n=1, max_new=3))
    cal = eng.stats()["calibration"]
    assert cal["container_bytes"]["ratio"] == pytest.approx(1.0)
    # fp cache + untraced run: no state-bytes or latency measurement yet
    assert "state_bytes" not in cal and "latency_s" not in cal
    obs_trace.enable()
    eng.run(_requests(n=1, max_new=3))
    obs_trace.disable()
    cal = eng.stats()["calibration"]
    assert "latency_s" in cal and cal["latency_s"]["measured"] > 0


def test_stats_without_report_has_no_calibration(setup):
    cfg, sp = setup
    eng = _engine(cfg, sp)
    eng.run(_requests(n=1, max_new=2))
    assert "calibration" not in eng.stats()


def test_trace_report_attributes_step_time(setup):
    cfg, sp = setup
    eng = _engine(cfg, sp)
    eng.run(_requests())          # warmup: compile outside the traced pass
    obs_trace.enable()
    eng.run(_requests(n=2))
    obs_trace.disable()
    rep = eng.trace_report()
    assert rep["steps"] > 0
    assert set(rep["phases"]) <= {"hook", "reap", "admission", "prep",
                                  "dispatch", "device_sync", "commit",
                                  "bookkeeping"}
    assert "dispatch" in rep["phases"]
    # acceptance bar: >= 90% of traced step wall time lands in named phases
    assert rep["attributed_fraction"] >= 0.90, rep
    assert rep["unattributed_fraction"] <= 0.10
    fracs = [p["fraction_of_step"] for p in rep["phases"].values()]
    assert abs(sum(fracs) - rep["attributed_fraction"]) < 1e-6


def test_trace_report_notes_untraced_engine(setup):
    cfg, sp = setup
    eng = _engine(cfg, sp)
    eng.run(_requests(n=1))
    rep = eng.trace_report()
    assert rep["steps"] == 0 and "note" in rep


def test_lifecycle_spans_done(setup):
    cfg, sp = setup
    eng = _engine(cfg, sp)
    obs_trace.enable()
    eng.run(_requests(n=1))
    tr = obs_trace.get_tracer()
    evs = _events_on(tr, "req/0")
    # the request's one lifecycle event: its wait from submit to admission
    assert [e[:3] for e in evs] == [("X", "queued", "request")]
    assert evs[0][6] == {"uid": 0}
    q_end = evs[0][4] + evs[0][5]
    assert any(e[1] == "admission" and e[4] <= q_end <= e[4] + e[5]
               for e in tr.events())


def test_lifecycle_spans_timed_out(setup):
    cfg, sp = setup
    eng = _engine(cfg, sp)
    obs_trace.enable()
    eng.run([Request(uid=0, prompt=[3, 4, 5], max_new_tokens=4,
                     deadline_s=0.0)])
    assert eng.lifecycles[0].state is RequestState.TIMED_OUT
    tr = obs_trace.get_tracer()
    evs = _events_on(tr, "req/0")
    # never admitted: the queued segment closes at the expiry
    assert [e[1] for e in evs] == ["queued"]


def _finish_as(outcome):
    """(engine kwargs, step hook, request) ending one request ``outcome``."""
    if outcome == "failed":
        return ({"state_bits": 8, "fault_injector": FailureInjector(
            schedule={"nan_logit": (1,)})}, None, _requests(n=1)[0])
    if outcome == "cancelled":
        return {}, lambda engine, step: engine.cancel(0), \
            _requests(n=1, max_new=32)[0]
    if outcome == "timed_out":
        return {}, None, Request(uid=0, prompt=[3, 4, 5], max_new_tokens=4,
                                 deadline_s=0.0)
    return {}, None, _requests(n=1)[0]


@pytest.mark.parametrize("outcome",
                         ["done", "failed", "cancelled", "timed_out"])
def test_queued_span_per_request(setup, outcome):
    """Every request submitted while the tracer is on gets one ``queued``
    span, whatever its end: the wait the benchmark's queue metric reads."""
    cfg, sp = setup
    kw, hook, req = _finish_as(outcome)
    eng = _engine(cfg, sp, **kw)
    obs_trace.enable()
    eng.run([req], step_hook=hook)
    assert eng.lifecycles[0].state.value == outcome
    evs = _events_on(obs_trace.get_tracer(), "req/0")
    assert [(e[1], e[2], e[6]) for e in evs] == [
        ("queued", "request", {"uid": 0})]
    assert evs[0][5] >= 0


def test_lifecycle_spans_preempted_requeue(setup):
    cfg, sp = setup
    eng = _engine(cfg, sp, max_slots=1)
    fired = []

    def hook(engine, step):
        if step == 3 and not fired:
            fired.append(step)
            engine.submit(Request(uid=100, prompt=[9, 9, 9],
                                  max_new_tokens=4, priority=2))

    obs_trace.enable()
    out = eng.run(_requests(n=1, max_new=24), step_hook=hook)
    assert eng.lifecycles[0].state is RequestState.DONE
    assert eng.lifecycles[0].preemptions == 1
    assert len(out[0]) == 24
    tr = obs_trace.get_tracer()
    evs = _events_on(tr, "req/0")
    # the preempted request waits in the queue twice: from submit, and
    # from its preemption to its second admission
    assert [e[1] for e in evs] == ["queued", "queued"]
    assert evs[0][4] + evs[0][5] <= evs[1][4]


def test_kernel_config_replay_traced(setup):
    cfg, sp = setup
    obs_trace.enable()
    from repro.kernels import autotune
    key = autotune.KernelKey(family="decode_step", k_bits=4, v_bits=4,
                             heads=cfg.n_kv_heads,
                             head_dim=cfg.resolved_head_dim, block=16,
                             impl="xla")
    autotune.autotune_key(key, batch=2, blocks=4, repeats=1)
    tr = obs_trace.get_tracer()
    names = [e[1] for e in _events_on(tr, "kernel")]
    assert "autotune_candidate" in names and "autotune_winner" in names


def test_tracing_overhead_bounded(setup):
    """Tracing must stay cheap: generous bound (3x + slack) so a noisy CI
    box never flakes, while a pathological per-span cost still fails."""
    import time

    cfg, sp = setup
    eng = _engine(cfg, sp)
    reqs = _requests(n=2, max_new=8)
    eng.run(reqs)  # compile

    def timed(traced):
        if traced:
            obs_trace.enable()
        else:
            obs_trace.disable()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            eng.run(_requests(n=2, max_new=8))
            best = min(best, time.perf_counter() - t0)
        return best

    untraced = timed(False)
    traced = timed(True)
    obs_trace.disable()
    assert traced <= untraced * 3 + 0.05, (traced, untraced)


# ---------------------------------------------------------------------------
# the profiler's clock: live spans mirrored as TraceAnnotations
# ---------------------------------------------------------------------------


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; logs enter/exit with
    the clock reading at each."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        import time
        self.log.append(("enter", self.name, time.perf_counter()))

    def __exit__(self, *exc):
        import time
        self.log.append(("exit", self.name, time.perf_counter()))


@pytest.mark.parametrize("kind", ["span", "complete", "instant", "counter"])
def test_only_live_spans_open_annotations(kind):
    t = Tracer()
    t.enable()
    t._annotation = _FakeAnnotation
    _FakeAnnotation.log = []
    if kind == "span":
        with t.span("admission", cat="phase"):
            pass
    elif kind == "complete":
        t.complete("queued", ts=t.now(), dur=0.0, cat="request")
    elif kind == "instant":
        t.instant("mark")
    else:
        t.counter("depth", 1.0)
    log = _FakeAnnotation.log
    if kind != "span":        # retroactive and point events: not mirrored
        assert log == []
        return
    (enter, name, t_in), (leave, name2, t_out) = log
    assert (enter, leave, name, name2) == ("enter", "exit", "admission",
                                           "admission")
    (ev,) = t.events()
    # the annotation opens before the span's first clock read and closes
    # after its second: the recorded duration leaves out its cost
    assert t_in <= ev[4] and ev[4] + ev[5] <= t_out


@pytest.mark.parametrize("enable", [False, True])
def test_obs_imports_jax_only_on_enable(enable):
    """Importing ``repro.obs`` stays stdlib-only; the first ``enable()``
    binds ``jax.profiler.TraceAnnotation``."""
    import subprocess
    import sys
    code = ("import sys; from repro.obs import trace; "
            "assert 'jax' not in sys.modules; "
            + ("trace.enable(); from jax.profiler import TraceAnnotation; "
               "assert trace.get_tracer()._annotation is TraceAnnotation; "
               if enable else "")
            + "print('ok')")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.stdout.strip() == "ok", out.stderr


def test_profiler_capture_shows_engine_spans(setup, tmp_path):
    """A JAX profiler capture taken while the tracer is on holds every
    engine phase and admission span of the run on its host plane, each at
    least as long as the span the tracer recorded."""
    import glob
    from jax.profiler import ProfileData

    cfg, sp = setup
    eng = _engine(cfg, sp)
    eng.run(_requests(n=2))          # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    obs_trace.enable()
    try:
        eng.run(_requests(n=2))
    finally:
        obs_trace.disable()
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(e.duration_ns / 1e9)
    spans = {}
    for ev in obs_trace.get_tracer().events():
        if ev[0] == "X" and ev[2] in ("phase", "admit", "step"):
            spans.setdefault(ev[1], []).append(ev[5])
    assert {"admission", "kv_insert", "prefill_dispatch", "step"} <= set(spans)
    for name, durs in spans.items():
        assert len(host.get(name, [])) == len(durs), name
        assert max(host[name]) >= max(durs), name


# ---------------------------------------------------------------------------
# admission sub-spans (category ``admit``)
# ---------------------------------------------------------------------------

ADMIT_MODES = {
    "dense": {},
    "paged": {"state_bits": 4, "paged": True, "pool_blocks": 24},
    "chunked": {"prefill_chunk": 3},
    "chunked-paged": {"prefill_chunk": 3, "state_bits": 4, "paged": True,
                      "pool_blocks": 24},
}


def _phase_events(events):
    return [e for e in events if e[2] == "phase"]


@pytest.fixture(scope="module", params=list(ADMIT_MODES))
def admit_runs(request, setup):
    """Per engine mode: one run untraced, a twin traced, and a third traced
    with the admission sub-spans switched off."""
    from types import SimpleNamespace

    cfg, sp = setup
    kw = ADMIT_MODES[request.param]
    tr = obs_trace.get_tracer()
    tr.clear()
    plain = _engine(cfg, sp, **kw)
    ref = plain.run(_requests())
    untraced_events = tr.events()

    def traced_run(eng):
        obs_trace.enable()
        try:
            out = eng.run(_requests())
        finally:
            obs_trace.disable()
        events = tr.events()
        tr.clear()
        return out, events

    traced = _engine(cfg, sp, **kw)
    out, events = traced_run(traced)
    bare = _engine(cfg, sp, **kw)
    bare._admit_span = lambda *a: NOOP_SPAN
    _, bare_events = traced_run(bare)
    return SimpleNamespace(chunked="prefill_chunk" in kw, plain=plain,
                           ref=ref, untraced_events=untraced_events,
                           traced=traced, out=out, events=events,
                           bare_events=bare_events)


def test_admit_spans_nest_in_phase(admit_runs):
    """Each admitted request is counted by one ``kv_insert`` span and one
    ``prefill_dispatch`` span (chunked: ``kv_insert`` alone, at the final
    chunk), each inside the phase that does the admission."""
    r = admit_runs
    admit = [e for e in r.events if e[2] == "admit"]
    names = {"kv_insert"} if r.chunked else {"kv_insert", "prefill_dispatch"}
    assert {e[1] for e in admit} == names
    for name in names:
        spans = [e for e in admit if e[1] == name]
        assert sum(e[6]["n"] for e in spans) == len(_requests())
        assert all(e[3] == "engine" and e[6]["pad"] == 8 for e in spans)
    parent = "prefill_chunk" if r.chunked else "admission"
    phases = [e for e in r.events if e[2] == "phase" and e[1] == parent]
    for e in admit:
        assert any(p[4] <= e[4] and e[4] + e[5] <= p[4] + p[5]
                   for p in phases), e
    if not r.chunked:     # the dispatch comes first, then the insertion
        order = [e[1] for e in sorted(admit, key=lambda e: e[4])]
        assert order == ["prefill_dispatch", "kv_insert"] * (len(order) // 2)


def test_tracer_off_records_nothing(admit_runs):
    """Disabled, the run records no event and fills no phase histogram, and
    its stats() counters are those of the traced twin."""
    r = admit_runs
    assert r.untraced_events == []
    assert not [k for k in r.plain.metrics.snapshot() if "/" in k]
    a, b = r.plain.stats(), r.traced.stats()
    assert set(a) == set(b) and set(a["health"]) == set(b["health"])
    for key in ("prefill_tokens", "decode_steps", "loop_turns", "completed",
                "prefill_chunks", "shed_events"):
        assert a[key] == b[key], key


def test_traced_tokens_match_untraced(admit_runs):
    assert admit_runs.out == admit_runs.ref


def test_admit_spans_leave_phase_totals(admit_runs):
    """The ``admit`` spans are counted in no phase: the phase events of a
    traced run are those of a run without them, the phase histograms hold
    exactly the phase events, and the attributed fraction is theirs."""
    r = admit_runs
    key = lambda e: (e[1], e[2])
    assert sorted(map(key, _phase_events(r.events))) == \
        sorted(map(key, _phase_events(r.bare_events)))
    assert not [e for e in r.bare_events if e[2] == "admit"]
    rep = r.traced.trace_report()
    assert not set(rep["phases"]) & {"kv_insert", "prefill_dispatch"}
    totals = {}
    for e in _phase_events(r.events):
        totals[e[1]] = totals.get(e[1], 0.0) + e[5]
    assert set(rep["phases"]) == set(totals)
    for name, total in totals.items():
        assert rep["phases"][name]["total_s"] == pytest.approx(total)
    steps = sum(e[5] for e in r.events if e[2] == "step")
    assert rep["attributed_fraction"] == pytest.approx(
        sum(totals.values()) / steps)
    assert rep["attributed_fraction"] <= 1.0
