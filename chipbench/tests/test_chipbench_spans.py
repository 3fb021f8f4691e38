"""The readers of the program's spans: on a hand-made traced run (the
profiler ran over host seconds [100, 110), engine turns [10, 20); its
start held the loop 0.04 s before 100, its stop 30 s after 110), and on a
traced run of the tiny CPU cell through ``profile_check``."""
from types import SimpleNamespace

import pytest

from chipbench import harness, profile_check, traffic
from chipbench.tests.test_chipbench_run import REPO, checkout  # noqa: F401

METRICS = REPO / "chipbench"
#: uid -> engine turn of its first token (admission); uid 5 is due after
#: the window, so it is not one of the window's requests
FIRST_TURN = {1: 12, 2: 15, 3: 5, 4: 25, 5: 30, 6: 21, 7: 60}


def _span(name, cat, ts, dur, args):
    return ("X", name, cat, "engine", ts, dur, args)


def _queued(uid, ts, dur):
    return ("X", "queued", "request", f"req/{uid}", ts, dur, {"uid": uid})


ADMIT = [
    _span("prefill_dispatch", "admit", 101.0, 0.010, {"n": 1, "pad": 512}),
    _span("kv_insert", "admit", 101.01, 1.2, {"n": 1, "pad": 512}),
    _span("prefill_dispatch", "admit", 105.0, 0.030, {"n": 1, "pad": 1024}),
    _span("kv_insert", "admit", 105.03, 1.6, {"n": 1, "pad": 1024}),
    # before and after the profiler's window
    _span("prefill_dispatch", "admit", 99.0, 0.5, {"n": 1, "pad": 512}),
    _span("kv_insert", "admit", 111.0, 9.0, {"n": 1, "pad": 512}),
    # a phase span of the same name is not an admission sub-span
    _span("kv_insert", "phase", 102.0, 5.0, None),
]
QUEUED = [
    _queued(1, 100.5, 0.5),
    _queued(2, 102.0, 2.0), _queued(2, 104.5, 0.25),   # re-queued once
    _queued(3, 90.0, 1.0),
    _queued(4, 109.0, 3.0),      # waits across the profiler's stop
    _queued(6, 99.9, 0.2),       # waits across the profiler's start
    _queued(7, 141.0, 5.0),      # queued behind the stop's backlog
    _queued(5, 101.0, 50.0),     # not one of the window's requests
]


def _ctx(spans, *, turns=(10, 20), stalls=None):
    stalls = {"start": 0.04, "stop": 30.0} if stalls is None else stalls
    recs = {uid: harness.Rec(req=traffic.Req(uid=uid, prompt=[1] * 8,
                                             max_new=4),
                             due=0.0, turns=[t, t + 1])
            for uid, t in FIRST_TURN.items()}
    # the plan's tracer, disabled after the window, still holds its events
    tracer = SimpleNamespace(get_tracer=lambda: SimpleNamespace(
        events=lambda: list(spans)))
    gen = SimpleNamespace(recs=recs, trace=SimpleNamespace(stalls=stalls,
                                                            tracer=tracer))
    trace = SimpleNamespace(turn_range=turns, phase_s={"admission": 3.0})
    return harness.Context(cell=None, gen=gen,
                           recs=[r for u, r in recs.items() if u != 5],
                           seconds=51, setup_s=0.0, counters={}, peaks=None,
                           dims=None, trace=trace, traced=(100.0, 110.0))


def _read(name, ctx):
    return harness.reader(name, METRICS)(ctx)


@pytest.mark.parametrize("name, want", [
    # (0.010 + 0.030) s over the 2 requests admitted in turns [10, 20)
    ("admission.prefill_dispatch_ms_per_request", 20.0),
    ("admission.kv_insert_ms_per_request", 1400.0),
    # waits 0.5, 2.25 and 1.0 s; uids 4, 6 and 7 are left out: p95 of
    # [0.5, 1.0, 2.25] is 1.0 + 0.9 * 1.25
    ("engine.queue_wait_p95_ms", 2125.0),
])
def test_span_readers_by_hand(name, want):
    assert _read(name, _ctx(ADMIT + QUEUED)) == pytest.approx(want)


def test_admission_parts_within_the_whole():
    ctx = _ctx(ADMIT + QUEUED)
    parts = sum(_read(f"admission.{p}_ms_per_request", ctx)
                for p in ("prefill_dispatch", "kv_insert"))
    assert parts <= _read("engine.admission_ms_per_request", ctx)


@pytest.mark.parametrize("stalls, want", [
    ({"start": 0.04, "stop": 30.0}, 2125.0),
    # a start that held nothing: uid 6 (0.2 s) counts too; uids 4 and 7,
    # whose waits end after the stop, still do not.  p95 of
    # [0.2, 0.5, 1.0, 2.25] is 1.0 + 0.85 * 1.25
    ({"start": 0.0, "stop": 0.0}, 2062.5),
    ({"start": 0.0, "stop": 30.0}, 2062.5),
])
def test_queue_wait_leaves_out_stalled_requests(stalls, want):
    ctx = _ctx(QUEUED, stalls=stalls)
    assert _read("engine.queue_wait_p95_ms", ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "admission.prefill_dispatch_ms_per_request",
    "admission.kv_insert_ms_per_request", "engine.queue_wait_p95_ms"])
@pytest.mark.parametrize("case", ["no_spans", "no_admission_spans",
                                  "none_admitted", "untraced"])
def test_span_readers_find_nothing(name, case):
    """None, never a raise, where there is nothing to read: a program
    without the spans (a parent's queued spans alone feed the queue
    metric), no request admitted in the traced turns, no traced run."""
    spans, turns, traced = ADMIT + QUEUED, (10, 20), True
    if case == "no_spans":
        spans = []
    elif case == "no_admission_spans":
        spans = QUEUED
    elif case == "none_admitted":
        turns = (40, 50)
    ctx = _ctx(spans, turns=turns)
    if case == "untraced":
        ctx.trace = ctx.traced = ctx.gen.trace = None
    got = _read(name, ctx)
    queue = name == "engine.queue_wait_p95_ms"
    if queue and case in ("no_admission_spans", "none_admitted"):
        assert got == pytest.approx(2125.0)
    else:
        assert got is None


NEW = ["admission.prefill_dispatch_ms_per_request",
       "admission.kv_insert_ms_per_request", "engine.queue_wait_p95_ms"]


@pytest.fixture(scope="module")
def traced(checkout):  # noqa: F811
    return profile_check.run("tiny-open", 2**40 + 3, 3.0, checkout=checkout,
                             need_chip=False)


@pytest.mark.parametrize("name", NEW)
def test_traced_tiny_run_reports(traced, name):
    assert traced["correct"] is True
    assert traced["metrics"][name]["value"] > 0
    assert traced["metrics"][name]["unit"] == "ms"


def test_traced_tiny_run_parts_within_admission(traced):
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    assert m["admission.prefill_dispatch_ms_per_request"] \
        + m["admission.kv_insert_ms_per_request"] \
        <= m["engine.admission_ms_per_request"]


@pytest.mark.parametrize("span", [name for name, _ in profile_check.CHECKED])
def test_sync_mark_places_annotations(traced, span):
    """Each engine span the sync mark puts on the trace's clock lands on its
    own annotation in the profiler's trace, within 2 ms (the offsets read
    about 1 µs on the CPU; a misplaced one lands a whole span away)."""
    prof = traced["profile"]
    got = prof["sync_offsets"][span]
    assert got["annotations"] > 0
    assert abs(got["offset_us"]) < 2000
    assert len(prof["stop_s"]) == 1 and prof["lines"]
