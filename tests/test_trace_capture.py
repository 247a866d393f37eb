"""The tracer follows any profiler capture; the serve loop's spans cover the
whole turn; admission counts why it refused; the benchmark's readers turn
spans and counters into numbers (ISSUE 26).

The load-bearing guarantees:

- outside a capture and not enabled the tracer records nothing (the flight
  recorder still does); inside ``jax.profiler.start_trace``, with no
  ``enable()``, the same spans land in ``tracer.events`` AND in the capture's
  host plane with their scalar args, on clocks that two ``perf_counter``
  readings map onto each other;
- the event list is bounded and counts what it dropped;
- one ``scheduler.run`` over a paged engine yields every span of the turn,
  properly nested, and the spans that idle time is attributed to (the leaves
  of the issue's table: ``span_reduce.TURN_SPANS``) cover the run's wall
  (85% of it since ISSUE 37 took the wait for the step out of the wall);
- ``ServeReport.admission_turns`` names the resource that blocked the head,
  and the page sums obey written <= reserved (0 on the dense engine);
- each of the seven per-layer readers under ``benchmarks/layer_metrics``
  returns the number worked out by hand from known spans, idle intervals and
  a 40 ms clock offset, and nothing when the two clock points disagree.
"""

from __future__ import annotations

import glob
import os
import sys
import time
import types

import jax
import numpy as np
import pytest

from distributeddeeplearning_tpu.models.pipelined_transformer import (
    init_params,
)
from distributeddeeplearning_tpu.obs import recorder as recorder_mod
from distributeddeeplearning_tpu.obs import trace as trace_mod
from distributeddeeplearning_tpu.obs.recorder import FlightRecorder
from distributeddeeplearning_tpu.obs.trace import Tracer
from distributeddeeplearning_tpu.serve import (
    ContinuousBatchingScheduler,
    InferenceEngine,
    PagedInferenceEngine,
    Request,
)

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

CFG = dict(num_layers=2, d_model=32, num_heads=4, d_ff=64, vocab_size=61,
           max_len=64)
# wide enough that a turn is the model's work and not the tracer's own cost
WIDE = dict(CFG, num_layers=4, d_model=128, d_ff=512)
ALL_SPANS = (
    "serve/poll", "serve/admission", "serve/admit", "serve/prefill_chunk",
    "serve/engine.chunk_dispatch", "serve/decode_step",
    "serve/engine.decode_upload", "serve/engine.decode_dispatch",
    "serve/engine.decode_fetch", "serve/emit",
)
PARENT = {
    "serve/admit": "serve/admission",
    "serve/engine.chunk_dispatch": "serve/prefill_chunk",
    "serve/engine.decode_upload": "serve/decode_step",
    "serve/engine.decode_dispatch": "serve/decode_step",
    "serve/engine.decode_fetch": "serve/decode_step",
}


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), **CFG)


@pytest.fixture
def tracer():
    """A fresh process tracer, NOT enabled, restored afterwards."""
    fresh = trace_mod.set_tracer(Tracer(enabled=False))
    yield fresh
    trace_mod.set_tracer(Tracer(enabled=False))


def _paged(params, *, slots=3, pages=40, page_size=8):
    return PagedInferenceEngine(
        params, num_heads=CFG["num_heads"], batch_slots=slots, max_seq=64,
        page_size=page_size, num_pages=pages, prefill_chunk=8,
    )


def _requests(n, *, prompt_len=10, new=12, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(uid=f"r{i}", max_new_tokens=new, trace_id=f"t{i}",
                prompt=rng.integers(1, CFG["vocab_size"], prompt_len).tolist())
        for i in range(n)
    ]


def _poll_all_then_close(requests):
    """A live source: everything at the first poll, closed once drained."""
    state = {"handed": False}

    def poll():
        if not state["handed"]:
            state["handed"] = True
            return list(requests)
        return None

    return poll


def _capture(trace_dir):
    """A capture with the benchmark harness's own options."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)


def _host_plane_events(trace_dir, prefix):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    found = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith(prefix):
                    found.append((event.name, dict(event.stats),
                                  event.start_ns * 1e-9,
                                  event.duration_ns * 1e-9))
    return found


# --- the tracer ------------------------------------------------------------

def test_span_outside_capture_feeds_only_the_flight_recorder():
    rec = FlightRecorder(capacity=16)
    t = Tracer(enabled=False, recorder=rec)
    with t.span("serve/decode_step", active=2):
        pass
    t.event("serve/request_complete", uid="r0")
    assert t.events == [] and t.dropped == 0
    names = [e["name"] for e in rec.entries()]
    assert names == ["serve/decode_step", "serve/request_complete"]


def test_capture_turns_the_tracer_on_and_spans_land_in_the_host_plane(
    tmp_path,
):
    t = Tracer(enabled=False)
    with t.span("serve/before"):
        pass
    _capture(tmp_path)
    try:
        with t.span("serve/decode_step", active=3):
            with t.span("serve/admit", uid="r7", trace="t7", prompt_len=11):
                time.sleep(0.002)
        t.event("serve/request_complete", uid="r7")
    finally:
        jax.profiler.stop_trace()
    with t.span("serve/after"):
        pass
    assert not t.enabled
    assert [e["name"] for e in t.events] == [
        "serve/admit", "serve/decode_step", "serve/request_complete"]
    assert t.events[0]["args"]["depth"] == 1
    in_plane = {name: stats for name, stats, _, _ in
                _host_plane_events(tmp_path, "serve/")}
    assert set(in_plane) == {"serve/decode_step", "serve/admit"}
    assert in_plane["serve/decode_step"]["active"] == 3
    assert in_plane["serve/admit"]["uid"] == "r7"
    assert in_plane["serve/admit"]["trace"] == "t7"
    assert in_plane["serve/admit"]["prompt_len"] == 11


def test_annotate_false_opts_out_of_following_a_capture(tmp_path):
    t = Tracer(enabled=False, annotate=False)
    _capture(tmp_path)
    try:
        with t.span("serve/decode_step"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert t.events == []


def test_two_clock_readings_put_tracer_spans_on_the_capture_clock(tmp_path):
    """What ``benchmarks/span_reduce.py`` relies on: a mark entered and left
    beside two ``perf_counter`` readings maps ``ts`` (through
    ``epoch_perf_s``) onto the capture's clock to well under 1 ms."""
    t = Tracer(enabled=False)
    _capture(tmp_path)
    try:
        mark = jax.profiler.TraceAnnotation("bench/window")
        mark.__enter__()
        t_started = time.perf_counter()
        for _ in range(3):
            with t.span("serve/emit"):
                time.sleep(0.003)
        t_stopped = time.perf_counter()
        mark.__exit__(None, None, None)
    finally:
        jax.profiler.stop_trace()
    ((_, _, lo, dur),) = _host_plane_events(tmp_path, "bench/window")
    at_start, at_stop = lo - t_started, lo + dur - t_stopped
    assert abs(at_start - at_stop) < 1e-3
    offset = (at_start + at_stop) / 2
    twins = sorted(s for _, _, s, _ in _host_plane_events(tmp_path, "serve/"))
    mapped = sorted(t.epoch_perf_s + 1e-6 * e["ts"] + offset for e in t.events)
    assert len(twins) == len(mapped) == 3
    assert max(abs(a - b) for a, b in zip(twins, mapped)) < 1e-3


def test_events_are_bounded_oldest_dropped_and_counted():
    t = Tracer(enabled=True, annotate=False, max_events=4)
    for i in range(7):
        with t.span("s", i=i):
            pass
    t.event("last")
    assert [e["args"].get("i") for e in t.events] == [4, 5, 6, None]
    assert t.dropped == 4
    assert t.to_chrome_trace()["metadata"]["dropped"] == 4
    t.clear()
    assert t.events == [] and t.dropped == 0
    assert trace_mod.MAX_EVENTS >= 100_000  # the process tracer's bound


# --- the serve loop's spans -------------------------------------------------

def _children(events):
    """{index: parent index}: the innermost span of the same thread that
    contains each span."""
    spans = [e for e in events if e["ph"] == "X"]
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i]["ts"], -spans[i]["dur"]))
    parent, stack = {}, []
    for i in order:
        s = spans[i]
        while stack and (spans[stack[-1]]["ts"] + spans[stack[-1]]["dur"]
                         <= s["ts"]):
            stack.pop()
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    return spans, parent


def test_one_run_yields_every_span_of_the_turn_nested_and_covering(tracer):
    import span_reduce

    engine = _paged(init_params(jax.random.key(1), **WIDE))
    scheduler = ContinuousBatchingScheduler(engine, eos_id=None)
    scheduler.run(_requests(3, new=3, seed=9))  # compiles, outside the spans
    tracer.enable()
    tracer.clear()
    t0 = time.perf_counter()
    results, report = scheduler.run(
        [], poll=_poll_all_then_close(_requests(8, new=24)))
    wall = time.perf_counter() - t0
    assert [r.finish_reason for r in results] == ["length"] * 8

    spans, parent = _children(tracer.events)
    names = {s["name"] for s in spans}
    assert set(ALL_SPANS) <= names, set(ALL_SPANS) - names
    for i, s in enumerate(spans):
        up = parent[i]
        want = PARENT.get(s["name"])
        if want is not None:
            assert up is not None and spans[up]["name"] == want, s
        if up is not None:
            assert s["args"]["depth"] == spans[up]["args"]["depth"] + 1
            assert s["ts"] + s["dur"] <= spans[up]["ts"] + spans[up]["dur"] + 1
    # request-scoped spans keep the request's uid and trace id
    admits = [s for s in spans if s["name"] == "serve/admit"]
    assert {(s["args"]["uid"], s["args"]["trace"]) for s in admits} == {
        (f"r{i}", f"t{i}") for i in range(8)}
    steps = [s for s in spans if s["name"] == "serve/decode_step"]
    # one of each engine span a decode step; one serve/decode_step a turn,
    # which holds a step's dispatch and the read of the step before it (a
    # step dispatched with nothing in flight is read a turn later, in a
    # span that holds the read alone)
    for name in ("serve/engine.decode_upload", "serve/engine.decode_dispatch",
                 "serve/engine.decode_fetch"):
        assert sum(s["name"] == name for s in spans) == report.decode_steps
    assert report.decode_steps_overlapped >= 0.9 * report.decode_steps
    assert len(steps) == (
        2 * report.decode_steps - report.decode_steps_overlapped)
    assert all(s["args"]["active"] >= 1 for s in steps)
    # none of the seven lies inside another, so their lengths add up
    assert not any(
        spans[up]["name"] in span_reduce.TURN_SPANS
        for i, up in parent.items()
        if up is not None and spans[i]["name"] in span_reduce.TURN_SPANS)
    covered = 1e-6 * sum(
        s["dur"] for s in spans if s["name"] in span_reduce.TURN_SPANS)
    # what no span covers (the sweeps, building the step) is the same tenth
    # of a millisecond a turn it was; the wait for the step inside
    # decode_fetch, which was half the wall and wholly covered, now runs
    # beside the host's turn, so the share stands on a shorter wall
    assert covered >= 0.85 * wall, (covered, wall)


def test_dense_engine_decode_has_the_three_engine_spans(params, tracer):
    engine = InferenceEngine(
        params, num_heads=CFG["num_heads"], batch_slots=2, max_seq=64)
    tracer.enable()
    _, report = ContinuousBatchingScheduler(engine, eos_id=None).run(
        _requests(2, new=4))
    counts = {}
    for e in tracer.events:
        counts[e["name"]] = counts.get(e["name"], 0) + 1
    for name in ("serve/engine.decode_upload", "serve/engine.decode_dispatch",
                 "serve/engine.decode_fetch", "serve/emit"):
        assert counts[name] == report.decode_steps
    # no pages on the dense engine: nothing reserved, nothing written
    assert report.kv_pages_reserved_sum == report.kv_pages_written_sum == 0
    assert report.admission_turns["blocked_pages"] == 0


def test_no_capture_and_not_enabled_leaves_the_process_tracer_empty(
    params, tracer,
):
    """The driver's ``--trace 0`` run at a small size: the whole loop, spans
    and all, records nothing into the tracer (the ring still fills)."""
    ring = recorder_mod.set_recorder(FlightRecorder(capacity=64))
    try:
        tracer.attach_recorder(trace_mod.PROCESS_RECORDER)
        results, _ = ContinuousBatchingScheduler(
            _paged(params), eos_id=None,
        ).run([], poll=_poll_all_then_close(_requests(4, new=6)))
    finally:
        recorder_mod.set_recorder(FlightRecorder())
    assert len(results) == 4
    assert tracer.events == [] and tracer.dropped == 0
    assert {"serve/emit", "serve/admission"} <= {
        e["name"] for e in ring.entries()}


def test_capture_from_outside_a_running_loop_holds_its_spans(
    params, tracer, tmp_path,
):
    """An operator's capture: ``start_trace`` / ``stop_trace`` around a
    running ``scheduler.run``, no ``enable()``: the ``serve/`` spans sit on
    the host track of the capture."""
    scheduler = ContinuousBatchingScheduler(_paged(params), eos_id=None)
    scheduler.run(_requests(2, new=2, seed=5))
    batch, done, polls = _requests(4, new=10), [], []

    def poll():
        polls.append(None)
        if len(polls) == 1:
            return batch
        if len(polls) == 3:
            _capture(tmp_path)  # the loop is three turns in
        return None if len(done) == len(batch) else []

    try:
        scheduler.run([], poll=poll, on_complete=done.append)
    finally:
        jax.profiler.stop_trace()
    assert not tracer.enabled
    in_tracer = {e["name"] for e in tracer.events}
    in_plane = {name for name, _, _, _ in
                _host_plane_events(tmp_path, "serve/")}
    wanted = {"serve/decode_step", "serve/engine.decode_fetch", "serve/emit",
              "serve/poll"}
    assert wanted <= in_tracer and wanted <= in_plane


# --- admission counters ------------------------------------------------------

@pytest.mark.parametrize("slots,pages,blocked,clear", [
    (3, 5, "blocked_pages", "blocked_slots"),   # a pool too small for two
    (1, 40, "blocked_slots", "blocked_pages"),  # one slot, pages to spare
])
def test_admission_turns_name_the_resource_that_blocked(
    params, slots, pages, blocked, clear,
):
    engine = _paged(params, slots=slots, pages=pages)
    # 10 + 12 tokens reserve 3 pages of 8 a request
    results, report = ContinuousBatchingScheduler(engine, eos_id=None).run(
        _requests(3))
    assert [r.finish_reason for r in results] == ["length"] * 3
    turns = report.admission_turns
    assert turns[blocked] > 0 and turns[clear] == 0
    assert turns["blocked_hbm"] == 0
    assert turns["queued"] >= turns[blocked] + 1  # the admitting turns too
    assert report.to_dict()["admission_turns"] == turns


def test_written_pages_never_exceed_reserved_pages(params):
    engine = _paged(params)
    _, report = ContinuousBatchingScheduler(engine, eos_id=None).run(
        _requests(3, prompt_len=10, new=12))
    assert 0 < report.kv_pages_written_sum <= report.kv_pages_reserved_sum
    # 3 requests hold 3 pages each from their first decode step; the third
    # page (positions 16-21) is written only from each request's 7th token on
    assert report.kv_pages_written_sum < report.kv_pages_reserved_sum
    # shared prefix pages count once: two slots over one prompt
    shared = _requests(1, prompt_len=17, new=4)[0].prompt
    for slot in (0, 1):
        engine.prefill(slot, shared, 4)
    reserved, written = engine.kv_pages_held({0: 17, 1: 17})
    assert reserved == engine.allocator.pages_in_use == 4  # 2 shared + 1 + 1
    assert written == 4
    assert engine.kv_pages_held({0: 16, 1: 3}) == (4, 2)


# --- the seven readers over a hand-made context ------------------------------

EPOCH = 1000.0      # the tracer's epoch on the perf_counter clock
OFFSET = 0.040      # trace clock = perf_counter - EPOCH + 40 ms


def _span(name, lo_ms, hi_ms, depth=0):
    """A tracer event given by its bounds on the TRACE's clock, in ms."""
    return {"ph": "X", "name": name, "args": {"depth": depth},
            "ts": (lo_ms * 1e-3 - OFFSET) * 1e6,
            "dur": (hi_ms - lo_ms) * 1e3}


def _step(lo, up, disp, hi):
    return [_span("serve/decode_step", lo, hi),
            _span("serve/engine.decode_upload", lo, up, 1),
            _span("serve/engine.decode_dispatch", up, disp, 1),
            _span("serve/engine.decode_fetch", disp, hi, 1)]


HAND_SPANS = (
    _step(100, 103, 104, 145) + [_span("serve/emit", 145, 148)]   # cut at 140
    + _step(150, 152, 153, 210) + [
        _span("serve/emit", 210, 214), _span("serve/poll", 215, 216),
        _span("serve/admission", 216, 220), _span("serve/admit", 217, 219, 1),
        _span("serve/prefill_chunk", 220, 240),
        _span("serve/engine.chunk_dispatch", 221, 225, 1)]
    + _step(250, 253, 254, 310) + [
        _span("serve/emit", 310, 312), _span("serve/poll", 313, 314)]
    + _step(350, 352, 353, 410) + [_span("serve/emit", 410, 414)]
    + [{"ph": "i", "name": "serve/request_complete", "ts": 1.0, "args": {}}]
)
# device 0 is busy but for: 209.5-210.5 (fetch | emit), 214-215.5 (nothing |
# poll), 217.5-218.5 (admit), 222-223 (chunk dispatch), 240.5-251.5 (nothing
# for 9.5 ms | upload for 1.5 ms): 15.5 ms idle, 5.0 ms of it inside a span
# of the turn
BUSY_MS = [(140, 209.5), (210.5, 214), (215.5, 217.5), (218.5, 222),
           (223, 240.5), (251.5, 540)]
BY_HAND = {
    # turns [140,150) [150,250) [250,350): 210 ms less 125 ms of decode steps
    # less 20 ms of prefill chunk, over 3
    "turn_host_ms": 65.0 / 3,
    "decode_upload_ms": (2 + 3 + 2) / 3,          # the whole ones
    "decode_fetch_ms": (57 + 56 + 57) / 3,        # the cut one left out
    "prefill_share_of_turn": 100 * 20 / (20 + 5 + 60 + 60 + 60),
    "idle_unattributed.serve": 100 * 10.5 / 15.5,
    "admission_blocked_by_pages": 75.0,
    "kv_reserved_unwritten": 20.0,
}
SPAN_READERS = ("turn_host_ms", "decode_upload_ms", "decode_fetch_ms",
                "prefill_share_of_turn", "idle_unattributed.serve")


def _hand_ctx(stop_skew_s=0.0):
    ops = [("fusion.1", a * 1e-3, (b - a) * 1e-3) for a, b in BUSY_MS]
    return types.SimpleNamespace(
        events={"devices": {0: {"ops": ops, "modules": []}}, "marks": []},
        trace_lo=0.140, trace_hi=0.540,
        tracer=types.SimpleNamespace(
            t_started=EPOCH + 0.140 - OFFSET,
            t_stopped=EPOCH + 0.540 - OFFSET + stop_skew_s),
        report=types.SimpleNamespace(
            admission_turns={"queued": 40, "blocked_slots": 4,
                             "blocked_pages": 30, "blocked_hbm": 0},
            kv_pages_reserved_sum=1000, kv_pages_written_sum=800),
    )


@pytest.fixture
def hand_tracer():
    trace_mod.set_tracer(types.SimpleNamespace(
        events=list(HAND_SPANS), epoch_perf_s=EPOCH))
    yield
    trace_mod.set_tracer(Tracer(enabled=False))


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_returns_the_number_worked_out_by_hand(name, hand_tracer):
    import harness

    value = harness.load_reader(name)(_hand_ctx())
    assert value == pytest.approx(BY_HAND[name], rel=1e-9)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_reader_returns_nothing_when_the_clock_points_disagree(
    name, hand_tracer, capsys,
):
    import harness

    assert harness.load_reader(name)(_hand_ctx(stop_skew_s=0.002)) is None
    assert "disagree by 2.000 ms" in capsys.readouterr().err


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_returns_nothing_from_a_program_without_spans_or_counters(
    name,
):
    """The parent commit under this PR's benchmark files: a tracer that is
    off and has no ``epoch_perf_s``, a report without the counters."""
    import harness

    trace_mod.set_tracer(types.SimpleNamespace(events=[]))
    try:
        ctx = _hand_ctx()
        ctx.report = types.SimpleNamespace(decode_steps=5)
        assert harness.load_reader(name)(ctx) is None
    finally:
        trace_mod.set_tracer(Tracer(enabled=False))


def test_idle_seconds_by_innermost_span(hand_tracer, capsys):
    import span_reduce

    ctx = _hand_ctx()
    spans = span_reduce.program_spans(ctx)
    idle = span_reduce.shifted_idle(ctx, 0.0)
    segments = span_reduce.innermost_segments(spans)
    total, named, by_span = span_reduce.idle_by_span(idle, spans, segments)
    assert total == pytest.approx(0.0155)
    assert named == pytest.approx(0.0050)
    assert {k: round(v * 1e3, 6) for k, v in by_span.items()} == {
        "serve/engine.decode_fetch": 0.5, "serve/emit": 0.5,
        "serve/poll": 0.5, "serve/admit": 1.0,
        "serve/engine.chunk_dispatch": 1.0,
        "serve/engine.decode_upload": 1.5, "no span": 10.5}
    longest = span_reduce.longest_idle(idle, segments, k=2)
    assert [(round(1e3 * s, 6), round(1e3 * at, 6), name)
            for s, at, name in longest] == [
        (11.0, 240.5, "no span"), (1.5, 214.0, "no span")]


@pytest.mark.parametrize("lead_ms,shift_ms", [
    (0.0, 0.0),     # causality holds as recorded: nothing to repair
    (0.8, 0.3),     # the device plane leads: the least shift that repairs it
    (-2.0, -0.5),   # it lags: programs would end after their results are read
])
def test_plane_shift_is_the_least_that_causality_asks(
    lead_ms, shift_ms, hand_tracer,
):
    """Decode programs really ran from 0.5 ms into their dispatch span to
    1.5 ms before their fetch span's end; the device plane records them
    ``lead_ms`` early."""
    import span_reduce

    ctx = _hand_ctx()
    ctx.events["devices"][0]["modules"] = [
        ("jit__decode_fn(7)", (a + 0.5 - lead_ms) * 1e-3,
         (b - 1.5 - a - 0.5) * 1e-3)
        for a, b in ((152, 210), (253, 310), (352, 410))
    ] + [("jit__chunk_fn(9)", 0.221, 0.018)]
    shift, lower, upper = span_reduce.plane_shift(
        ctx, span_reduce.program_spans(ctx))
    assert (lower, upper) == pytest.approx(
        ((lead_ms - 0.5) * 1e-3, (lead_ms + 1.5) * 1e-3))
    assert shift == pytest.approx(shift_ms * 1e-3, abs=1e-12)
