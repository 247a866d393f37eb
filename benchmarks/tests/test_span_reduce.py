"""The program's spans on the trace's clock: the two-point mapping, the
clipping, the turn table, and the attribution of device-idle time to spans,
on the recorded serve trace (`serve_trace_fixture.json`: a chunk program, a
decode program, the start of a second) under hand-made spans whose bounds
are read off that trace."""
import types

import pytest
from test_trace_reduce import recorded

import span_reduce as sr
import trace_reduce as tr

EPOCH = 5000.0       # the tracer's epoch on the perf_counter clock
OFFSET = -4999.25    # trace clock = perf_counter + OFFSET
DECODE = "jit__decode_fn"  # the decode program, as the recorded trace names it

# (name, start, end, depth) on the TRACE's clock, in seconds: one turn that
# carries a prefill chunk, then the next turn's first 12 ms
HAND = [
    ("serve/decode_step", 0.0300, 0.0439, 0),          # ends before the window
    ("serve/prefill_chunk", 0.0420, 0.0935, 0),        # cut by the window's start
    ("serve/engine.chunk_dispatch", 0.0440, 0.0445, 1),  # cut likewise
    ("serve/decode_step", 0.0936, 0.1523, 0),
    ("serve/engine.decode_upload", 0.0936, 0.0943, 1),
    ("serve/engine.decode_dispatch", 0.0943, 0.0946, 1),
    ("serve/engine.decode_fetch", 0.0946, 0.1520, 1),
    ("serve/emit", 0.15235, 0.1526, 0),
    ("serve/poll", 0.15262, 0.15265, 0),
    ("serve/decode_step", 0.1527, 0.2100, 0),          # cut by the window's end
    ("serve/engine.decode_upload", 0.1527, 0.1530, 1),
    ("serve/engine.decode_dispatch", 0.1530, 0.1533, 1),
    ("serve/engine.decode_fetch", 0.1533, 0.2099, 1),  # cut likewise
]


def tracer_events():
    return [{"ph": "X", "name": name, "args": {"depth": depth},
             "ts": (a - OFFSET - EPOCH) * 1e6, "dur": (b - a) * 1e6}
            for name, a, b, depth in HAND]


@pytest.fixture
def ctx(monkeypatch):
    from distributeddeeplearning_tpu.obs import trace as trace_mod

    events, (lo, hi) = recorded("serve_trace_fixture.json")
    monkeypatch.setattr(trace_mod, "_TRACER", types.SimpleNamespace(
        events=tracer_events(), epoch_perf_s=EPOCH))
    return types.SimpleNamespace(
        events=events, trace_lo=lo, trace_hi=hi,
        tracer=types.SimpleNamespace(t_started=lo - OFFSET + 2e-5,
                                     t_stopped=hi - OFFSET - 2e-5))


def test_two_points_give_the_offset_and_spans_are_clipped(ctx):
    assert sr.clock_offset(ctx) == pytest.approx(OFFSET, abs=1e-9)
    spans = sr.program_spans(ctx)
    lo, hi = ctx.trace_lo, ctx.trace_hi
    assert [s.name for s in spans][:2] == [
        "serve/prefill_chunk", "serve/engine.chunk_dispatch"]
    assert len(spans) == len(HAND) - 1          # the step before the window is out
    assert all(lo <= s.start < s.end <= hi for s in spans)
    cut = {(s.name, round(s.start, 4)) for s in spans if not s.whole}
    assert cut == {("serve/prefill_chunk", round(lo, 4)),
                   ("serve/engine.chunk_dispatch", round(lo, 4)),
                   ("serve/decode_step", 0.1527),
                   ("serve/engine.decode_fetch", 0.1533)}
    by_name = {s.name: s for s in spans if s.whole}
    assert by_name["serve/emit"].start == pytest.approx(0.15235, abs=1e-7)
    # means take whole spans only; sums take the clipped parts too
    assert sr.mean_ms(spans, "serve/engine.decode_fetch") == pytest.approx(57.4)
    assert sr.total_s(spans, "serve/engine.decode_fetch") == pytest.approx(
        0.0574 + hi - 0.1533)


def test_clock_points_that_disagree_give_nothing(ctx, capsys):
    ctx.tracer.t_stopped += 0.0015
    assert sr.program_spans(ctx) is None
    assert "disagree" in capsys.readouterr().err
    ctx.tracer.t_stopped = None                 # the window never closed
    assert sr.program_spans(ctx) is None


def test_turn_table_of_the_one_whole_turn(ctx):
    table = sr.turn_table(sr.program_spans(ctx))
    assert table["turns"] == 1
    assert table["turn"] == pytest.approx(59.1)             # 0.0936 to 0.1527
    assert table["serve/decode_step"] == pytest.approx(58.7)
    assert table["serve/prefill_chunk"] == 0                # began before the turn
    assert table["host"] == pytest.approx(0.4)
    assert table["serve/emit"] == pytest.approx(0.25)


def idle_by_span(ctx, spans):
    """As the `idle_unattributed.serve` reader wires it."""
    idle = sr.shifted_idle(ctx, sr.plane_shift(ctx, spans, DECODE)[0])
    return sr.idle_by_span(idle, spans, sr.innermost_segments(spans))


def by_brute_force(idle, spans):
    """The reference: cut the idle time at every span's edge and give each
    piece to the deepest span that holds its middle."""
    edges = sorted({x for a, b in idle for x in (a, b)}
                   | {x for s in spans for x in (s.start, s.end)})
    out = {}
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        if not any(x <= mid <= y for x, y in idle):
            continue
        holding = [s for s in spans if s.start <= mid <= s.end]
        name = max(holding, key=lambda s: s.depth).name if holding else "no span"
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def test_idle_time_goes_to_the_innermost_span(ctx):
    spans = sr.program_spans(ctx)
    lo, hi = ctx.trace_lo, ctx.trace_hi
    idle = sr.idle_intervals(ctx.events, lo, hi)
    total, named, by_span = idle_by_span(ctx, spans)
    assert total == pytest.approx(sum(b - a for a, b in idle))
    assert total == pytest.approx(hi - lo - tr.busy_seconds(ctx.events, lo, hi))
    assert sum(by_span.values()) == pytest.approx(total)
    reference = by_brute_force(idle, spans)
    assert {k: v for k, v in by_span.items() if v > 1e-12} == pytest.approx(reference)
    # what the recorded trace shows, under these spans: of the 2.64 ms from
    # the sampler (done at 0.09123) to the next upload program (0.09387),
    # 2.27 ms are the prefill chunk's own (to 0.0935), 0.1 ms nobody's, the
    # rest the upload's, like the 0.47 ms before the decode program; the
    # 2.8 ms after it are shared by the fetch's tail, the step's own end, the
    # emit block, the poll and what no span covers
    assert by_span["serve/prefill_chunk"] == pytest.approx(0.00228, abs=2e-5)
    assert by_span["serve/engine.decode_upload"] == pytest.approx(
        0.00027 + 0.00047 + 0.00006, abs=3e-5)
    assert by_span["serve/emit"] == pytest.approx(0.00025, abs=1e-6)
    assert by_span["serve/poll"] == pytest.approx(0.00003, abs=1e-6)
    uncovered = 100.0 * (1.0 - named / total)
    assert uncovered == pytest.approx(
        100.0 * (by_span["no span"] + by_span.get("serve/decode_step", 0.0)) / total)
    assert 0 < uncovered < 10


def test_a_device_plane_that_leads_is_shifted_back(ctx):
    """The recorded trace with every device time 0.9 ms early, as a capture
    on the chip showed it: causality (a decode program starts inside its
    dispatch span, 34 us in) gives the shift back but for those 34 us, and
    the idle time lands in the same spans."""
    spans = sr.program_spans(ctx)
    before = idle_by_span(ctx, spans)
    assert sr.plane_shift(ctx, spans, DECODE)[0] == 0.0
    for device in ctx.events["devices"].values():
        for line in device:
            device[line] = [(n, a - 0.0009, d) for n, a, d in device[line]]
    shift, lower, upper = sr.plane_shift(ctx, spans, DECODE)
    assert shift == lower == pytest.approx(0.0009 - 0.000034, abs=2e-6)
    assert upper == pytest.approx(0.0009 + 0.00228, abs=2e-5)
    total, named, by_span = idle_by_span(ctx, spans)
    # all but the 0.24 ms before the first recorded op, which now lies before
    # the window and was the chunk dispatch's
    lost = "serve/engine.chunk_dispatch"
    assert before[0] - total == pytest.approx(0.000242, abs=4e-5)
    assert before[2][lost] - by_span.get(lost, 0.0) == pytest.approx(0.000242, abs=4e-5)
    for name, seconds in before[2].items():
        if name != lost:
            assert by_span.get(name, 0.0) == pytest.approx(seconds, abs=4e-5), name
