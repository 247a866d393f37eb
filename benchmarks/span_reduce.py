"""The program's own spans on the device trace's clock.

The serve loop records spans through the program's tracer
(`distributeddeeplearning_tpu.obs.trace`), which follows any profiler capture,
so after a traced window `get_tracer().events` holds the window's spans on the
host's `perf_counter` clock. The harness took two readings of that clock as it
entered and left its `bench/window` mark (`TraceWindow.t_started`,
`t_stopped`); the same mark on the trace's clock is `ctx.trace_lo`,
`ctx.trace_hi`. Two points give the offset, and their disagreement says
whether to trust it.

A program without these spans (the tracer off, or an older program) gives no
span inside the window: every function here then returns nothing and the
metric is left out.
"""
import collections
import sys

import harness
import trace_reduce

MAX_CLOCK_GAP_S = 1e-3
DECODE_STEP = "serve/decode_step"
PREFILL_CHUNK = "serve/prefill_chunk"
DECODE_UPLOAD = "serve/engine.decode_upload"
DECODE_DISPATCH = "serve/engine.decode_dispatch"
DECODE_FETCH = "serve/engine.decode_fetch"
#: the spans that cover a loop turn, none inside another: what device-idle
#: time is attributed to (`serve/decode_step` is its three engine spans)
TURN_SPANS = ("serve/poll", "serve/admission", PREFILL_CHUNK, DECODE_UPLOAD,
              DECODE_DISPATCH, DECODE_FETCH, "serve/emit")

#: start and end in seconds on the trace's clock, clipped to the window;
#: `whole` is false for a span the window's edge cut
Span = collections.namedtuple("Span", "name start end depth whole")


def _say(why: str):
    print(f"span_reduce: no program spans: {why}", file=sys.stderr)


def clock_offset(ctx):
    """Seconds to add to a `perf_counter` reading to land on the trace's
    clock, or None (with a line on standard error) where it cannot be had."""
    window = getattr(ctx, "tracer", None)
    lo, hi = getattr(ctx, "trace_lo", None), getattr(ctx, "trace_hi", None)
    if (window is None or lo is None or hi is None
            or window.t_started is None or window.t_stopped is None):
        _say("the run has no traced window")
        return None
    at_start, at_stop = lo - window.t_started, hi - window.t_stopped
    if abs(at_start - at_stop) > MAX_CLOCK_GAP_S:
        _say(f"the window's two clock points disagree by "
             f"{1e3 * abs(at_start - at_stop):.3f} ms")
        return None
    return (at_start + at_stop) / 2


def program_spans(ctx):
    """The tracer's spans that touch the traced window, as `Span`s sorted by
    start, or None."""
    offset = clock_offset(ctx)
    if offset is None:
        return None
    from distributeddeeplearning_tpu.obs.trace import get_tracer

    tracer = get_tracer()
    epoch = getattr(tracer, "epoch_perf_s", None)
    if epoch is None:
        _say("the program's tracer gives no epoch_perf_s")
        return None
    lo, hi = ctx.trace_lo, ctx.trace_hi
    spans = []
    for event in tracer.events:
        if event.get("ph") != "X":
            continue
        start = epoch + 1e-6 * event["ts"] + offset
        end = start + 1e-6 * event["dur"]
        a, b = max(start, lo), min(end, hi)
        if b > a:
            spans.append(Span(event["name"], a, b, event["args"].get("depth", 0),
                              a == start and b == end))
    if not spans:
        _say("the tracer holds no span inside the window")
        return None
    return sorted(spans, key=lambda s: (s.start, s.depth, -s.end))


def mean_ms(spans, name):
    """Mean length of the whole spans of that name, in ms, or None."""
    lengths = [s.end - s.start for s in spans if s.name == name and s.whole]
    if not lengths:
        return None
    return 1e3 * sum(lengths) / len(lengths)


def total_s(spans, name):
    return sum(s.end - s.start for s in spans if s.name == name)


def turn_table(spans):
    """A loop turn runs from the start of one decode step to the start of the
    next. Per turn, in ms: the turn's mean length, each span's mean time in
    it, and `host`: the turn less its decode step and prefill chunks. None
    where the window holds no whole turn."""
    starts = [s.start for s in spans if s.name == DECODE_STEP]
    if len(starts) < 2:
        return None
    first, last, n = starts[0], starts[-1], len(starts) - 1
    table = {"turns": n, "turn": 1e3 * (last - first) / n}
    for name in (DECODE_STEP,) + TURN_SPANS:
        inside = sum(min(s.end, last) - s.start for s in spans
                     if s.name == name and first <= s.start < last)
        table[name] = 1e3 * inside / n
    table["host"] = table["turn"] - table[DECODE_STEP] - table[PREFILL_CHUNK]
    return table


def idle_intervals(events, lo, hi):
    """[(start, end)] in which no operation ran on device 0, inside the
    window, as `trace_reduce.idle_gaps` builds them."""
    if not events or not events["devices"]:
        return []
    device = events["devices"][min(events["devices"])]
    busy = trace_reduce._union(
        (a, b) for _, a, b in trace_reduce._clip(device["ops"], lo, hi))
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def innermost_segments(spans):
    """[(start, end, name)]: the window's time cut by the innermost span
    that covers it. Spans of one thread nest, and the tracer's `depth` says
    how: a span ends its siblings whatever their rounded ends say. Time under
    no span is left out."""
    segments, stack = [], []  # stack of [name, end, cursor, depth]

    def close(upto, depth):
        while stack and (stack[-1][1] <= upto or stack[-1][3] >= depth):
            name, end, cursor, _ = stack.pop()
            end = min(end, upto)
            if end > cursor:
                segments.append((cursor, end, name))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for s in spans:
        close(s.start, s.depth)
        if stack:
            parent = stack[-1]
            if s.start > parent[2]:
                segments.append((parent[2], s.start, parent[0]))
            parent[2] = max(parent[2], s.start)
        stack.append([s.name, s.end, s.start, s.depth])
    close(float("inf"), 0)
    return sorted(segments)


def overlap_s(intervals, segments):
    """Seconds of `intervals` inside each segment's name: {name: seconds}.
    Both are sorted and, each among themselves, disjoint."""
    out, j = {}, 0
    for a, b in intervals:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            start, end, name = segments[k]
            shared = min(b, end) - max(a, start)
            if shared > 0:
                out[name] = out.get(name, 0.0) + shared
            k += 1
    return out


def plane_shift(ctx, spans, decode_program=None):
    """Seconds to add to device 0's times to put them on the host plane's
    clock; `decode_program` is the decode program's name in the trace, by
    default the `PROGRAMS["decode"]` of the ctx's family. The profiler aligns
    the two planes only to about a millisecond (a decode program has been
    seen to start 0.9 ms before the span that dispatches it), which matters
    where a turn's idle time is 5 ms. Causality
    bounds the shift from both sides: a decode program cannot start before
    its `serve/engine.decode_dispatch` span does (`lower`), nor end after the
    `serve/engine.decode_fetch` span that reads its result (`upper`). The
    shift is the value between the two that is nearest to 0, and 0 where they
    cross. Returns (shift, lower, upper)."""
    if decode_program is None:
        decode_program = harness.family_of(ctx).PROGRAMS["decode"]
    device = ctx.events["devices"][min(ctx.events["devices"])]
    programs = sorted((a, a + d) for name, a, d in device["modules"]
                      if decode_program in name)
    lower, upper = -float("inf"), float("inf")
    dispatches = [s for s in spans if s.name == DECODE_DISPATCH and s.whole]
    fetches = [s for s in spans if s.name == DECODE_FETCH and s.whole]
    for d in dispatches:
        launched = min(programs, key=lambda p: abs(p[0] - d.start), default=None)
        fetch = next((f for f in fetches if f.start >= d.end), None)
        if launched is None or fetch is None or fetch.start - d.end > 1e-3:
            continue
        lower = max(lower, d.start - launched[0])
        upper = min(upper, fetch.end - launched[1])
    if lower > upper:
        return 0.0, lower, upper
    return min(max(0.0, lower), upper), lower, upper


def shifted_idle(ctx, shift):
    """Device 0's idle intervals in the window, `shift` seconds later: on the
    host plane's clock where `shift` is `plane_shift`'s."""
    lo, hi = ctx.trace_lo, ctx.trace_hi
    idle = [(max(a + shift, lo), min(b + shift, hi))
            for a, b in idle_intervals(ctx.events, lo, hi)]
    return [(a, b) for a, b in idle if b > a]


def longest_idle(idle, segments, k=5):
    """The `k` longest of the `idle` intervals: [(seconds, start, the
    innermost span over the interval's middle)], `segments` being
    `innermost_segments`'."""
    out = []
    for a, b in sorted(idle, key=lambda ab: ab[0] - ab[1])[:k]:
        mid = (a + b) / 2
        name = next((n for s, e, n in segments if s <= mid < e), "no span")
        out.append((b - a, a, name))
    return out


def idle_by_span(idle, spans, segments):
    """Of the `idle` intervals: (seconds in all, seconds inside at least one
    of `TURN_SPANS`, {innermost span of any name: seconds}); what no span at
    all covers is under "no span"."""
    total = sum(b - a for a, b in idle)
    named = [(a, b, "named") for a, b in trace_reduce._union(
        (s.start, s.end) for s in spans if s.name in TURN_SPANS)]
    by_span = overlap_s(idle, segments)
    by_span["no span"] = max(total - sum(by_span.values()), 0.0)
    return total, overlap_s(idle, named).get("named", 0.0), by_span
