"""The serve loop's spans with their args, and device 0 on the host plane's
clock by step number (ISSUE 38).

`span_reduce` reads the tracer's spans without their args, and its
`plane_shift` pairs a decode dispatch with the fetch that follows it, which
since PR 37 reads the step before: its bounds cross. Since ISSUE 38 the
engine's `serve/engine.decode_dispatch` and `.decode_fetch` carry `step`, the
engine's number for the step, so a dispatch is paired with its own fetch
whatever the depth in flight, and with its own program: decode programs run
one at a time, in the order they were dispatched.

Since ISSUE 38 the loop is tiled at depth 0 by `serve/turn` (a turn that
holds a request) and `serve/idle` (a stretch that holds none). A program
without them (the parent of ISSUE 38) gives no `serve/turn` in the window:
`window_spans` then returns nothing, and so does every reader built on it.
"""
import bisect
import collections
import sys

import harness
import span_reduce
import trace_reduce

TURN = "serve/turn"
IDLE = "serve/idle"
FIRST_TOKEN_FETCH = "serve/engine.first_token_fetch"
GC = "host/gc"
#: what a turn waits on the device for; the rest of it is the host's work
WAITS = (span_reduce.DECODE_FETCH, FIRST_TOKEN_FETCH)
#: how far the ranks of the dispatches and of the programs may be apart: a
#: program dispatched before the capture can run inside it, and a dispatch
#: made just before the capture's end may not run inside it
MAX_RANK_OFFSET = 3

#: start and end in seconds on the trace's clock, not clipped; `args` as
#: the tracer recorded them
Span = collections.namedtuple("Span", "name start end depth args")


def window_spans(ctx):
    """Every span the tracer holds, on the trace's clock and sorted by
    start, or None (with a line on standard error) where the window holds
    no `serve/turn`."""
    offset = span_reduce.clock_offset(ctx)
    if offset is None:
        return None
    from distributeddeeplearning_tpu.obs.trace import get_tracer

    tracer = get_tracer()
    epoch = getattr(tracer, "epoch_perf_s", None)
    if epoch is None:
        return None
    spans = []
    for event in tracer.events:
        if event.get("ph") != "X":
            continue
        start = epoch + 1e-6 * event["ts"] + offset
        spans.append(Span(event["name"], start, start + 1e-6 * event["dur"],
                          event["args"].get("depth", 0), event["args"]))
    lo, hi = ctx.trace_lo, ctx.trace_hi
    if not any(s.name == TURN and s.end > lo and s.start < hi for s in spans):
        print("step_spans: no serve/turn span in the window", file=sys.stderr)
        return None
    return sorted(spans, key=lambda s: (s.start, s.depth, -s.end))


def whole(spans, name, lo, hi):
    """The spans of that name that lie wholly inside [lo, hi]."""
    return [s for s in spans if s.name == name and lo <= s.start and s.end <= hi]


def clipped_s(spans, name, lo, hi):
    """Seconds of the spans of that name inside [lo, hi]."""
    return sum(max(0.0, min(s.end, hi) - max(s.start, lo))
               for s in spans if s.name == name)


def step_shift(ctx, spans, decode_program=None):
    """Seconds to add to device 0's times to put them on the host plane's
    clock: (shift, lower, upper, pairs), or None where no step has both its
    spans and a program. Decode program n starts no earlier than
    `decode_dispatch(step=n)` starts (`lower`) and ends no later than
    `decode_fetch(step=n)` ends (`upper`). The k-th dispatch ran the
    program of rank k + r; r is the offset whose bounds hold a shift nearest
    0 (one off by a rank is off by a whole step). The shift is the value in
    the bounds nearest 0, and 0 where they cross."""
    if decode_program is None:
        decode_program = harness.family_of(ctx).PROGRAMS["decode"]
    device = ctx.events["devices"][min(ctx.events["devices"])]
    programs = sorted((a, a + d) for name, a, d in device["modules"]
                      if decode_program in name)
    dispatch, fetch = {}, {}
    for s in spans:
        step = s.args.get("step")
        if step is None:
            continue
        if s.name == span_reduce.DECODE_DISPATCH:
            dispatch[step] = s
        elif s.name == span_reduce.DECODE_FETCH:
            fetch[step] = s
    steps = sorted(dispatch)
    best = None
    for r in range(-MAX_RANK_OFFSET, MAX_RANK_OFFSET + 1):
        lower, upper, pairs = -float("inf"), float("inf"), 0
        for k, step in enumerate(steps):
            if step not in fetch or not 0 <= k + r < len(programs):
                continue
            start, end = programs[k + r]
            lower = max(lower, dispatch[step].start - start)
            upper = min(upper, fetch[step].end - end)
            pairs += 1
        if not pairs:
            continue
        crossed = lower > upper
        shift = 0.0 if crossed else min(max(0.0, lower), upper)
        key = (crossed, lower - upper if crossed else abs(shift))
        if best is None or key < best[0]:
            best = (key, (shift, lower, upper, pairs))
    return None if best is None else best[1]


def _subtract(intervals, cuts):
    """`intervals` less `cuts`, both sorted and disjoint among themselves."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(cuts) and cuts[j][1] <= a:
            j += 1
        k = j
        while k < len(cuts) and cuts[k][0] < b:
            if cuts[k][0] > a:
                out.append((a, cuts[k][0]))
            a = max(a, cuts[k][1])
            k += 1
        if b > a:
            out.append((a, b))
    return out


def idle_split(ctx, spans):
    """Device 0's idle time in the window split by `serve/idle`, the spans
    put on the device's clock (less the step-paired shift), so that the idle
    time is `device_idle.serve`'s to the digit. A dict: `window`, `idle`
    (seconds of `serve/idle` spans), `no_request` (device idle inside
    them), `live` (the device's idle intervals outside them), `shift`
    (`step_shift`'s tuple, or None: no shift), `moved` (the spans on the
    device's clock, clipped, as `span_reduce.Span`s)."""
    lo, hi = ctx.trace_lo, ctx.trace_hi
    found = step_shift(ctx, spans)
    shift = found[0] if found else 0.0
    moved = [span_reduce.Span(s.name, max(s.start - shift, lo),
                              min(s.end - shift, hi), s.depth,
                              lo <= s.start - shift and s.end - shift <= hi)
             for s in spans if s.end - shift > lo and s.start - shift < hi]
    idle = span_reduce.idle_intervals(ctx.events, lo, hi)
    stretches = [tuple(ab) for ab in trace_reduce._union(
        (s.start, s.end) for s in moved if s.name == IDLE)]
    live = _subtract(idle, stretches)
    total = sum(b - a for a, b in idle)
    return {"window": hi - lo, "idle": sum(b - a for a, b in stretches),
            "no_request": total - sum(b - a for a, b in live),
            "live": live, "shift": found,
            "moved": sorted(moved, key=lambda s: (s.start, s.depth, -s.end))}


def turn_waits(spans, lo, hi):
    """(turns, mean turn in ms, mean wait on the device in ms: `WAITS`)
    over the whole `serve/turn` spans in [lo, hi], or None."""
    turns = whole(spans, TURN, lo, hi)
    if not turns:
        return None
    waits = sorted((s.start, s.end) for s in spans if s.name in WAITS)
    starts = [a for a, _ in waits]
    length = waited = 0.0
    for t in turns:
        length += t.end - t.start
        i = bisect.bisect_left(starts, t.start)
        while i < len(waits) and waits[i][0] < t.end:
            waited += min(waits[i][1], t.end) - waits[i][0]
            i += 1
    return len(turns), 1e3 * length / len(turns), 1e3 * waited / len(turns)
