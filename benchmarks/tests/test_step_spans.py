"""The serve loop's spans with their args (ISSUE 38): the step-paired shift,
the idle time split by `serve/idle`, the host's work in a turn, the first
token's read and the collections, on a synthetic set shaped like PR 37's
traces: one decode step in flight, so a turn dispatches step n+1 before it
reads step n; the device plane recorded 0.9 ms early."""
import types

import pytest

import harness
import span_reduce as sr
import step_spans as ss
import trace_reduce as tr
from distributeddeeplearning_tpu.obs import trace as trace_mod

EPOCH = 5000.0       # the tracer's epoch on the perf_counter clock
OFFSET = -4999.25    # trace clock = perf_counter + OFFSET
LEAD = 0.0009        # the device plane is recorded this much early
DECODE = "jit__decode_fn"
STEP = 0.008         # a decode program
NEW = ("serve/turn", "serve/idle", "host/gc", "serve/engine.first_token_fetch")


def _synthetic():
    """([(name, start, end, depth, args)] on the host's true clock, [(start,
    end)] of the decode programs on the same clock). Eleven turns, then 60
    ms in which the loop holds nothing, then nine more; the engine's step
    numbers skip one every third step (a prefill's sample)."""
    spans, programs = [], []
    t, prev_end, unread, k = 0.1000, 0.0, None, 0

    def span(name, a, b, depth, **args):
        spans.append((name, a, b, depth, args))
        return b

    for turn in range(20):
        if turn == 11:
            t = span("serve/idle", t, t + 0.060, 0)
        start = t
        t = span("serve/poll", t, t + 0.0001, 1)
        if turn == 4:  # a final chunk: its first token read after the chunk
            a = t
            t = span("serve/engine.chunk_dispatch", t, t + 0.0005, 2)
            t = span("serve/engine.first_token_fetch", t, t + 0.003, 2,
                     uid="r4", slot=1)
            span("serve/prefill_chunk", a, t, 1, uid="r4")
        step = 10 + k + k // 3
        last = turn in (10, 19)  # nothing new dispatched: read only
        a = t
        if not last:
            t = span("serve/engine.decode_upload", t, t + 0.0015, 2, step=step)
            if turn == 7:  # a collection lands inside the upload
                span("host/gc", t - 0.0012, t - 0.0002, 3, generation=2,
                     collected=5)
            t = span("serve/engine.decode_dispatch", t, t + 0.0003, 2,
                     step=step)
            begin = max(t - 0.0003 + 0.00005, prev_end)
            programs.append((begin, begin + STEP))
        if unread is not None:
            n, done = unread
            t = span("serve/engine.decode_fetch", t, max(t + 0.00005,
                                                         done + 0.00005),
                     2, step=n)
        span("serve/decode_step", a, t, 1, active=2)
        unread = None if last else (step, begin + STEP)
        if not last:
            prev_end = begin + STEP
            k += 1
        t = span("serve/emit", t, t + 0.0002, 1)
        span("serve/turn", start, t, 0, step=-1 if last else k, live=2)
    return spans, programs


SPANS, PROGRAMS = _synthetic()
LO, HI = 0.0995, SPANS[-1][2] + 0.0005


def _events(with_new=True):
    """The tracer's events; without the new spans and args, as the parent
    recorded them (no `serve/turn` above, so one level shallower)."""
    out = []
    for name, a, b, depth, args in SPANS:
        if not with_new:
            if name in NEW:
                continue
            args, depth = {}, depth - 1
        out.append({"ph": "X", "name": name, "args": {**args, "depth": depth},
                    "ts": (a - OFFSET - EPOCH) * 1e6, "dur": (b - a) * 1e6})
    return out


def _ctx():
    programs = [(DECODE, a - LEAD, b - a) for a, b in PROGRAMS]
    return types.SimpleNamespace(
        events={"devices": {0: {"ops": list(programs), "modules": programs}},
                "marks": []},
        trace_lo=LO, trace_hi=HI,
        family=types.SimpleNamespace(PROGRAMS={"decode": DECODE}),
        tracer=types.SimpleNamespace(t_started=LO - OFFSET,
                                     t_stopped=HI - OFFSET))


@pytest.fixture
def tracer(monkeypatch):
    def use(with_new=True):
        monkeypatch.setattr(trace_mod, "_TRACER", types.SimpleNamespace(
            events=_events(with_new), epoch_perf_s=EPOCH))
    use()
    return use


def test_step_pairing_holds_where_the_next_fetch_crosses(tracer):
    ctx = _ctx()
    _, lower, upper = sr.plane_shift(ctx, sr.program_spans(ctx), DECODE)
    assert lower > upper  # PR 37's "causality allows 5.571 to -6.780"
    shift, lower, upper, pairs = ss.step_shift(ctx, ss.window_spans(ctx))
    assert pairs == len(PROGRAMS) == 18
    assert lower <= LEAD <= upper
    assert shift == pytest.approx(lower)  # the least that causality asks
    assert lower == pytest.approx(LEAD - 0.00005)  # a program 50 us in


def test_a_device_plane_that_lags_is_shifted_forward(tracer):
    ctx = _ctx()
    for line in ("ops", "modules"):
        ctx.events["devices"][0][line] = [
            (n, a + 2 * LEAD + 0.0001, d) for n, a, d in
            ctx.events["devices"][0][line]]
    shift, lower, upper, _ = ss.step_shift(ctx, ss.window_spans(ctx))
    assert lower <= shift == upper < 0  # a program ends before its read


def _read(name, ctx=None):
    return harness.load_reader(name)(ctx or _ctx())


def test_the_two_idle_shares_make_device_idle(tracer):
    ctx = _ctx()
    split = ss.idle_split(ctx, ss.window_spans(ctx))
    window = HI - LO
    no_request = _read("idle_no_request.serve")
    live = _read("idle_live.serve")
    device = _read("device_idle.serve")
    assert no_request * window + live * (window - split["idle"]) == (
        pytest.approx(device * window, rel=1e-12))
    # the 60 ms stretch is idle throughout; the turns idle while the host
    # uploads before the first program and after the last read
    assert split["idle"] == pytest.approx(0.060)
    assert no_request == pytest.approx(100 * 0.060 / window)
    assert 0 < live < device


def test_live_idle_goes_to_spans_and_to_standard_error(tracer, capsys):
    _read("idle_live.serve")
    err = capsys.readouterr().err
    assert "step-paired shift 0.850 ms (causality allows 0.850 to" in err
    assert "18 steps" in err
    assert "idle live by span: serve/engine.decode_upload" in err
    assert "longest live idle:" in err


def test_the_host_work_of_a_turn_is_the_turn_less_its_waits(tracer):
    turns = [(a, b) for n, a, b, _, _ in SPANS if n == "serve/turn"]
    waits = [(a, b) for n, a, b, _, _ in SPANS
             if n in ("serve/engine.decode_fetch",
                      "serve/engine.first_token_fetch")]
    work = sum(b - a for a, b in turns) - sum(b - a for a, b in waits)
    assert _read("host_turn_work_ms") == pytest.approx(1e3 * work / len(turns))
    assert _read("first_token_wait_ms") == pytest.approx(3.0)
    assert _read("gc_pause_share.serve") == pytest.approx(100 * 0.001 / (HI - LO))


@pytest.mark.parametrize("name", ["turn_host_ms", "idle_unattributed.serve",
                                  "prefill_share_of_turn"])
def test_older_readers_read_the_same_with_the_new_spans(tracer, name):
    with_new = _read(name)
    tracer(with_new=False)
    assert with_new is not None
    assert _read(name) == pytest.approx(with_new, rel=1e-12)


@pytest.mark.parametrize("name", ["idle_no_request.serve", "idle_live.serve",
                                  "host_turn_work_ms", "first_token_wait_ms",
                                  "gc_pause_share.serve"])
def test_a_program_without_the_new_spans_gives_nothing(tracer, name, capsys):
    tracer(with_new=False)
    assert _read(name) is None
    assert "no serve/turn span in the window" in capsys.readouterr().err


def test_no_step_args_leave_no_pair():
    ctx = _ctx()
    spans = [ss.Span(n, a, b, d, {}) for n, a, b, d, _ in SPANS]
    assert ss.step_shift(ctx, spans) is None
    assert tr.idle_share_pct(ctx.events, LO, HI) > 0
