"""Scheduler: the mean whole `serve/turn` less the time it waits on the
device (`serve/engine.decode_fetch`, `serve/engine.first_token_fetch`): the
host's own work in a turn, which the step in flight has to cover. The turn
and the wait go to standard error."""
import sys

import step_spans


def read(ctx):
    spans = step_spans.window_spans(ctx)
    if spans is None:
        return None
    found = step_spans.turn_waits(spans, ctx.trace_lo, ctx.trace_hi)
    if found is None:
        return None
    turns, turn_ms, wait_ms = found
    print(f"host turn work: {turns} whole turns of {turn_ms:.4f} ms, "
          f"{wait_ms:.4f} ms of it waiting on the device", file=sys.stderr)
    return turn_ms - wait_ms
