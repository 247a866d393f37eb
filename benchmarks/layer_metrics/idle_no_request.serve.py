"""Scheduler: device 0's idle time inside `serve/idle` spans, where the loop
held no request, over the traced window (`step_spans.idle_split`): idle for
want of demand. With `idle_live.serve`, each weighted by its window, it makes
`device_idle.serve`."""
import step_spans


def read(ctx):
    if ctx.events is None:
        return None
    spans = step_spans.window_spans(ctx)
    if spans is None:
        return None
    split = step_spans.idle_split(ctx, spans)
    return 100.0 * split["no_request"] / split["window"]
