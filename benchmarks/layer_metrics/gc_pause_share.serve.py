"""Host process: the time in `host/gc` spans (the program's `gc.callbacks`
hook: one span a collection) inside the traced window, over the window. 0
where no collection landed in it; nothing from a program without
`serve/turn` spans, which has no such hook."""
import step_spans


def read(ctx):
    spans = step_spans.window_spans(ctx)
    if spans is None:
        return None
    lo, hi = ctx.trace_lo, ctx.trace_hi
    return 100.0 * step_spans.clipped_s(spans, step_spans.GC, lo, hi) / (hi - lo)
