"""Engine and cache: mean length of the whole
`serve/engine.first_token_fetch` spans in the window: the blocking read of a
request's first token after its final chunk (or its whole prefill), behind
whatever decode step is in flight."""
import step_spans


def read(ctx):
    spans = step_spans.window_spans(ctx)
    if spans is None:
        return None
    reads = step_spans.whole(spans, step_spans.FIRST_TOKEN_FETCH,
                             ctx.trace_lo, ctx.trace_hi)
    if not reads:
        return None
    return 1e3 * sum(s.end - s.start for s in reads) / len(reads)
