"""Engine and cache: mean length of `serve/engine.decode_fetch`, the reads of
the step's results from the device: how long the host waits on the device
each step."""
import span_reduce


def read(ctx):
    spans = span_reduce.program_spans(ctx)
    if spans is None:
        return None
    return span_reduce.mean_ms(spans, span_reduce.DECODE_FETCH)
