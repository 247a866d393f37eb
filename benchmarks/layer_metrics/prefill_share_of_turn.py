"""Model programs: the time in `serve/prefill_chunk` spans over the time in
`serve/prefill_chunk` and `serve/decode_step` spans, inside the window: what
chunked prefill adds to the decode steps it shares turns with."""
import span_reduce


def read(ctx):
    spans = span_reduce.program_spans(ctx)
    if spans is None:
        return None
    prefill = span_reduce.total_s(spans, span_reduce.PREFILL_CHUNK)
    decode = span_reduce.total_s(spans, span_reduce.DECODE_STEP)
    if prefill + decode <= 0:
        return None
    return 100.0 * prefill / (prefill + decode)
