"""Engine and cache: mean length of `serve/engine.decode_upload`, in which
`engine.decode` puts the step's tokens, positions, block tables and step
number on the device."""
import span_reduce


def read(ctx):
    spans = span_reduce.program_spans(ctx)
    if spans is None:
        return None
    return span_reduce.mean_ms(spans, span_reduce.DECODE_UPLOAD)
