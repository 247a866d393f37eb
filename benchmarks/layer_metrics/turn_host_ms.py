"""Scheduler: the host's own share of a loop turn. A turn runs from the start
of one `serve/decode_step` span to the start of the next; this is its mean
length less its decode step and its prefill chunks: `serve/poll`, the
admission block's own time, `serve/emit`, and what no span covers. The turn's
whole table goes to standard error."""
import sys

import span_reduce


def read(ctx):
    spans = span_reduce.program_spans(ctx)
    if spans is None:
        return None
    table = span_reduce.turn_table(spans)
    if table is None:
        return None
    for name, value in table.items():
        print(f"turn table: {name} {value:.4f}", file=sys.stderr)
    return table["host"]
