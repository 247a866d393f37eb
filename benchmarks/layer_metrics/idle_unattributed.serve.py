"""Device: of device 0's idle seconds in the window, the share that lies
inside none of the serve loop's spans (`span_reduce.TURN_SPANS`), the device's
times first shifted onto the host plane's clock (`span_reduce.plane_shift`).
The shift, its bounds, the idle seconds by innermost span and the five longest
idle intervals go to standard error."""
import sys

import span_reduce


def read(ctx):
    if ctx.events is None:
        return None
    spans = span_reduce.program_spans(ctx)
    if spans is None:
        return None
    shift, lower, upper = span_reduce.plane_shift(ctx, spans)
    idle = span_reduce.shifted_idle(ctx, shift)
    segments = span_reduce.innermost_segments(spans)
    total, named, by_span = span_reduce.idle_by_span(idle, spans, segments)
    if total <= 0:
        return None
    print(f"device plane shifted by {1e3 * shift:.3f} ms onto the host plane "
          f"(causality allows {1e3 * lower:.3f} to {1e3 * upper:.3f})",
          file=sys.stderr)
    for name, seconds in sorted(by_span.items(), key=lambda kv: -kv[1]):
        print(f"idle by span: {name} {seconds:.6f} s "
              f"({100.0 * seconds / total:.1f}%)", file=sys.stderr)
    for seconds, start, name in span_reduce.longest_idle(idle, segments):
        print(f"longest idle: {1e3 * seconds:.3f} ms at "
              f"{start - ctx.trace_lo:.4f} s in {name}", file=sys.stderr)
    return 100.0 * (1.0 - named / total)
