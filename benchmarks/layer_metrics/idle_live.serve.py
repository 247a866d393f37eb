"""Device: device 0's idle time outside `serve/idle` spans, over the window
less their time (`step_spans.idle_split`): what the device idles while the
loop holds a request. The step-paired shift and its bounds, this idle time by
innermost span and its five longest gaps go to standard error."""
import sys

import span_reduce
import step_spans


def read(ctx):
    if ctx.events is None:
        return None
    spans = step_spans.window_spans(ctx)
    if spans is None:
        return None
    split = step_spans.idle_split(ctx, spans)
    held = split["window"] - split["idle"]
    if held <= 0:
        return None
    live = split["live"]
    total = sum(b - a for a, b in live)
    if split["shift"] is None:
        print("step-paired shift: no decode step with both spans and a "
              "program; none applied", file=sys.stderr)
    else:
        shift, lower, upper, pairs = split["shift"]
        print(f"step-paired shift {1e3 * shift:.3f} ms (causality allows "
              f"{1e3 * lower:.3f} to {1e3 * upper:.3f}, {pairs} steps)",
              file=sys.stderr)
    print(f"idle live: {total:.6f} s of {held:.6f} s with a request held; "
          f"no request: {split['no_request']:.6f} s idle in "
          f"{split['idle']:.6f} s", file=sys.stderr)
    segments = span_reduce.innermost_segments(split["moved"])
    by_span = span_reduce.overlap_s(live, segments)
    by_span["no span"] = max(total - sum(by_span.values()), 0.0)
    for name, seconds in sorted(by_span.items(), key=lambda kv: -kv[1]):
        print(f"idle live by span: {name} {seconds:.6f} s "
              f"({100.0 * seconds / total if total else 0.0:.1f}%)",
              file=sys.stderr)
    for seconds, start, name in span_reduce.longest_idle(live, segments):
        print(f"longest live idle: {1e3 * seconds:.3f} ms at "
              f"{start - ctx.trace_lo:.4f} s in {name}", file=sys.stderr)
    return 100.0 * total / held
