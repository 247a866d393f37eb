"""Device: 1 - the union of the device-op intervals over the traced window."""
import trace_reduce


def read(ctx):
    if ctx.events is None:
        return None
    return trace_reduce.idle_share_pct(ctx.events, ctx.trace_lo, ctx.trace_hi)
