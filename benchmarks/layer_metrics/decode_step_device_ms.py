"""Model programs: device time of the paged decode program per call, from
the `XLA Modules` line of the profiler trace."""
import trace_reduce


def read(ctx):
    if ctx.events is None:
        return None
    seconds, calls = trace_reduce.op_seconds(
        ctx.events, ctx.trace_lo, ctx.trace_hi, ctx.family.PROGRAMS["decode"],
        line="modules")
    if not calls:
        return None
    return 1e3 * seconds / calls
