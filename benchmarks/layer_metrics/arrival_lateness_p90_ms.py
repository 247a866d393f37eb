"""Load generator: how late the harness handed each request over, release
time minus due time, 90th percentile over all requests of the window."""
import traffic_gen


def read(ctx):
    if not getattr(ctx, "released", None):
        return None
    late = [ctx.released[i.uid] - i.due_s for i in ctx.schedule if i.uid in ctx.released]
    return 1e3 * traffic_gen.percentile(late, 90)
