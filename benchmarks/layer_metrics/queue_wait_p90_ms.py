"""Scheduler: arrival to admission, from each CompletedRequest.queue_wait_s
(exact, per request); the percentile is the harness's."""
import traffic_gen


def read(ctx):
    waits = [r.queue_wait_s for r in getattr(ctx, "finished", {}).values()]
    if not waits:
        return None
    return 1e3 * traffic_gen.percentile(waits, 90)
