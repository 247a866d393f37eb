"""Scheduler: of the loop turns whose admission block began with a request
queued, the share that ended with the head refused for pages
(ServeReport.admission_turns, counted where the decision is taken)."""
import sys


def read(ctx):
    turns = getattr(getattr(ctx, "report", None), "admission_turns", None)
    if not turns or not turns.get("queued"):
        return None
    print(f"admission turns: {dict(turns)}", file=sys.stderr)
    return 100.0 * turns["blocked_pages"] / turns["queued"]
