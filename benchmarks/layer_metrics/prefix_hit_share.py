"""Engine and cache: share of the prompt tokens served from shared prefix
pages (ServeReport.prefix_hit_rate, a program counter)."""


def read(ctx):
    report = getattr(ctx, "report", None)
    if report is None or not report.prompt_tokens:
        return None
    return 100.0 * report.prefix_hit_rate
