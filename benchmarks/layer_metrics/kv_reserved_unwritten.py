"""Engine and cache: of the pool pages that requests hold, the share in which
nothing is written yet, averaged over decode steps
(ServeReport.kv_pages_written_sum over kv_pages_reserved_sum)."""


def read(ctx):
    report = getattr(ctx, "report", None)
    reserved = getattr(report, "kv_pages_reserved_sum", 0)
    if not reserved:
        return None
    return 100.0 * (1.0 - report.kv_pages_written_sum / reserved)
