"""Scheduler: mean share of the decode slots in use per decode step
(ServeReport.slot_occupancy_mean, a program counter)."""


def read(ctx):
    report = getattr(ctx, "report", None)
    if report is None or not report.decode_steps:
        return None
    return 100.0 * report.slot_occupancy_mean
