"""Scheduler: of the run's decode steps, the share dispatched while the step
before was still unread (ServeReport.decode_steps_overlapped, counted in the
loop): how often the host's turn ran beside a device step and not after it.
A program whose report has no such counter gives nothing."""


def read(ctx):
    report = getattr(ctx, "report", None)
    overlapped = getattr(report, "decode_steps_overlapped", None)
    if overlapped is None or not report.decode_steps:
        return None
    return 100.0 * overlapped / report.decode_steps
