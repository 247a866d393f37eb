"""Expert layer: of the (token, expert) pairs the routers made over the run's
decode steps, the share that landed on an expert this chip holds
(ServeReport.expert_pairs_here over expert_pairs_total). It says that the
share is the stated one (held / published experts) and not a score."""


def read(ctx):
    report = getattr(ctx, "report", None)
    total = getattr(report, "expert_pairs_total", 0)
    if not total:
        return None
    return 100.0 * report.expert_pairs_here / total
