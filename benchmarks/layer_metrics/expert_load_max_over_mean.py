"""Expert layer: the fullest held expert's tokens over the mean held expert's,
over the run's decode steps (ServeReport.expert_tokens_max_sum over
expert_tokens_mean_sum): 1 is an even deal."""


def read(ctx):
    report = getattr(ctx, "report", None)
    mean = getattr(report, "expert_tokens_mean_sum", 0)
    if not mean:
        return None
    return report.expert_tokens_max_sum / mean
