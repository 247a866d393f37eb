"""Expert layer: the mean tokens a held expert gets in a decode step, averaged
over the step's expert layers and the run's decode steps
(ServeReport.expert_tokens_mean_sum over decode_steps)."""


def read(ctx):
    report = getattr(ctx, "report", None)
    steps = getattr(report, "decode_steps", 0)
    if not steps or not getattr(report, "expert_pairs_total", 0):
        return None
    return report.expert_tokens_mean_sum / steps
