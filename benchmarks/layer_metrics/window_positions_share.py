"""Engine and cache: the positions a window layer holds over the positions a
full layer holds, a layer and live slot, summed over the run's decode steps
(ServeReport.window_positions_held_sum over full_positions_held_sum): the
window's bound at work. 100 would be a window cache kept like a full one."""


def read(ctx):
    report = getattr(ctx, "report", None)
    full = getattr(report, "full_positions_held_sum", 0)
    if not full:
        return None
    return 100.0 * report.window_positions_held_sum / full
