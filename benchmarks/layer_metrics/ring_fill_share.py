"""Engine and cache: of the positions the window layers' rings have room for
in the live lanes, the share that is live, summed over the run's decode steps
(ServeReport.window_positions_held_sum over ring_positions_capacity_sum). A
decode step reads every live lane's ring whole and masks what the lane has not
written, so this is the share of what the ring's read streams for the live
lanes that it needs: 100 once every live lane is past the window. (The read is
over every lane: the dead lanes' rings are streamed besides, `slot_occupancy`
says how many.)"""


def read(ctx):
    report = getattr(ctx, "report", None)
    room = getattr(report, "ring_positions_capacity_sum", 0)
    if not room:
        return None
    return 100.0 * report.window_positions_held_sum / room
