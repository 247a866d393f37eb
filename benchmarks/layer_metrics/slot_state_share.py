"""Engine and cache: of the bytes the live lanes hold in the cache, the share
that is per-slot state (a convolution layer's last inputs, a window layer's
ring) and not pages of K/V, summed over the run's decode steps
(ServeReport.slot_state_bytes_held_sum over that plus kv_bytes_held_sum). The
state is held whole whatever the sequence's length, and a decode step reads
and writes all of it; the K/V grows with every token."""


def read(ctx):
    report = getattr(ctx, "report", None)
    state = getattr(report, "slot_state_bytes_held_sum", 0)
    held = state + getattr(report, "kv_bytes_held_sum", 0)
    if not state or not held:
        return None
    return 100.0 * state / held
