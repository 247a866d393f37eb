"""Model step, whole: the decode program's share of its memory roofline over
the traced window. The least time is, at the HBM's peak, the bytes its calls
have to read: every weight outside the experts once a call, the weights of the
experts a step touched (the mean `experts_touched_sum` of the steps whose
`serve/engine.step_counts` event lies inside the traced window) and the live
K/V of both kinds of layer for every token decoded in the trace (the family's
`decode_step_bytes`); the time is the program's device time (`XLA Modules`
events named by `PROGRAMS["decode"]`). Every term is the traced window's own."""
import harness
import peaks
import span_reduce
import trace_reduce

STEP_COUNTS = "serve/engine.step_counts"


def touched_in_trace(ctx):
    """`experts_touched_sum` of each decode step the program counted inside
    the traced window, or None where it recorded no such event."""
    offset = span_reduce.clock_offset(ctx)
    if offset is None:
        return None
    from distributeddeeplearning_tpu.obs.trace import get_tracer

    tracer = get_tracer()
    epoch = getattr(tracer, "epoch_perf_s", None)
    if epoch is None:
        return None
    return [event["args"]["experts_touched_sum"] for event in tracer.events
            if event.get("ph") == "i" and event["name"] == STEP_COUNTS
            and ctx.trace_lo <= epoch + 1e-6 * event["ts"] + offset <= ctx.trace_hi]


def read(ctx):
    step_bytes = getattr(ctx.family, "decode_step_bytes", None)
    if ctx.events is None or step_bytes is None:
        return None
    seconds, calls = trace_reduce.op_seconds(
        ctx.events, ctx.trace_lo, ctx.trace_hi, ctx.family.PROGRAMS["decode"],
        line="modules")
    contexts = harness.decoded_contexts_in_trace(ctx)
    touched = touched_in_trace(ctx)
    if not calls or not contexts or not touched:
        return None
    a_call = step_bytes(ctx.cfg, [], sum(touched) / len(touched))
    live_kv = step_bytes(ctx.cfg, contexts, 0) - step_bytes(ctx.cfg, [], 0)
    least = (calls * a_call + live_kv) / peaks.peaks_for(
        ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / seconds
