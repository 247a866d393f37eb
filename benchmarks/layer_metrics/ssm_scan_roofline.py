"""Kernels: the state-space layers' chunk scan's share of its memory roofline
over the traced window. The least time is, at the HBM's peak, the bytes each
call has to move (the family's `ssm_scan_call`: the step, the convolved inputs,
B and C in, y out, A and D in, the slot's state in and out) at the mean width
of the prefill chunks dispatched inside the window (the `chunk` of each
`serve/engine.chunk_dispatch` span; a chunk's padded rows are scanned too), times
the calls; the time is the sum of the trace events named `ssm_scan_chunk_*`.

It reads the kernel as memory-bound: it says how far the kernel is from
streaming its operands, not whether the vector unit, which does the scan's
arithmetic, is the limit."""
import peaks
import span_reduce
import trace_reduce

KERNEL = r"ssm_scan_chunk_"
CHUNK_DISPATCH = "serve/engine.chunk_dispatch"


def chunk_widths_in_trace(ctx):
    """The `chunk` of each chunk-dispatch span that starts inside the traced
    window, or None where the program recorded no such span."""
    offset = span_reduce.clock_offset(ctx)
    if offset is None:
        return None
    from distributeddeeplearning_tpu.obs.trace import get_tracer

    tracer = get_tracer()
    epoch = getattr(tracer, "epoch_perf_s", None)
    if epoch is None:
        return None
    return [event["args"]["chunk"] for event in tracer.events
            if event.get("ph") == "X" and event["name"] == CHUNK_DISPATCH
            and ctx.trace_lo <= epoch + 1e-6 * event["ts"] + offset <= ctx.trace_hi]


def read(ctx):
    call = getattr(ctx.family, "ssm_scan_call", None)
    if ctx.events is None or call is None:
        return None
    seconds, calls = trace_reduce.op_seconds(
        ctx.events, ctx.trace_lo, ctx.trace_hi, KERNEL)
    widths = chunk_widths_in_trace(ctx)
    if not calls or not widths:
        return None
    rows = sum(widths) / len(widths)
    least = calls * call(ctx.cfg, rows)["bytes"] / peaks.peaks_for(
        ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / seconds
