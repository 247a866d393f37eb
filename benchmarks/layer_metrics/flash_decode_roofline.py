"""Kernels: the paged flash-decode kernel's share of its roofline over the
traced window. The least time is the larger of FLOPs over the bf16 peak and
bytes over the HBM peak for the K/V each live slot has to read (shape
functions in benchmarks/flops.py); the time is the sum of the trace events
named `flash_decode_decode_*`."""
import flops
import harness
import peaks
import trace_reduce

KERNEL = r"flash_decode_decode_"


def read(ctx):
    if ctx.events is None:
        return None
    seconds, calls = trace_reduce.op_seconds(
        ctx.events, ctx.trace_lo, ctx.trace_hi, KERNEL)
    if not calls:
        return None
    contexts = harness.decoded_contexts_in_trace(ctx)
    if not contexts:
        return None
    work = flops.flash_decode_call(ctx.cfg, contexts)
    layers = ctx.cfg["num_hidden_layers"]
    work = {k: v * layers for k, v in work.items()}
    least, _bound = flops.roofline_least_seconds(work, peaks.peaks_for(ctx.device_kind))
    return 100.0 * least / seconds
