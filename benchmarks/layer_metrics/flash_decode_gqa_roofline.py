"""Kernels: the grouped-query paged decode kernel's share of its roofline over
the traced window. The least time is the larger of FLOPs over the bf16 peak
and bytes over the HBM peak for the K/V each live slot has to read in every
full-attention layer (the family's `gqa_decode_call`); the time is the sum of
the trace events named `flash_decode_decode_gqa_*`."""
import flops
import harness
import peaks
import trace_reduce

KERNEL = r"flash_decode_decode_gqa_"


def read(ctx):
    call = getattr(ctx.family, "gqa_decode_call", None)
    if ctx.events is None or call is None:
        return None
    seconds, calls = trace_reduce.op_seconds(
        ctx.events, ctx.trace_lo, ctx.trace_hi, KERNEL)
    contexts = harness.decoded_contexts_in_trace(ctx)
    if not calls or not contexts:
        return None
    layers = ctx.family.full_layers(ctx.cfg)
    work = {k: v * layers for k, v in call(ctx.cfg, contexts).items()}
    least, _bound = flops.roofline_least_seconds(work, peaks.peaks_for(ctx.device_kind))
    return 100.0 * least / seconds
