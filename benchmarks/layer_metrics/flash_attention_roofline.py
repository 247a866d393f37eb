"""Kernels: the flash-attention kernels' share of their roofline over the
traced window: trace events `flash_attention_fwd`, `_bwd_dq`, `_bwd_dkv`,
each kernel's time against the least time for as many calls of it as the
window holds (shape functions in benchmarks/flops.py), summed, per chip. A
window that cuts a step between its forward and its backward counts each
kernel's own calls, not whole steps."""
import flops
import peaks
import trace_reduce


def read(ctx):
    if ctx.events is None:
        return None
    peak = peaks.peaks_for(ctx.device_kind)
    least = seconds = 0.0
    for kernel in flops.FLASH_ATTENTION_KERNELS:
        # a call is one layer of one step on this chip
        spent, calls = trace_reduce.op_seconds(
            ctx.events, ctx.trace_lo, ctx.trace_hi, "flash_attention_" + kernel)
        work = flops.flash_attention_call(
            ctx.cfg, ctx.rows // ctx.chips, ctx.mix["seq_len"], kernel)
        least += calls * flops.roofline_least_seconds(work, peak)[0]
        seconds += spent
    if not seconds:
        return None
    return 100.0 * least / seconds
