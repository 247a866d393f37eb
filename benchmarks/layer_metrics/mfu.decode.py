"""Model step, whole: the paged decode program's share of the chip's bf16
peak over the traced window: forward FLOPs of the tokens it decoded (2 x
matmul parameters + attention over each token's live context) over the
program's device time (`XLA Modules` events) and the peak. A later change that
takes the flash-decode kernel off the path leaves its roofline silent; this
share still bounds the claim."""
import harness
import peaks
import trace_reduce


def read(ctx):
    if ctx.events is None:
        return None
    seconds, calls = trace_reduce.op_seconds(
        ctx.events, ctx.trace_lo, ctx.trace_hi, ctx.family.PROGRAMS["decode"],
        line="modules")
    if not calls:
        return None
    work = sum(ctx.family.serve_token_flops(ctx.cfg, c)
               for c in harness.decoded_contexts_in_trace(ctx))
    if not work:
        return None
    peak = peaks.peaks_for(ctx.device_kind)["bf16_flops"]
    return 100.0 * work / seconds / (ctx.chips * peak)
