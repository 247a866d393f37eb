"""Kernels: the state-space layers' decode step's share of its memory roofline
over the traced window. The least time is, at the HBM's peak, the bytes the
live lanes need moved: each call (one state-space layer of one decode step)
moves for each live lane its state in and out and its step, input, B and C in
and y out (`ssm_step_lane_bytes`), with the live lanes of a call the tokens
decoded inside the window over the decode program's calls there, times the
calls; the time is the sum of the trace events named `ssm_decode_step_*`.

Only the live lanes' bytes count: a kernel that still streamed the other
lanes' state reads low."""
import harness
import peaks
import trace_reduce

KERNEL = r"ssm_decode_step_"


def ssm_step_lane_bytes(cfg) -> float:
    """Bytes one decode step of ONE state-space layer moves for one live lane,
    all in float32: its state [d_state, d_inner] in and out, its step and
    input [d_inner] and B and C [d_state] in, and y [d_inner] out."""
    di, n = cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"]
    return 4.0 * (2 * n * di + 3 * di + 2 * n)


def read(ctx):
    programs = getattr(ctx.family, "PROGRAMS", None)
    if ctx.events is None or not programs or "mamba_d_state" not in ctx.cfg:
        return None
    seconds, calls = trace_reduce.op_seconds(
        ctx.events, ctx.trace_lo, ctx.trace_hi, KERNEL)
    _, steps = trace_reduce.op_seconds(
        ctx.events, ctx.trace_lo, ctx.trace_hi, programs["decode"],
        line="modules")
    tokens = len(harness.decoded_contexts_in_trace(ctx))
    if not calls or not steps or not tokens:
        return None
    least = calls * tokens / steps * ssm_step_lane_bytes(ctx.cfg) / peaks.peaks_for(
        ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / seconds
