"""Model step, whole: analytic forward+backward FLOPs per token (6 x matmul
parameters + causal attention; recomputed work not counted) x tokens per
second over the chips' bf16 peak."""
import peaks


def read(ctx):
    rate = ctx.numbers.get("train_tokens_per_s")
    if not rate:
        return None
    peak = peaks.peaks_for(ctx.device_kind)["bf16_flops"]
    per_token = ctx.family.train_token_flops(ctx.cfg, ctx.mix["seq_len"])
    return 100.0 * per_token * rate / (ctx.chips * peak)
