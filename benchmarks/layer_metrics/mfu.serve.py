"""Model step, whole: 2 x matmul parameters x (prompt tokens computed +
tokens generated in the window) over the window and the chips' bf16 peak.
Attention over the live context is left out, so this is a floor."""
import peaks


def read(ctx):
    if not getattr(ctx, "token_times", None):
        return None
    seconds = ctx.seconds
    generated = sum(1 for t in ctx.token_times.values() for x in t if x <= seconds)
    # prompts whose first token landed in the window, less what the prefix
    # cache served (the engine's aggregate hit share)
    stats = ctx.engine_stats
    computed_share = 1.0 - stats["prefix_hit_tokens"] / max(stats["prompt_tokens_seen"], 1)
    prompts = sum(len(i.prompt) for i in ctx.schedule
                  if ctx.token_times[i.uid] and ctx.token_times[i.uid][0] <= seconds)
    tokens = generated + prompts * computed_share
    if not tokens:
        return None
    peak = peaks.peaks_for(ctx.device_kind)["bf16_flops"]
    return (100.0 * 2.0 * ctx.family.matmul_params(ctx.cfg) * tokens / seconds
            / (ctx.chips * peak))
