"""Share of the chip's bf16 peak that the decode steps' useful operations
make: per output token, what the model's forward pass needs at its cut
depth and the token's context (projections, attention, router, top_k
experts however they were served, lm_head), summed over the window's decode
tokens, over the window's seconds."""


def read(ctx):
    if ctx.peak is None:
        return None
    flops = sum(ctx.counts.token_flops(ctx.m, n)
                for c in ctx.window_calls("step") for n in c["lengths"])
    return 100.0 * flops / ctx.seconds / float(ctx.peak["bf16_flops"]) if flops else None
