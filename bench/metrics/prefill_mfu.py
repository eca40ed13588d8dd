"""Share of the chip's bf16 peak that the prefill's useful operations make:
per prompt token the top_k routed experts only (not the capacity buffers
the dense dispatch computes), attention at the token's causal context, the
lm_head once per prompt, summed over the window's prefill chunks, over the
window's seconds."""


def read(ctx):
    if ctx.peak is None:
        return None
    flops = sum(ctx.counts.prompt_flops(ctx.m, p, a, b)
                for c in ctx.window_calls("join_step") for p, a, b in c["chunks"])
    return 100.0 * flops / ctx.seconds / float(ctx.peak["bf16_flops"]) if flops else None
