"""Host time per decode step inside the program's ``hobbit.layer.attn`` span:
the deadline clock, the attention and FFN-input dispatch, then the read of
``h`` to the host, which waits for the device (all MoE layers)."""

from program_stats import span_ms


def read(ctx):
    return span_ms(ctx, "hobbit.layer.attn")
