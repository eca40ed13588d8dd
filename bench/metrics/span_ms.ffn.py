"""Host time per decode step inside the program's ``hobbit.layer.ffn`` span:
the index transfers, the grouped FFN and residual dispatch and the layer's
bookkeeping (all MoE layers)."""

from program_stats import span_ms


def read(ctx):
    return span_ms(ctx, "hobbit.layer.ffn")
