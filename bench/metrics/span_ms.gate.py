"""Host time per decode step inside the program's ``hobbit.layer.gate`` span:
the router dispatch and the reads of its gate values and indices (all MoE
layers)."""

from program_stats import span_ms


def read(ctx):
    return span_ms(ctx, "hobbit.layer.gate")
