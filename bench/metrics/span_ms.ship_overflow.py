"""Host time per decode step inside the program's
``hobbit.layer.ship_overflow`` span: handing the overflow buffers to the
device (all MoE layers)."""

from program_stats import span_ms


def read(ctx):
    return span_ms(ctx, "hobbit.layer.ship_overflow")
