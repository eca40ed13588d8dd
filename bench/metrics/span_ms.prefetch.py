"""Host time per decode step inside the program's ``hobbit.layer.prefetch``
span: the adaptive predictions and their prefetch submissions (all MoE
layers)."""

from program_stats import span_ms


def read(ctx):
    return span_ms(ctx, "hobbit.layer.prefetch")
