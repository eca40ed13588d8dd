"""Host time per decode step inside the program's ``hobbit.layer.stage_wait``
span: the staging barrier and the on-demand drain, commits included (all MoE
layers)."""

from program_stats import span_ms


def read(ctx):
    return span_ms(ctx, "hobbit.layer.stage_wait")
