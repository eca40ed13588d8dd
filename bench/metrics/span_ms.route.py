"""Host time per decode step inside the program's ``hobbit.layer.route`` span:
fleet-heat observation, scoring pending predictions and on-demand scoring
(all MoE layers)."""

from program_stats import span_ms


def read(ctx):
    return span_ms(ctx, "hobbit.layer.route")
