"""Host time per decode step inside the program's ``hobbit.layer.plan`` span:
placing each routed (row, rank) in the hi or lo GEMM, overflow host staging
included (all MoE layers)."""

from program_stats import span_ms


def read(ctx):
    return span_ms(ctx, "hobbit.layer.plan")
