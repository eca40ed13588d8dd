"""Host time per decode step inside the program's ``hobbit.decode.logits``
span: the positions update, the logits and their read to the host."""

from program_stats import span_ms


def read(ctx):
    return span_ms(ctx, "hobbit.decode.logits")
