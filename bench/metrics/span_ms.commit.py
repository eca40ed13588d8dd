"""Host time per decode step inside the program's ``hobbit.stage.commit`` span:
writing staged experts into the device pools (inside ``stage_wait``)."""

from program_stats import span_ms


def read(ctx):
    return span_ms(ctx, "hobbit.stage.commit")
