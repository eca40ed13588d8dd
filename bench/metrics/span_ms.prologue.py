"""Host time per decode step inside the program's ``hobbit.decode.prologue``
span: growing each live row's page chain, reading the positions to the host
and exporting the page table."""

from program_stats import span_ms


def read(ctx):
    return span_ms(ctx, "hobbit.decode.prologue")
