"""Model step: mean device time of one run of the paged decode program
(the program that runs the paged decode kernel)."""
from chipbench import trace

KERNEL = "paged_decode"


def read(ctx, variant):
    runs, _ = ctx.trace.programs(KERNEL)
    return trace.seconds(runs) * 1e3 / len(runs) if runs else None
