"""Model step: device time of the chunked-prefill (extend) program per
thousand prompt tokens it prefilled, over the traced window.  The extend
program is the engine's step program that runs no decode kernel: its
jitted function is anonymous today (``jit__unknown``), and
``paged_extend`` once the steps carry stable names."""
from chipbench import trace

KERNEL = "paged_decode"
EXTEND_PROGRAM = ("jit__unknown", "paged_extend")


def read(ctx, variant):
    _, other = ctx.trace.programs(KERNEL)
    runs = [m for m in other if any(n in m["name"] for n in EXTEND_PROGRAM)]
    tokens = ctx.counters["prefill_tokens"]
    return 1e6 * trace.seconds(runs) / tokens if runs and tokens else None
