"""Device: share of the traced window in which no operation ran."""


def read(ctx, variant):
    return 100.0 * ctx.trace.idle_share
