"""Engine batching: sequences per decode program call over the traced
window (the engine's decode-token counter over the calls recorded)."""


def read(ctx, variant):
    n = len(ctx.calls.decode)
    return ctx.counters["decode_tokens"] / n if n else None
