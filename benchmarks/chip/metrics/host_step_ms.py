"""Engine host loop: the host's own time in one servicer step, over the
traced window.

The mean, over the program's ``servicer.step`` spans that start inside
the window (one ``LLMServicer.step`` that has work), of the span's
duration less the ``engine.sample`` spans inside it (token choice and
the blocking readback, which wait for the device).  What is left is
admission, block tables, uploads, dispatch, bookkeeping and collection.
One replica: the program's threads share one host line of the trace, so
a sample belongs to the step whose interval holds it.  A program
without these spans gives nothing to read."""
import bisect

STEP, SAMPLE = "servicer.step", "engine.sample"


def read(ctx, variant):
    t = ctx.trace
    steps = [e for e in t.host
             if e["name"] == STEP and t.t0 <= e["start_ns"] < t.t1]
    if not steps:
        return None
    samples = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                     for e in t.host if e["name"] == SAMPLE)
    starts = [s for s, _ in samples]
    own_ns = 0.0
    for e in steps:
        lo, hi = e["start_ns"], e["start_ns"] + e["dur_ns"]
        own_ns += e["dur_ns"]
        for s, end in samples[bisect.bisect_left(starts, lo):
                              bisect.bisect_left(starts, hi)]:
            if end <= hi:
                own_ns -= end - s
    return own_ns / len(steps) / 1e6
