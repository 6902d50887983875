"""Replica service loop and middleware: device idle time that no engine
step explains, per servicer step, over the traced window.

The device's idle time outside every ``servicer.step`` span of the
program, over the number of those spans that start inside the window.
It is the idle the engine's host loop does not account for: the
replica's service loop, resolving futures, the middleware's threads, the
load generator holding the interpreter, garbage collection, or no work.
Idle is counted from the start of the first of those steps to the end
of the last step: a span already open when the profiler session began
is not in the trace, so the window's first moments would read as
outside every step.  One replica, as in ``host_step_ms``.

The spans are moved onto the device's clock first: the trace's host
clock runs ahead of its device clock (``lead``) by up to a millisecond
or more, which varies from one profiler session to the next and exceeds
the pause between two steps.  A program cannot start before
the host call that launches it, so each run of the engine's two step
programs, paired with its call, bounds the lead from below, and the
largest bound is taken.  A program without these spans, or whose step
programs cannot be paired with their calls, gives nothing to read."""
from chipbench import trace

STEP = "servicer.step"
PROGRAMS = ("paged_decode", "paged_extend")


def program_lead_ns(t, program):
    """How far the host clock runs ahead of the device clock, at least,
    by the runs of one jitted step program (``jit_<program>``) and its
    calls (``PjitFunction(<program>)``).

    Calls and runs pair in order.  A run whose call came before the
    profiler session has no call in the trace, so the pairing skips the
    fewest leading runs that leave every call less than half the
    shortest run ahead of its run: every engine step ends by waiting for
    its tokens, so a call paired with the run before its own, across
    that wait, is ahead of that run by more than the run's duration."""
    calls = []
    for e in sorted((e for e in t.host
                     if e["name"] == f"PjitFunction({program})"),
                    key=lambda e: e["start_ns"]):
        # the call is recorded twice, one event nested in the other
        if not calls or e["start_ns"] >= calls[-1][1]:
            calls.append((e["start_ns"], e["start_ns"] + e["dur_ns"]))
    runs = sorted((m["start_ns"], m["dur_ns"]) for m in t.modules
                  if m["plane"] == t.planes[0]
                  and m["name"].startswith(f"jit_{program}("))
    if not calls or not runs:
        return None
    shortest = min(d for _, d in runs)
    for skip in range(len(runs)):
        leads = [c - r for (c, _), (r, _) in zip(calls, runs[skip:])]
        if max(leads) < shortest / 2:
            return max(leads)
    return None


def lead_ns(t):
    """The host clock's lead: the larger bound of the two programs'.
    A decode call waits behind the chunks dispatched before it, so where
    every step prefills, the extend runs give the tighter bound."""
    leads = [x for x in (program_lead_ns(t, p) for p in PROGRAMS)
             if x is not None]
    return max(leads) if leads else None


def read(ctx, variant):
    t = ctx.trace
    lead = lead_ns(t)
    if lead is None:
        return None
    spans = [(e["start_ns"] - lead, e["start_ns"] + e["dur_ns"] - lead)
             for e in t.host if e["name"] == STEP]
    starts = [s for s, _ in spans if t.t0 <= s < t.t1]
    if not starts:
        return None
    lo, hi = min(starts), max(e for _, e in spans)
    steps = trace.union_ns(spans)
    idle_ns = 0.0
    i = 0
    for start, secs in sorted(t.gaps()):
        start, end = max(start, lo), min(start + secs * 1e9, hi)
        if end <= start:
            continue
        idle_ns += end - start
        # both lists are sorted and disjoint: walk them together
        while i < len(steps) and steps[i][1] <= start:
            i += 1
        j = i
        while j < len(steps) and steps[j][0] < end:
            idle_ns -= min(end, steps[j][1]) - max(start, steps[j][0])
            j += 1
    return idle_ns / len(starts) / 1e6
