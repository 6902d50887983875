"""The readers of the program's own spans: the engine's host time per
servicer step and the device idle time outside every step (with the
host clock's lead over the device clock that places the spans), on
synthetic events and on a trace recorded on the chip."""
import gzip
import json
import types
from pathlib import Path

import pytest

from chipbench import metrics, trace

FIXTURES = Path(__file__).resolve().parent / "fixtures"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


def _summary(host, lead=0):
    # device busy [0, 100) and [400, 700) of a 1000-ns window:
    # idle [100, 400) and [700, 1000); the second is a decode program
    # run whose call the host clock places ``lead`` ns later
    dev = [_ev(DEV, "XLA Ops", "fusion.1", 0, 100),
           _ev(DEV, "XLA Ops", "fusion.2", 400, 300),
           _ev(DEV, "XLA Modules", "jit_paged_decode(1)", 400, 300)]
    call = [("PjitFunction(paged_decode)", 400 + lead, 20)]
    return trace.Summary(dev + [_ev(HOST, "python3", *h)
                                for h in host + call], window_s=1000e-9)


SPANS = [
    ("servicer.step", 50, 450),      # [50, 500)
    ("engine.prepare", 60, 40),
    ("engine.sample", 300, 150),     # waits for the device: not host work
    ("servicer.step", 600, 200),     # [600, 800)
    ("engine.sample", 700, 90),
    ("engine.sample", 780, 40),      # runs past its step's end: not held
    ("service.resolve", 800, 150),
    ("servicer.step", 1200, 100),    # starts after the window
    ("engine.sample", 1210, 40),
]


def _read(name, host, lead=0):
    ctx = types.SimpleNamespace(trace=_summary(host, lead))
    return metrics.reader(name)(ctx, None), \
        metrics.reader(name)(ctx, "batch")


def _lead_ns(summary):
    return metrics.reader("idle_outside_step_ms").__globals__["lead_ns"](
        summary)


def test_host_step_is_the_step_less_the_samples_it_holds():
    # (450 - 150 + 200 - 90) / 2 steps that start in the window
    assert _read("host_step_ms", SPANS) == (pytest.approx(205e-6),
                                            pytest.approx(205e-6))


def test_idle_outside_steps_is_the_idle_no_step_covers():
    # [100, 400) lies inside the first step; of [700, 1000) the second
    # step covers [700, 800): 200 ns over 2 steps
    assert _read("idle_outside_step_ms", SPANS) == (pytest.approx(1e-4),
                                                    pytest.approx(1e-4))


def test_idle_outside_steps_counts_every_step_once():
    # overlapping spans (two threads) cover an interval once
    spans = SPANS + [("servicer.step", 650, 400)]  # [650, 1050)
    value, _ = _read("idle_outside_step_ms", spans)
    assert value == pytest.approx(0.0)


def test_idle_before_the_first_step_or_after_the_last_is_not_counted():
    # a step already running when the trace began left no span: of the
    # idle [100, 400) only [300, 400) lies after the first step's start
    # and outside it; [700, 1000) lies after the last step's end
    spans = [("servicer.step", 200, 100), ("servicer.step", 600, 50)]
    assert _read("idle_outside_step_ms", spans) == (pytest.approx(5e-5),
                                                    pytest.approx(5e-5))


def test_idle_outside_steps_reads_the_spans_on_the_device_clock():
    # every host event 100 ns late: the same reading as on one clock
    # (read on the host's clock, the first step would hide all of the
    # idle [100, 400) and the second leave 100 ns of [700, 1000))
    late = [(n, s + 100, d) for n, s, d in SPANS]
    assert _read("idle_outside_step_ms", late, lead=100) == (
        pytest.approx(1e-4), pytest.approx(1e-4))


def test_the_host_clock_lead_pairs_calls_with_runs_in_order():
    runs = [("jit_paged_decode(1)", 0, 100), ("jit__argmax(2)", 100, 1),
            ("jit_paged_decode(1)", 300, 100),
            ("jit_paged_decode(1)", 600, 100),
            ("jit_paged_extend(3)", 750, 100)]
    # the first run's call came before the session; each call is
    # recorded twice, nested; a later call's run lies past the window
    calls = [("paged_decode", 340, 20), ("paged_decode", 341, 18),
             ("paged_decode", 610, 20), ("paged_decode", 611, 18),
             ("paged_decode", 950, 20), ("paged_extend", 795, 20)]
    s = trace.Summary(
        [_ev(DEV, "XLA Modules", n, a, d) for n, a, d in runs]
        + [_ev(DEV, "XLA Ops", "fusion.1", a, d) for _, a, d in runs]
        + [_ev(HOST, "python3", f"PjitFunction({n})", a, d)
           for n, a, d in calls], window_s=900e-9)
    lead = metrics.reader("idle_outside_step_ms").__globals__[
        "program_lead_ns"]
    assert lead(s, "paged_decode") == 40  # max(340 - 300, 610 - 600)
    assert lead(s, "paged_extend") == 45
    assert _lead_ns(s) == 45  # the tighter bound


@pytest.mark.parametrize("name", ["host_step_ms", "idle_outside_step_ms"])
def test_a_program_without_the_spans_gives_nothing(name):
    # the benchmark's own wrapper spans are not the program's
    spans = [("bench.step", 50, 450), ("bench.decode", 300, 150)]
    assert _read(name, spans) == (None, None)


def _recorded():
    """The events that overlap the first 280 ms of a traced
    qwen1.5-0.5b.chat run on one TPU v5e, with the program's spans
    (``tracedump.py --fixture``)."""
    with gzip.open(FIXTURES / "chat_trace_spans.json.gz", "rt") as f:
        d = json.load(f)
    return trace.Summary(d["events"], d["window_s"])


def test_recorded_trace_with_the_programs_spans():
    s = _recorded()
    ctx = types.SimpleNamespace(trace=s, counters={"prefill_tokens": 1000})
    names = [m["name"] for m in s.modules]
    assert not [n for n in names if "jit__unknown" in n]
    ext = [m for m in s.modules if "paged_extend" in m["name"]]
    dec, _ = s.programs("paged_decode")
    # a decode step, two prefill chunks of one step, the next decode step
    assert len(ext) == 2 and len(dec) == 2
    assert all("jit_paged_decode" in m["name"] for m in dec)
    assert metrics.reader("prefill_ms_per_ktok")(ctx, None) == \
        pytest.approx(1e6 * trace.seconds(ext) / 1000)
    assert 50 < metrics.reader("decode_step_ms")(ctx, None) < 100

    steps = [e for e in s.host if e["name"] == "servicer.step"
             and s.t0 <= e["start_ns"] < s.t1]
    assert len(steps) == 1
    (step,) = steps
    waited = sum(e["dur_ns"] for e in s.host if e["name"] == "engine.sample"
                 and step["start_ns"] <= e["start_ns"]
                 and e["start_ns"] + e["dur_ns"]
                 <= step["start_ns"] + step["dur_ns"])
    host = metrics.reader("host_step_ms")(ctx, None)
    assert host == pytest.approx((step["dur_ns"] - waited) / 1e6)
    assert 0 < host < 20  # uploads and dispatch of three calls
    idle = metrics.reader("idle_outside_step_ms")(ctx, None)
    assert 0 <= idle <= (s.window_s - s.busy_s) * 1e3


def test_recorded_trace_places_the_host_spans_on_the_device_clock():
    """The trace opens inside a decode run whose call came before the
    session; the one decode call recorded pairs with the next run, which
    the device clock puts 1.15 ms before the call.  The first of the two
    chunks, on an idle device, puts its run 1.28 ms before its call."""
    s = _recorded()

    def starts(names, name):
        return sorted(e["start_ns"] for e in names
                      if e["name"].startswith(name))

    dec = starts(s.modules, "jit_paged_decode(")
    ext = starts(s.modules, "jit_paged_extend(")
    dec_calls = starts(s.host, "PjitFunction(paged_decode)")[::2]
    ext_calls = starts(s.host, "PjitFunction(paged_extend)")[::2]
    assert len(dec) == 2 and dec[0] == s.t0 and len(dec_calls) == 1
    assert len(ext) == len(ext_calls) == 2
    lead = metrics.reader("idle_outside_step_ms").__globals__[
        "program_lead_ns"]
    assert lead(s, "paged_decode") == dec_calls[0] - dec[1]
    assert lead(s, "paged_extend") == ext_calls[0] - ext[0]
    assert _lead_ns(s) == ext_calls[0] - ext[0]
    assert 1.2e6 < _lead_ns(s) < 1.3e6
