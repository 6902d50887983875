"""The trace reduction: busy time as the union of device operations,
program and kernel times, idle gaps and what the host was doing in them."""
import gzip
import json
import types
from pathlib import Path

import numpy as np
import pytest

from chipbench import metrics, trace

FIXTURES = Path(__file__).resolve().parent / "fixtures"

DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


def _synthetic():
    return [
        _ev(DEV, "XLA Modules", "jit_paged_decode_step(1)", 0, 400),
        _ev(DEV, "XLA Ops", "fusion.1", 0, 100),
        _ev(DEV, "XLA Ops", "paged_decode_kernel", 50, 250),  # overlaps
        _ev(DEV, "XLA Modules", "jit_paged_extend_step(2)", 600, 300),
        _ev(DEV, "XLA Ops", "fusion.2", 600, 300),
        _ev(DEV, "XLA Ops", "fusion.3", 950, 200),  # runs past the window
        _ev(HOST, "python3", "bench.decode", 250, 400),
        _ev(HOST, "python3", "other", 0, 1000),
    ]


def test_union_busy_and_idle():
    s = trace.Summary(_synthetic(), window_s=1000e-9)
    # busy: [0, 300) + [600, 900) + [950, 1000) = 650 ns of 1000
    assert s.busy_s == pytest.approx(650e-9)
    assert s.idle_share == pytest.approx(0.35)
    assert [round(d * 1e9) for _, d in s.gaps()] == [300, 50]


def test_programs_are_told_apart_by_what_they_run():
    s = trace.Summary(_synthetic(), window_s=1000e-9)
    with_, without = s.programs("paged_decode")
    assert [m["name"] for m in with_] == ["jit_paged_decode_step(1)"]
    assert [m["name"] for m in without] == ["jit_paged_extend_step(2)"]
    assert trace.seconds(with_) == pytest.approx(4e-7)
    assert s.op_seconds("paged_decode") == \
        (pytest.approx(2.5e-7), 1)


def test_breakdown_names_the_host_span_in_each_gap():
    b = trace.Summary(_synthetic(), window_s=1000e-9).breakdown()
    assert b["device_ops"][0] == ["fusion.2", pytest.approx(3e-7)]
    assert b["idle_gaps"][0] == ["bench.decode", pytest.approx(3e-7)]
    assert b["idle_gaps"][1][0] == "outside bench spans"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_without_device_events_is_refused():
    with pytest.raises(ValueError):
        trace.Summary([_ev(HOST, "python3", "bench.decode", 0, 5)], 1e-6)


def _recorded():
    """The events that overlap the first 280 ms of a traced
    qwen1.5-0.5b.chat run on one TPU v5e."""
    with gzip.open(FIXTURES / "chat_trace.json.gz", "rt") as f:
        d = json.load(f)
    return trace.Summary(d["events"], d["window_s"])


def test_recorded_trace_busy_time_is_the_union_of_device_ops():
    s = _recorded()
    assert s.planes == ["/device:TPU:0"]
    # the union, counted again on a 100 ns grid
    grid = np.zeros(int(s.window_s * 1e7) + 1, bool)
    for e in s.ops:
        lo = int(e["start_ns"] - s.t0) // 100
        hi = -(-int(min(e["start_ns"] + e["dur_ns"], s.t1) - s.t0) // 100)
        grid[lo:hi] = True
    assert s.busy_s == pytest.approx(grid.sum() * 1e-7, abs=2e-5)
    assert 0 < s.busy_s <= s.window_s
    assert s.busy_s + sum(d for _, d in s.gaps()) == \
        pytest.approx(s.window_s, rel=1e-6)


def test_recorded_trace_programs_and_kernel():
    s = _recorded()
    ctx = types.SimpleNamespace(trace=s, counters={"prefill_tokens": 1000})
    kernel = metrics.reader("decode_step_ms").__globals__["KERNEL"]
    dec, other = s.programs(kernel)
    ext = [m for m in other if "jit__unknown" in m["name"]]
    # decode steps of about 90 ms alternate with prefill chunks
    assert len(dec) == 2 and len(ext) == 2
    assert metrics.reader("decode_step_ms")(ctx, None) == pytest.approx(
        1e3 * trace.seconds(dec) / 2)
    assert 80 < metrics.reader("decode_step_ms")(ctx, None) < 100
    assert metrics.reader("prefill_ms_per_ktok")(ctx, "batch") == \
        pytest.approx(1e6 * trace.seconds(ext) / 1000)
    kern, n = s.op_seconds(kernel)
    # an operation is named by itself, not by the operands it reads
    assert n == sum(1 for e in s.ops
                    if e["name"].startswith("%paged_decode_attention"))
    assert n > 0 and 0 < kern < trace.seconds(dec)
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert {name for name, _ in b["idle_gaps"]} <= {
        "outside bench spans", "bench.step", "bench.admit_paged",
        "bench.prefill_step_paged", "bench.decode_step_paged",
        "bench.collect_finished_paged", "bench.extend", "bench.decode"}
