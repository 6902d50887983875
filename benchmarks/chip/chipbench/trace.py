"""Reduction of a profiler trace to device busy time, program and kernel
times, and idle gaps.

``events(path)`` reads an ``.xplane.pb`` into plain event dicts
(``plane``, ``line``, ``name``, ``start_ns``, ``dur_ns``); ``Summary``
reduces those.  Device planes are ``/device:TPU:<n>``: their ``XLA Ops``
line holds one event per operation run, their ``XLA Modules`` line one
per program run.  Host threads are on ``/host:CPU``; the benchmark's own
spans there are named ``bench.*``.
"""
from __future__ import annotations

import bisect
import dataclasses

DEVICE_PREFIX = "/device:TPU:"
OPS, MODULES = "XLA Ops", "XLA Modules"
HOST = "/host:CPU"


def events(path: str) -> list:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not (plane.name.startswith(DEVICE_PREFIX) or plane.name == HOST):
            continue
        for line in plane.lines:
            if plane.name == HOST or line.name in (OPS, MODULES):
                for e in line.events:
                    out.append({"plane": plane.name, "line": line.name,
                                "name": e.name, "start_ns": e.start_ns,
                                "dur_ns": e.duration_ns})
    return out


def op_name(event_name: str) -> str:
    """An operation's own name: a TPU trace names each operation by its
    whole HLO instruction (``%name.4 = type op(%operand, ...)``), whose
    operands may name other operations."""
    return event_name.split(" = ", 1)[0]


def seconds(evs) -> float:
    """Summed duration of events."""
    return sum(e["dur_ns"] for e in evs) / 1e9


def union_ns(intervals) -> list:
    """Merge [start, end) intervals into disjoint sorted ones."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


@dataclasses.dataclass
class Summary:
    """One device's view of the traced window.  ``window_s`` is the
    length of the traced window; busy time is the union of the device's
    operations inside it, averaged over the devices that ran any."""

    evs: list
    window_s: float

    def __post_init__(self):
        dev = [e for e in self.evs if e["plane"].startswith(DEVICE_PREFIX)]
        self.planes = sorted({e["plane"] for e in dev})
        if not self.planes:
            raise ValueError("the trace holds no device events")
        ops = [e for e in dev if e["line"] == OPS]
        self.t0 = min(e["start_ns"] for e in dev)
        self.t1 = self.t0 + int(self.window_s * 1e9)
        self.ops = [e for e in ops if e["start_ns"] < self.t1]
        self.modules = [e for e in dev if e["line"] == MODULES
                        and e["start_ns"] < self.t1]
        self.host = [e for e in self.evs if e["plane"] == HOST]
        busy = {}
        for p in self.planes:
            iv = union_ns((e["start_ns"], min(e["start_ns"] + e["dur_ns"],
                                              self.t1))
                          for e in self.ops if e["plane"] == p)
            busy[p] = iv
        self.busy_iv = busy[self.planes[0]]
        self.busy_s = sum(sum(e - s for s, e in iv)
                          for iv in busy.values()) / len(busy) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def programs(self, op_part: str) -> tuple:
        """The program runs of the first device, split by whether an
        operation whose name holds ``op_part`` ran inside them: (with,
        without).  Programs are told apart by what they run, since the
        engine's jitted steps carry no name of their own."""
        ops = sorted((e["start_ns"], op_part in op_name(e["name"]))
                     for e in self.ops if e["plane"] == self.planes[0])
        starts = [s for s, _ in ops]
        with_, without = [], []
        for m in self.modules:
            if m["plane"] != self.planes[0]:
                continue
            lo = bisect.bisect_left(starts, m["start_ns"])
            hi = bisect.bisect_left(starts, m["start_ns"] + m["dur_ns"])
            (with_ if any(h for _, h in ops[lo:hi]) else without).append(m)
        return with_, without

    def op_seconds(self, part: str) -> tuple:
        """(device seconds, runs) of the operations of the first device
        whose own name holds ``part``."""
        ev = [e for e in self.ops if part in op_name(e["name"])
              and e["plane"] == self.planes[0]]
        return seconds(ev), len(ev)

    def gaps(self) -> list:
        """Idle gaps of the first device inside the window: (start_ns,
        seconds), longest first."""
        out = []
        prev = self.t0
        for s, e in self.busy_iv + [[self.t1, self.t1]]:
            if s > prev:
                out.append((prev, (s - prev) / 1e9))
            prev = max(prev, e)
        return sorted(out, key=lambda g: -g[1])

    def host_span_at(self, t_ns: int) -> str:
        """The innermost ``bench.*`` host span running at ``t_ns``."""
        best = None
        for e in self.host:
            if (e["name"].startswith("bench.")
                    and e["start_ns"] <= t_ns < e["start_ns"] + e["dur_ns"]):
                if best is None or e["dur_ns"] < best["dur_ns"]:
                    best = e
        return best["name"] if best else "outside bench spans"

    def breakdown(self, n: int = 10) -> dict:
        by_op: dict = {}
        for e in self.ops:
            if e["plane"] == self.planes[0]:
                by_op[e["name"]] = by_op.get(e["name"], 0) + e["dur_ns"]
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:n]
        gaps = self.gaps()[:n]
        return {"device_ops": [[k, v / 1e9] for k, v in top],
                "idle_gaps": [[self.host_span_at(s + int(d * 5e8)), d]
                              for s, d in gaps]}


def reduce(path: str, window_s: float) -> Summary:
    return Summary(events(path), window_s)
