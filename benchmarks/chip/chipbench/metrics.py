"""Per-layer metric readers, found by name.

The reader of metric ``<name>[.<variant>]`` is ``metrics/<name>.py`` in
the benchmark's directory, with ``read(ctx, variant) -> float | None``.
A reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Any, Optional

from . import BENCH_DIR


@dataclasses.dataclass
class Context:
    """What a reader may read.  ``records``: one dict per request sent
    (see ``harness._Load.records``); ``window``: the measured window (host
    clock); ``traced``: the traced sub-window; ``counters``: the engine
    counters' change over the traced window; ``calls``: the jitted steps'
    arguments recorded while traced; ``trace``: the reduced device trace;
    ``block``: the configuration's block kind, whose counts a reader
    takes (``chipbench/spec.py::load_block``)."""

    cell: Any
    block: Any
    dims: dict
    records: list
    window: tuple
    load: Any
    device: Any
    trace: Any = None
    traced: Optional[tuple] = None
    counters: Optional[dict] = None
    calls: Any = None


def reader(name: str, root: Optional[Path] = None):
    base = name.split(".", 1)[0]
    path = Path(root or BENCH_DIR) / "metrics" / f"{base}.py"
    if not path.is_file():
        raise KeyError(f"no reader for metric {name!r}: {path} does not "
                       f"exist")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{base}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(ctx: Context, wanted: dict, root: Optional[Path] = None) -> dict:
    """``wanted``: metric name -> unit.  Returns the metrics line."""
    out = {}
    for name, unit in wanted.items():
        variant = name.split(".", 1)[1] if "." in name else None
        value = reader(name, root)(ctx, variant)
        if value is not None:
            out[name] = {"value": float(value), "unit": unit}
    return out
