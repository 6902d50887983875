"""Cells, configurations, traffic mixes and block kinds, found by name as
files.

A cell ``<config>.<mix>`` is ``cells/<cell>.json``.  It names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<mix>.json``), and holds the engine settings, the phases of a
run and the limit of the correctness check.  The configuration names its
block kind (``"block"``), the module ``blocks/<kind>.py`` that knows the
model's layers.  Adding a cell, a configuration, a mix or a kind is
adding a file; no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Optional

from . import BENCH_DIR


@dataclasses.dataclass
class Cell:
    name: str
    config: dict  # configs/<config>.json
    block: Any  # the module blocks/<kind>.py the configuration names
    traffic: dict  # traffic/<mix>.json, with the cell's overrides merged
    engine: dict  # engine settings (max_len, max_running, ...)
    run: dict  # ramp_s, drain_cap_s, trace_s, trace_offset_s
    check: dict  # sample size and the limit of the comparison
    limits: dict  # latency limits a request has to meet (knee sweeps)


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Optional[Path] = None) -> Cell:
    root = Path(root or BENCH_DIR)
    path = root / "cells" / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no cell {name!r}: {path} does not exist")
    c = _load(path)
    config = load_config(c["config"], root)
    block = load_block(config, root)
    traffic = _load(root / "traffic" / f"{c['traffic']}.json")
    for key, over in c.get("traffic_params", {}).items():
        if isinstance(over, dict):
            traffic[key] = dict(traffic.get(key, {}), **over)
        else:
            traffic[key] = over
    return Cell(name=name, config=config, block=block, traffic=traffic,
                engine=dict(c["engine"]), run=dict(c["run"]),
                check=dict(c["check"]), limits=dict(c.get("limits", {})))


def load_config(name: str, root: Optional[Path] = None) -> dict:
    root = Path(root or BENCH_DIR)
    cfg = _load(root / "configs" / f"{name}.json")
    if cfg.get("name") != name:
        raise ValueError(f"configs/{name}.json names itself "
                         f"{cfg.get('name')!r}")
    return cfg


def load_block(config: dict, root: Optional[Path] = None):
    """The block kind a configuration names with its ``"block"`` key: the
    module ``blocks/<kind>.py`` under ``root``, or else under the
    benchmark's own directory.  It provides, from the configuration file
    alone, everything the harness asks of a model's layers:

    - ``dims(config)``: the sizes it computes with; every kind's hold
      ``layers``, ``vocab`` and ``norm_eps`` (of the final norm);
    - ``model_config(config)``: the program's ``ModelConfig``, refused
      where the program would run something else than the file says;
    - ``specs(dims, layer)``: name -> (shape, kind, std) of each seeded
      weight of layer ``layer`` (an int; layers may differ), or with
      ``layer`` None of the model's own: ``embed`` [vocab, d], ``ln_f``
      [d] and ``unembed`` [d, vocab].  Leading axes, such as experts',
      are part of a weight's shape;
    - ``program_layout(dims)``: the program's parameter path -> (weight
      name, layer): None for the model's own weights, else the layer it
      holds or, where the path has a leading layer axis, the first layer
      that axis covers;
    - ``layer(w, x, dims, i, block)``: the plain reference layer over one
      sequence ``x`` [S, d], float32 at ``Precision.HIGHEST``, attention
      in blocks of ``block`` query rows; ``w`` holds layer ``i``'s
      weights, so their names tell which kind of layer it is (``i`` is
      traced);
    - the counts: ``flops_per_token(dims)`` (the projections and FFN of
      every layer, for one token: the active ones where a token uses only
      some), ``attention_flops(dims, attended)``, ``logits_flops(dims)``,
      ``kv_bytes_per_position(dims)`` (one layer) and
      ``decode_attention_bytes(dims, attended)`` (the decode attention
      kernel's least bytes, one sequence, one layer).
    """
    kind = config.get("block")
    name = config.get("name")
    if not kind:
        raise KeyError(f"configs/{name}.json names no block kind: give it "
                       f"\"block\": \"<kind>\" for the file blocks/<kind>.py")
    bases = dict.fromkeys((Path(root or BENCH_DIR), BENCH_DIR))
    paths = [base / "blocks" / f"{kind}.py" for base in bases]
    path = next((p for p in paths if p.is_file()), None)
    if path is None:
        raise KeyError(f"configs/{name}.json names the block kind "
                       f"{kind!r}, and no file {' or '.join(map(str, paths))}"
                       f" exists")
    spec = importlib.util.spec_from_file_location(f"chipbench_block_{kind}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
