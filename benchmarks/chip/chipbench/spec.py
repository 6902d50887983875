"""Cells, configurations and traffic mixes, found by name as data files.

A cell ``<config>.<mix>`` is ``cells/<cell>.json``.  It names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<mix>.json``), and holds the engine settings, the phases of a
run and the limit of the correctness check.  Adding a cell, a
configuration or a mix is adding a file; no code here changes.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Optional

from . import BENCH_DIR

# published config key -> the program's ModelConfig field
_WIDTH_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}


@dataclasses.dataclass
class Cell:
    name: str
    config: dict  # configs/<config>.json
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
    traffic = _load(root / "traffic" / f"{c['traffic']}.json")
    for key, over in c.get("traffic_params", {}).items():
        if isinstance(over, dict):
            traffic[key] = dict(traffic.get(key, {}), **over)
        else:
            traffic[key] = over
    return Cell(name=name, config=config, traffic=traffic,
                engine=dict(c["engine"]), run=dict(c["run"]),
                check=dict(c["check"]), limits=dict(c.get("limits", {})))


def load_config(name: str, root: Optional[Path] = None) -> dict:
    root = Path(root or BENCH_DIR)
    cfg = _load(root / "configs" / f"{name}.json")
    if cfg.get("name") != name:
        raise ValueError(f"configs/{name}.json names itself "
                         f"{cfg.get('name')!r}")
    return cfg


def dims(config: dict) -> dict:
    """The sizes the yardstick computes with (FLOPs, bytes, the plain
    reference, the weights), from the configuration file alone."""
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    return {
        "layers": config["num_hidden_layers"],
        "d_model": d,
        "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim", d // heads),
        "d_ff": config["intermediate_size"],
        "vocab": config["vocab_size"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "qkv_bias": bool(config["qkv_bias"]),
        "qk_norm": bool(config["qk_norm"]),
    }


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: the repo
    arch named by ``arch``, with every size set from the file.  Fails when
    the program's config would run something else than the file says."""
    from repro.configs import get_config

    if config["tie_word_embeddings"]:
        raise ValueError(f"{config['name']}: tied embeddings; the "
                         f"benchmark's seeded weights and plain reference "
                         f"keep a separate output head")
    dm = dims(config)
    over = {field: config[key] for key, field in _WIDTH_KEYS.items()}
    over.update(head_dim=dm["head_dim"], qkv_bias=dm["qkv_bias"],
                qk_norm=dm["qk_norm"], tie_embeddings=False)
    cfg = get_config(config["arch"], **over)
    if (cfg.family != "dense" or not cfg.gated_mlp
            or cfg.activation != "silu" or cfg.positions != "rope"
            or cfg.param_dtype != config["torch_dtype"]
            or cfg.compute_dtype != config["torch_dtype"]):
        raise ValueError(f"{config['name']}: the program's {config['arch']} "
                         f"config is not a bf16 gated-silu rope decoder")
    return cfg
