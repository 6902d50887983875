"""A new configuration, mix, cell, block kind or per-layer metric is a new
file, found by name with no change to the harness."""
import json
import types

import pytest

from chipbench import metrics, spec


def test_new_files_are_found_by_name(tmp_path):
    for d in ("configs", "traffic", "cells", "metrics"):
        (tmp_path / d).mkdir()
    cfg = json.loads((spec.BENCH_DIR / "configs" /
                      "qwen1.5-0.5b.json").read_text())
    cfg.update(name="newmodel", num_hidden_layers=4)
    (tmp_path / "configs" / "newmodel.json").write_text(json.dumps(cfg))
    (tmp_path / "traffic" / "burst.json").write_text(json.dumps({
        "arrivals": {"kind": "gamma", "cv": 3.0},
        "prompt_tokens": {"dist": "lognormal", "median": 9, "sigma": 0.1,
                          "min": 1, "max": 99},
        "output_tokens": {"dist": "lognormal", "median": 9, "sigma": 0.1,
                          "min": 1, "max": 99}}))
    (tmp_path / "cells" / "newmodel.burst.json").write_text(json.dumps({
        "config": "newmodel", "traffic": "burst",
        "traffic_params": {"arrivals": {"rate_per_s": 7.0}},
        "engine": {"max_len": 64, "max_running": 2},
        "run": {"ramp_s": 1}, "check": {"sample": 1, "max_logit_gap": 0}}))
    (tmp_path / "metrics" / "answer.py").write_text(
        "def read(ctx, variant):\n"
        "    return 42.0 if variant is None else len(variant)\n")

    cell = spec.load_cell("newmodel.burst", tmp_path)
    assert cell.config["num_hidden_layers"] == 4
    assert cell.traffic["arrivals"] == {"kind": "gamma", "cv": 3.0,
                                        "rate_per_s": 7.0}
    assert cell.block.dims(cell.config)["layers"] == 4
    ctx = types.SimpleNamespace()
    out = metrics.read_all(ctx, {"answer": "u", "answer.batch": "u"},
                           tmp_path)
    assert out == {"answer": {"value": 42.0, "unit": "u"},
                   "answer.batch": {"value": 5.0, "unit": "u"}}


def test_every_benchmark_metric_has_a_reader():
    bench = json.loads((spec.BENCH_DIR.parents[1] /
                        "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(metrics.reader(m["name"]))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]


def test_a_config_with_tied_embeddings_is_refused(tmp_path):
    (tmp_path / "configs").mkdir()
    cfg = json.loads((spec.BENCH_DIR / "configs" /
                      "qwen1.5-0.5b.json").read_text())
    cfg.update(name="tied", tie_word_embeddings=True)
    (tmp_path / "configs" / "tied.json").write_text(json.dumps(cfg))
    cfg = spec.load_config("tied", tmp_path)
    with pytest.raises(ValueError, match="tied embeddings"):
        spec.load_block(cfg, tmp_path).model_config(cfg)


@pytest.mark.parametrize("block,missing", [
    (None, "names no block kind"),
    ("sparse", "the block kind 'sparse', and no file .*blocks/sparse.py"),
])
def test_a_config_without_a_known_block_kind_is_refused(tmp_path, block,
                                                         missing):
    for d in ("configs", "cells"):
        (tmp_path / d).mkdir()
    cfg = json.loads((spec.BENCH_DIR / "configs" /
                      "qwen1.5-0.5b.json").read_text())
    cfg.pop("block")
    cfg.update(name="nokind", **({"block": block} if block else {}))
    (tmp_path / "configs" / "nokind.json").write_text(json.dumps(cfg))
    (tmp_path / "cells" / "nokind.chat.json").write_text(json.dumps({
        "config": "nokind", "traffic": "chat", "engine": {}, "run": {},
        "check": {}}))
    with pytest.raises(KeyError, match=missing):
        spec.load_cell("nokind.chat", tmp_path)


def test_a_reader_that_finds_nothing_leaves_the_metric_out(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "nothing.py").write_text(
        "def read(ctx, variant):\n    return None\n")
    assert metrics.read_all(None, {"nothing": "%"}, tmp_path) == {}
