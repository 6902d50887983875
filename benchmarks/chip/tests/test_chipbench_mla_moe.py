"""The latent-attention MoE block kind (``blocks/mla_moe.py``): at toy
widths, as a configuration and cell fixture, a run through the harness
is correct and its fp8 control and a planted fault (one held expert's
output dropped) are not; the program holds every seeded weight once; the
kind refuses what the program does not run; its counts are pinned at the
Moonlight cell's widths, and the cell is in the benchmark."""
import json
import time
from pathlib import Path

import jax
import pytest

from chipbench import harness, spec, trace, weights

FIXTURES = Path(__file__).resolve().parent / "fixtures"
SEED = 2**31 + 12345


def _run(**kw):
    return harness.run_cell(spec.load_cell("toymla.tinychat", FIXTURES),
                            SEED, 1.5, False, t_start=time.perf_counter(),
                            require_chip=False, **kw)


def test_the_kind_runs_correct_and_its_control_does_not():
    r = _run(controls=("fp8",))
    assert r["attempted"] > 10 and r["failed"] == 0
    assert r["correct"] is True
    assert r["control_correct"] == {"fp8": False}
    gap, ctl = r["check"]["mean_logit_gap"], \
        r["check"]["control_fp8_mean_logit_gap"]
    assert gap["value"] <= gap["limit"] < ctl["value"]


def test_a_dropped_held_expert_makes_the_run_incorrect(monkeypatch):
    from repro.models import moe

    real = moe.held_experts

    def drop_first(x, tw, ti, up, gate, down, **kw):
        return real(x, tw, ti, up, gate, down.at[0].set(0), **kw)

    monkeypatch.setattr(moe, "held_experts", drop_first)
    r = _run()
    assert r["correct"] is False
    gap = r["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_program_holds_every_seeded_weight_once():
    from repro.models import get_model, nn

    config = spec.load_config("toymla", FIXTURES)
    block = spec.load_block(config, FIXTURES)
    cfg = block.model_config(config)
    api = get_model(cfg)
    shapes = jax.eval_shape(lambda k: nn.split(api.init(k, cfg))[0],
                            jax.random.PRNGKey(0))
    params = weights.program_params(shapes, block, block.dims(config), SEED)
    assert params["blocks"]["moe"]["up"].shape == (2, 4, 64, 32)
    assert params["blocks"]["moe"]["router"]["w"].shape == (2, 64, 16)
    paths = {"/".join(str(getattr(k, "key", k)) for k in p)
             for p, _ in jax.tree_util.tree_leaves_with_path(shapes)}
    assert paths == set(block.program_layout(block.dims(config)))


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4),
    ("topk_method", "greedy"), ("rope_scaling", {"type": "yarn"}),
    ("tie_word_embeddings", True), ("scoring_func", "softmax"),
])
def test_the_kind_refuses_what_the_program_does_not_run(key, value):
    config = dict(spec.load_config("toymla", FIXTURES), **{key: value})
    block = spec.load_block(config, FIXTURES)
    with pytest.raises(ValueError, match=key):
        block.model_config(config)


def test_the_moonlight_counts_are_pinned():
    config = spec.load_config("moonlight-16b-a3b-9l-e8")
    kind = spec.load_block(config)
    d = kind.dims(config)
    assert (d["n_experts"], d["n_held"], d["layers"]) == (64, 8, 9)
    # q 2048 x 16*192, kv_a 2048 x 576, kv_b 512 x 16*256, o 2048 x 2048
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    expert = 3 * 2048 * 1408
    # 6 of 64 experts a token, 8 held: 0.75 of an expert on average
    moe = 2048 * 64 + expert * 6 * 8 // 64 + 2 * expert
    assert kind.flops_per_token(d) == 2 * (9 * attn + 3 * 2048 * 11264
                                           + 8 * moe)
    assert kind.attention_flops(d, 100) == 2 * 9 * 100 * 16 * (192 + 128)
    assert kind.logits_flops(d) == 2 * 2048 * 163840
    assert kind.kv_bytes_per_position(d) == 1152
    assert harness.kv_block_bytes(kind, d) == 9 * 1152 * 16
    assert kind.decode_attention_bytes(d, 10) == \
        10 * 1152 + 16 * 576 * 2 + 16 * 512 * 2
    assert kind.expert_weight_bytes(d) == 8 * 2048 * 1408 * 2


def test_the_new_cell_is_in_the_benchmark():
    bench = json.loads((spec.BENCH_DIR.parents[1] /
                        "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells["moonlight-16b-a3b-9l-e8.docs"]["chips"] == 1
    moon = [m for m in bench["per_layer"] if m["name"].endswith(".moonlight")]
    assert {m["name"].split(".")[0] for m in moon} == {
        "decode_batch_mean", "decode_step_ms", "prefill_ms_per_ktok", "mfu",
        "decode_attn_roofline", "expert_ffn_roofline", "idle_share",
        "host_step_ms", "idle_outside_step_ms"}
    assert all(m["workloads"] == ["moonlight-16b-a3b-9l-e8.docs"]
               for m in moon)



def _op(name, start, dur, text="fusion(f32[8]{0} %p)"):
    return {"plane": "/device:TPU:0", "line": trace.OPS,
            "name": f"%{name} = bf16[8]{{0}} {text}", "start_ns": start,
            "dur_ns": dur}


def _gmm(name, weight, start, dur):
    return _op(name, start, dur,
               "custom-call(s32[9]{0:T(128)} %meta, bf16[128,2048]{1,0} %x, "
               f"bf16[8,2048,1408]{{2,1,0:T(8,128)(2,1)S(1)}} %{weight}), "
               "custom_call_target=\"tpu_custom_call\", operand_layout_"
               "constraints={bf16[8,2048,1408]{2,1,0}}")


def test_the_expert_ffn_roofline_reads_the_grouped_matmuls_and_their_weight_copies():
    """The reader adds to each ``gmm`` call's time that of the operation
    that copies its weight stack, in the same program run, and counts
    only runs wholly inside the traced window."""
    from types import SimpleNamespace

    from chipbench import peaks
    from metrics import expert_ffn_roofline

    mod = lambda s, d: {"plane": "/device:TPU:0", "line": trace.MODULES,  # noqa: E731
                        "name": "jit_paged_decode", "start_ns": s,
                        "dur_ns": d}
    evs = [_op("first", 0, 10),
           mod(100, 1000), _op("w.7", 100, 300), _gmm("gmm.1", "w.7", 400, 100),
           _op("other", 500, 50),
           # the same operation names in a run cut by the window's end
           mod(5000, 10**9), _op("w.7", 5000, 300),
           _gmm("gmm.1", "w.7", 5300, 100)]
    tr = trace.Summary(evs, 1e-5)  # 10,000 ns
    config = spec.load_config("moonlight-16b-a3b-9l-e8")
    kind = spec.load_block(config)
    d = kind.dims(config)
    ctx = SimpleNamespace(trace=tr, block=kind, dims=d,
                          device=SimpleNamespace(device_kind="TPU v5 lite"))
    bw = peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    want = 100 * (8 * 2048 * 1408 * 2 / bw) / 400e-9
    assert expert_ffn_roofline.read(ctx, "moonlight") == pytest.approx(want)
    ctx.block = spec.load_block(spec.load_config("qwen1.5-0.5b"))
    assert expert_ffn_roofline.read(ctx, None) is None
