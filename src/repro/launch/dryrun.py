import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh, the abstract state/batch/cache
specs, jits the appropriate step (train_step / prefill / decode) with explicit
shardings, and runs ``.lower().compile()``.  It then extracts:

  * ``compiled.memory_analysis()``  — proves the cell fits per-device HBM,
  * ``compiled.cost_analysis()``    — HLO FLOPs / bytes for the roofline,
  * collective operand bytes parsed from the compiled HLO text
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute; all-reduce weighted 2x for its ring cost),

and writes a JSON record consumed by ``benchmarks/bench_roofline.py`` and
EXPERIMENTS.md.  Compile succeeding for the 16x16 AND 2x16x16 meshes for every
supported cell is the multi-pod runnability deliverable.
"""
import argparse
import functools
import json
import math
import re
import sys
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import ShapeDtypeStruct as SDS
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.launch import sharding as shd
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh
from repro.models import get_model
from repro.training.optim import OptimizerConfig
from repro.training.train import TrainConfig, make_train_step

# Hardware constants (TPU v5e-class target)
PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
ICI_BW = 50e9  # bytes/s / link

# Per-arch microbatch counts for train_4k (keep activations ~O(1 sample))
MICROBATCHES = {
    "nemotron-4-340b": 16,
    "qwen3-8b": 4,
    "llama3.2-3b": 4,
    "zamba2-2.7b": 4,
    "moonlight-16b-a3b": 4,
    "deepseek-moe-16b": 4,
    "rwkv6-1.6b": 4,
}
DEFAULT_MICRO = 2

# Archs whose optimizer state only fits with 8-bit moments
QUANTIZED_OPT = {"nemotron-4-340b"}


def arch_overrides(arch: str, shape: str, extra: Optional[dict] = None) -> dict:
    over = dict(extra or {})
    return over


# ---------------------------------------------------------------------------
# Collective-bytes extraction from compiled HLO
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")

_COLLECTIVES = {
    "all-gather": 1.0,
    "all-reduce": 2.0,  # ring: reduce-scatter + all-gather
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, Any]:
    """Sum output-shape bytes of collective ops (per-device program)."""
    per_op: dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    counts: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        for op, weight in _COLLECTIVES.items():
            # match "all-reduce(", "all-reduce-start(" but not "-done("
            if f" {op}(" in s or f" {op}-start(" in s:
                lhs = s.split("=", 1)[1]
                opname_idx = lhs.find(op)
                shape_part = lhs[:opname_idx]
                b = _shape_bytes(shape_part)
                per_op[op] += b * weight
                counts[op] += 1
                break
    total = sum(per_op.values())
    return {"total_bytes": total, "per_op_bytes": per_op, "counts": counts}


# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------


def build_cell(arch: str, shape: str, mesh, *, overrides: Optional[dict] = None):
    """Returns (jitted_fn, example_args) for the cell — ready to lower."""
    seq, batch, kind = sp.SHAPES[shape]
    cfg = get_config(arch, **arch_overrides(arch, shape, overrides))
    api = get_model(cfg)
    params_sds, axes = sp.abstract_params(api, cfg)

    if kind == "train":
        opt_cfg = OptimizerConfig(quantize_states=arch in QUANTIZED_OPT)
        n_devices = math.prod(mesh.devices.shape)
        dp = n_devices // mesh.shape.get("model", 1)
        micro = MICROBATCHES.get(arch, DEFAULT_MICRO)
        while batch % (micro * dp) and micro > 1:
            micro //= 2
        tcfg = TrainConfig(global_batch=batch, seq_len=seq,
                           microbatches=micro, optimizer=opt_cfg)
        opt_sds = sp.abstract_opt_state(params_sds, opt_cfg)
        state_sds = {"params": params_sds, "opt": opt_sds}
        p_sh = shd.make_specs(axes, shd.TRAIN_RULES, mesh)
        o_axes = shd.opt_axes_like(axes, opt_cfg.quantize_states)
        o_sh = shd.make_specs(o_axes, shd.TRAIN_RULES, mesh)
        state_sh = {"params": p_sh, "opt": o_sh}
        batch_sds = sp.train_batch_specs(cfg, batch, seq)
        b_sh = jax.tree.map(
            lambda x: shd.batch_spec(mesh, extra_dims=len(x.shape) - 1),
            batch_sds)
        step = make_train_step(api, cfg, tcfg, mesh, param_specs=p_sh)
        fn = jax.jit(step,
                     in_shardings=(_ns(mesh, state_sh), _ns(mesh, b_sh)),
                     donate_argnums=(0,))
        return fn, (state_sds, batch_sds), cfg, {"microbatches": tcfg.microbatches}

    p_sh = shd.make_specs(axes, shd.SERVE_RULES, mesh)
    # vlm: the vision prefix occupies cache positions ahead of the tokens
    eff_len = seq + (cfg.vision_tokens if cfg.family == "vlm" else 0)
    if kind == "prefill":
        batch_sds = sp.prefill_batch_specs(cfg, batch, seq)
        b_sh = jax.tree.map(
            lambda x: shd.batch_spec(mesh, extra_dims=len(x.shape) - 1),
            batch_sds)
        c_sh = sp.cache_specs(cfg, mesh, batch, eff_len)

        def prefill_fn(params, b):
            return api.prefill(params, b, cfg, max_len=eff_len, mesh=mesh)

        fn = jax.jit(
            prefill_fn,
            in_shardings=(_ns(mesh, p_sh), _ns(mesh, b_sh)),
            out_shardings=(_ns(mesh, c_sh),
                           NamedSharding(mesh, P(_batch_axes(mesh, batch), "model"))),
        )
        return fn, (params_sds, batch_sds), cfg, {}

    # decode
    cache_sds = sp.cache_template(cfg, batch, seq)
    c_sh = sp.cache_specs(cfg, mesh, batch, seq)
    tok_sds = SDS((batch,), jnp.int32)
    tok_sh = NamedSharding(mesh, P(_batch_axes(mesh, batch)))

    def decode_fn(params, cache, tokens):
        return api.decode(params, cache, tokens, cfg, mesh=mesh)

    fn = jax.jit(
        decode_fn,
        in_shardings=(_ns(mesh, p_sh), _ns(mesh, c_sh), tok_sh),
        out_shardings=(_ns(mesh, c_sh),
                       NamedSharding(mesh, P(_batch_axes(mesh, batch), "model"))),
        donate_argnums=(1,),
    )
    return fn, (params_sds, cache_sds, tok_sds), cfg, {}


def _batch_axes(mesh, batch: int):
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    size = math.prod(mesh.shape[a] for a in axes) if axes else 1
    return axes if (axes and batch % size == 0) else None


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Roofline terms
# ---------------------------------------------------------------------------


def roofline_from(compiled, cfg, *, tokens: int, n_chips: int,
                  kind: str = "train", seq: int = 0) -> dict:
    from repro.launch import hlo_cost

    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    bf16_dims = None
    if cfg.compute_dtype == "bfloat16" and seq:
        bf16_dims = {seq, seq // 16, seq // 256}
    model = hlo_cost.analyze(hlo, bf16_dims=bf16_dims)
    flops = float(model["flops"])
    byts = float(model["bytes"])
    coll = {"total_bytes": model["collective_bytes"],
            "per_op_bytes": model["collective_detail"],
            "xla_cost_analysis_flops": float(cost.get("flops", 0.0))}
    t_compute = flops / PEAK_FLOPS
    t_memory = byts / HBM_BW
    t_coll = coll["total_bytes"] / ICI_BW
    # 6*N*D for training (fwd+bwd), 2*N*D for inference forward; attention
    # FLOPs are excluded from MODEL_FLOPS by convention, so long-context
    # cells legitimately show ratios > 1 worth of attention compute.
    factor = 6 if kind == "train" else 2
    model_flops = factor * cfg.active_param_count() * tokens
    terms = {
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": byts,
        "collective_bytes_per_device": coll["total_bytes"],
        "collective_detail": coll,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "bottleneck": max(
            [("compute", t_compute), ("memory", t_memory),
             ("collective", t_coll)], key=lambda kv: kv[1])[0],
        "model_flops_total": model_flops,
        "useful_flops_ratio": (model_flops / (flops * n_chips)
                               if flops else 0.0),
    }
    return terms


def memory_summary(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        live = (out.get("argument_size_in_bytes", 0)
                + out.get("output_size_in_bytes", 0)
                + out.get("temp_size_in_bytes", 0)
                - out.get("alias_size_in_bytes", 0))
        out["est_live_bytes"] = int(live)
    return out


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape: str, *, multi_pod: bool,
             overrides: Optional[dict] = None, keep_hlo: bool = False) -> dict:
    seq, batch, kind = sp.SHAPES[shape]
    cfg0 = get_config(arch)
    ok, why = sp.cell_supported(cfg0, shape)
    rec: dict[str, Any] = {
        "arch": arch, "shape": shape, "kind": kind,
        "multi_pod": multi_pod, "seq": seq, "batch": batch,
    }
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = math.prod(mesh.devices.shape)
    t0 = time.time()
    fn, args, cfg, extra = build_cell(arch, shape, mesh,
                                      overrides=overrides)
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    tokens = batch * (seq if kind == "train" else (seq if kind == "prefill" else 1))
    rec.update(
        status="ok",
        n_chips=n_chips,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory=memory_summary(compiled),
        roofline=roofline_from(compiled, cfg, tokens=tokens, n_chips=n_chips,
                               kind=kind, seq=seq),
        **extra,
    )
    if keep_hlo:
        rec["hlo_path"] = f"/tmp/hlo_{arch}_{shape}_{'mp' if multi_pod else 'sp'}.txt"
        with open(rec["hlo_path"], "w") as f:
            f.write(compiled.as_text())
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(sp.SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="JSON output path")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (python literal)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        import ast

        try:
            overrides[k] = ast.literal_eval(v)
        except (SyntaxError, ValueError):
            overrides[k] = v

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(sp.SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    records = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                label = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                try:
                    rec = run_cell(arch, shape, multi_pod=mp,
                                   overrides=overrides or None,
                                   keep_hlo=args.keep_hlo)
                except Exception as e:  # noqa: BLE001 — report, keep going
                    rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": "error", "error": f"{type(e).__name__}: {e}"}
                records.append(rec)
                st = rec["status"]
                msg = f"[dryrun] {label}: {st}"
                if st == "ok":
                    r = rec["roofline"]
                    msg += (f" compile={rec['compile_s']}s"
                            f" bottleneck={r['bottleneck']}"
                            f" t_comp={r['t_compute_s']:.2e}s"
                            f" t_mem={r['t_memory_s']:.2e}s"
                            f" t_coll={r['t_collective_s']:.2e}s")
                elif st == "error":
                    msg += f" {rec['error']}"
                print(msg, flush=True)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"[dryrun] wrote {len(records)} records to {args.out}")
    bad = [r for r in records if r["status"] == "error"]
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
