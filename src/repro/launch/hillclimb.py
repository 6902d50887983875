import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Reproduce the EXPERIMENTS.md §Perf hillclimb cells (baseline vs optimized).

Usage: PYTHONPATH=src python -m repro.launch.hillclimb [--out results/hillclimb.json]
"""
import argparse
import json

from repro.launch import dryrun
from repro.launch.dryrun import build_cell, roofline_from
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import SHAPES

CELLS = [
    ("A0-baseline", "nemotron-4-340b", "train_4k", None, 16),
    ("A*-optimized", "nemotron-4-340b", "train_4k",
     {"explicit_tp": True, "fsdp_params": True,
      "seq_shard_activations": True}, 4),
    ("B0-baseline", "llama3.2-3b", "prefill_32k", None, None),
    ("B*-optimized", "llama3.2-3b", "prefill_32k",
     {"pad_heads_to": 32, "explicit_tp": True}, None),
    ("C0-baseline", "moonlight-16b-a3b", "decode_32k", None, None),
    ("C*-optimized", "moonlight-16b-a3b", "decode_32k",
     {"explicit_tp": True}, None),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/hillclimb.json")
    args = ap.parse_args()
    mesh = make_production_mesh()
    records = []
    for label, arch, shape, ov, micro in CELLS:
        if micro:
            dryrun.MICROBATCHES[arch] = micro
        fn, cell_args, cfg, extra = build_cell(arch, shape, mesh,
                                               overrides=ov)
        seq, batch, kind = SHAPES[shape]
        tokens = batch * (seq if kind != "decode" else 1)
        with mesh:
            compiled = fn.lower(*cell_args).compile()
        rl = roofline_from(compiled, cfg, tokens=tokens, n_chips=256,
                           kind=kind, seq=seq)
        dom = max(rl["t_compute_s"], rl["t_memory_s"], rl["t_collective_s"])
        rec = {"label": label, "arch": arch, "shape": shape,
               "overrides": ov, "roofline": rl,
               "dominant_s": dom,
               "roofline_fraction": rl["t_compute_s"] / dom if dom else 0.0,
               **extra}
        records.append(rec)
        print(f"{label:14s} t=({rl['t_compute_s']:.4f},"
              f"{rl['t_memory_s']:.4f},{rl['t_collective_s']:.4f}) "
              f"frac={rec['roofline_fraction']:.3f}", flush=True)
    with open(args.out, "w") as f:
        json.dump(records, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
