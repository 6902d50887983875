"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (+ JSON artifacts under
results/).  Experiments map 1:1 to the paper:

  exp1_*      Fig. 3  scaling of no-op task dispatch (weak/strong)
  exp2_*      Fig. 4  heterogeneity width
  exp3_*      Fig. 5a,b inference-at-scale throughput/utilization
  exp4_*      Fig. 5c,d batching sensitivity + routing policies
  exp5_*      Fig. 6  coupled AI-HPC data exchange
  exp6_*      Fig. 7  agent decision rate vs ARR
  roofline_*  (this build) dry-run roofline terms per arch x shape
  kernel_*    Pallas kernel micro-benchmarks (interpret mode on the CPU)
"""
from __future__ import annotations

import argparse
import sys

from repro.launch.compile_cache import enable_compile_cache

from . import (bench_agentic, bench_coupling, bench_heterogeneity,
               bench_inference_scaling, bench_roofline, bench_routing,
               bench_scaling)
from .common import Reporter


def _kernels(rep):
    from . import bench_kernels

    return bench_kernels.main(rep)


SUITES = {
    "exp1_scaling": lambda rep: bench_scaling.main(rep),
    "exp2_heterogeneity": lambda rep: bench_heterogeneity.main(rep),
    "exp3_inference": lambda rep: bench_inference_scaling.main(rep),
    "exp4_routing": lambda rep: bench_routing.main(rep),
    "exp5_coupling": lambda rep: bench_coupling.main(rep),
    "exp6_agentic": lambda rep: bench_agentic.main(rep),
    "roofline": lambda rep: bench_roofline.main(rep),
    "kernels": _kernels,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset of suites to run")
    args = ap.parse_args()
    enable_compile_cache()
    rep = Reporter()
    print("name,us_per_call,derived")
    payload = {}
    failures = []
    for name, fn in SUITES.items():
        if args.only and name not in args.only:
            continue
        try:
            payload[name] = fn(rep)
        except Exception as e:  # noqa: BLE001 — keep the suite running
            failures.append((name, repr(e)))
            rep.add(f"{name}_FAILED", 0.0, repr(e)[:120])
    rep.save_json("benchmarks.json", payload)
    if failures:
        print(f"# {len(failures)} suite(s) failed", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
