"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

From the repository root, on a machine whose JAX backend is a TPU with
the chips the cell asks for; anywhere else it exits non-zero before
measuring.  ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, read from a device trace of a few
seconds inside the window.  Progress goes to standard error, ending with
each number the correctness check compared and its limit; the last line
of standard output is the result as one JSON object.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
sys.path[:0] = [str(HERE), str(REPO / "src")]


def use_compile_cache() -> None:
    """JAX's persistent compile cache, at a fixed path inside the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` names one, for every
    program: only a checkout's first run of a cell compiles."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(REPO / ".jax_cache"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()


def wanted_metrics(bench: dict, cell: str, trace: bool) -> dict:
    """name -> unit of the metrics this cell reports in this kind of run."""
    e2e = {m["name"]: m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])}
    if not trace:
        return {n: m["unit"] for n, m in e2e.items()}
    return {m["name"]: m["unit"] for m in bench["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in e2e}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="FILE",
                    help="also copy the traced run's .xplane.pb here, to "
                         "read by hand")
    args = ap.parse_args(argv)

    with open(REPO / "BENCHMARK.json") as f:
        bench = json.load(f)
    work = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in work:
        ap.error(f"unknown workload {args.workload!r}")

    use_compile_cache()
    from chipbench import harness, spec

    cell = spec.load_cell(args.workload)
    wanted = wanted_metrics(bench, args.workload, bool(args.trace))
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             t_start=T_START,
                             chips=work[args.workload]["chips"],
                             wanted=wanted, keep_trace=args.keep_trace)
    for k, v in result["metrics"].items():
        if k not in wanted:  # read, but not a metric of this cell
            harness.log(f"not reported: {k} {v['value']} {v['unit']}")
    result["metrics"] = {k: v for k, v in result["metrics"].items()
                         if k in wanted}
    missing = sorted(set(wanted) - set(result["metrics"]))
    if missing:
        harness.log(f"nothing to read for {missing}")
    check = result.pop("check")
    result["check"] = check  # the compared numbers come last
    print(json.dumps(result), flush=True)
    for name, c in check.items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr,
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
