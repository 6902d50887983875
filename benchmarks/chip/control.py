"""Read the correctness check's two ends on the chip: the program's
widest and mean logit gaps and the lower-precision control's, per seed.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] [--precision fp8 [int8]]

Each seed is one run of the cell (the served path at the cell's own size
and load, with a short window) in this one process, whose check also
puts the plain reference in each ``--precision`` in the program's place
on the same prompts and tokens, judged by the cell's own limits.  Prints
one JSON line per seed, and exits non-zero if a control came out correct
on any seed: the comparison then cannot tell that precision from the
program's.  The benchmark's own runs never run a control.
"""
import argparse
import json
import sys
import time

from run import use_compile_cache  # puts src on the path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", nargs="+", default=["fp8"],
                    choices=("fp8", "int8"))
    args = ap.parse_args(argv)

    use_compile_cache()
    from chipbench import harness, spec

    cell = spec.load_cell(args.workload)
    passed = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = harness.run_cell(cell, seed, args.seconds, False, t_start=t0,
                              controls=tuple(args.precision))
        passed += [(seed, m) for m, ok in res["control_correct"].items()
                   if ok]
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": res["control_correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"], "check": res["check"],
                          "metrics": res["metrics"]}), flush=True)
    if passed:
        print(f"control came out correct (seed, precision): {passed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
