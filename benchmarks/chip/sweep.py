"""Find the knee of an open-loop cell: the highest offered rate that the
program sustains on every seed.

    python3 benchmarks/chip/sweep.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] --rates <r> [<r> ...] [--drain-cap <s>]

Runs the cell once per rate and seed, in this one process, with the
cell's mix at that rate.  Prints one JSON line per run: the offered and
the served output tokens per second, the median and 95th-percentile TPOT,
the share of the window's requests that met both of the cell's latency
limits (a failed or unfinished request misses), the requests in flight at
the window's start and end, and the check.  Then one line per rate:

- sustained: averaged over the seeds, the output tokens per second reach
  ``MIN_SERVED`` of the offered (a ramp leaves the window's first seconds
  below steady state, so a little under 1 is sustained) and the requests
  in flight grow over the window by at most ``MAX_GROWTH``; and on every
  seed the median request meets the cell's TPOT limit;
- the knee: the highest rate that is sustained with every lower rate.

Run once when a cell is defined; the chosen rate goes into the cell file.
"""
import argparse
import copy
import dataclasses
import json
import statistics
import sys
import time

from run import use_compile_cache  # puts src on the path

MIN_SERVED = 0.85
MAX_GROWTH = 2.0


def window_numbers(load, recs, limits) -> dict:
    from chipbench import stats

    ws, we = load.window
    due = [r for r in recs if ws <= r["due"] < we]
    ttft = stats.ttft_ms(due, load.window, load.t_end)
    met = 0
    for r, t in zip(due, ttft):
        if not r["ok"] or t > limits["ttft_ms"]:
            continue
        if r["n_out"] > 1 and 1e3 * (r["finish"] - r["first"]) / (
                r["n_out"] - 1) > limits["tpot_ms"]:
            continue
        met += 1

    def in_flight(t):
        return sum(1 for r in recs if r["submit"] <= t
                   and (r["finish"] is None or r["finish"] > t))

    offered = sum(load.schedule.requests[r["idx"]].out_len for r in due)
    return {"attainment": met / max(1, len(due)), "due": len(due),
            "offered_tokens_per_s": offered / (we - ws),
            "in_flight_start": in_flight(ws), "in_flight_end": in_flight(we)}


def verdict(rows: list, limits: dict) -> dict:
    served = statistics.mean(r["output_tokens_per_s"]
                             / r["offered_tokens_per_s"] for r in rows)
    growth = statistics.mean(r["in_flight_end"] - r["in_flight_start"]
                             for r in rows)
    tpot = max(r["tpot_p50_ms"] for r in rows)
    return {"served_share": served, "in_flight_growth": growth,
            "tpot_p50_ms_max": tpot,
            "sustained": (served >= MIN_SERVED and growth <= MAX_GROWTH
                          and tpot <= limits["tpot_ms"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--drain-cap", type=float,
                    help="seconds to follow the window's requests after "
                         "it (default: the cell's); unfinished ones miss")
    args = ap.parse_args(argv)

    use_compile_cache()
    from chipbench import harness, spec

    base = spec.load_cell(args.workload)
    knee, below = None, True
    for rate in sorted(args.rates):
        cell = dataclasses.replace(
            base, traffic=copy.deepcopy(base.traffic), run=dict(base.run))
        cell.traffic["arrivals"]["rate_per_s"] = rate
        if args.drain_cap is not None:
            cell.run["drain_cap_s"] = args.drain_cap
        rows = []
        for seed in args.seeds:
            seen = {}

            def on_window(load, recs):
                seen.update(window_numbers(load, recs, cell.limits))

            res = harness.run_cell(cell, seed, args.seconds, False,
                                  t_start=time.perf_counter(),
                                  on_window=on_window)
            m = {k: v["value"] for k, v in res["metrics"].items()}
            rows.append({"rate_per_s": rate, "seed": seed, **seen,
                         **{k: m[k] for k in (
                             "output_tokens_per_s", "tpot_p50_ms",
                             "tpot_p95_ms", "ttft_p50_ms")},
                         "failed": res["failed"],
                         "correct": res["correct"], "check": res["check"]})
            print(json.dumps(rows[-1]), flush=True)
        v = verdict(rows, cell.limits)
        below = below and v["sustained"]
        if below:
            knee = rate
        print(json.dumps({"rate_per_s": rate, **v}), flush=True)
    print(json.dumps({"knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
