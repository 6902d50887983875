"""Print what a profiler trace holds, to read it by hand.

    python3 benchmarks/chip/tracedump.py <file.xplane.pb> [--names N]
        [--fixture OUT.json.gz --ms M]

For each plane and line: the event count, the time they cover, and the
names that took most time, with the stats of one event of each.  With
``--fixture``, also writes the events the benchmark reads (see
``chipbench/trace.py``) that overlap the first ``M`` ms of device
activity, as the tests' recorded trace.
"""
import argparse
import collections
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("path")
    ap.add_argument("--names", type=int, default=12)
    ap.add_argument("--fixture")
    ap.add_argument("--ms", type=float, default=20.0)
    args = ap.parse_args(argv)
    if args.fixture:
        import gzip
        import json

        from chipbench import trace

        evs = trace.events(args.path)
        t0 = min(e["start_ns"] for e in evs
                 if e["plane"].startswith(trace.DEVICE_PREFIX))
        t1 = t0 + args.ms * 1e6
        keep = [e for e in evs if e["start_ns"] < t1
                and e["start_ns"] + e["dur_ns"] > t0]
        with gzip.open(args.fixture, "wt") as f:
            json.dump({"window_s": args.ms / 1e3, "events": keep}, f)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(args.path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            t0 = min(e.start_ns for e in evs)
            t1 = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{t0:.0f}..{t1:.0f} ns")
            tot = collections.Counter()
            one = {}
            for e in evs:
                tot[e.name] += e.duration_ns
                one.setdefault(e.name, e)
            for name, ns in tot.most_common(args.names):
                stats = {k: (str(v)[:80]) for k, v in one[name].stats}
                print(f"    {ns / 1e6:10.3f} ms  {name[:100]!r}  {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
