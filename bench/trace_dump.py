"""Print what a profiler trace holds, for reading it by hand: planes,
their lines with event counts, and the longest events of each line.

    python3 bench/trace_dump.py <trace dir> [--top N]
"""
import argparse
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import xtrace  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("dir")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args()
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xtrace.xplane_file(args.dir))
    for plane in pd.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            tot, cnt, stats = collections.Counter(), collections.Counter(), {}
            t0 = t1 = None
            for e in line.events:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
                stats.setdefault(e.name, dict(e.stats))
                t0 = e.start_ns if t0 is None else min(t0, e.start_ns)
                t1 = e.end_ns if t1 is None else max(t1, e.end_ns)
            print(f"  LINE {line.name!r}: {sum(cnt.values())} events, "
                  f"{len(cnt)} names, span {t0}..{t1}")
            for name, ns in tot.most_common(args.top):
                st = {k: v for k, v in stats[name].items()
                      if k in ("hlo_op", "hlo_module", "long_name",
                               "tf_op", "program_id", "flops",
                               "bytes_accessed")}
                print(f"    {ns / 1e6:12.3f} ms  x{cnt[name]:<7d} "
                      f"{name[:70]!r} {str(st)[:160]}")


if __name__ == "__main__":
    main()
