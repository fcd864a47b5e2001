"""One sweep of a serving cell's offered rate (not run by the benchmark;
it is how the cell's fixed rate was found).

    python3 bench/sweep.py --workload <cell> --seconds 30 --rates 2 4 8 ...

One process: set-up once, then for each rate one open-loop window of the
cell's mix at that rate.  Per rate it prints the requests offered, the
tail latencies, the requests unfinished at the close, and how long the
drain after the close took: a rate the system sustains drains in about
one request's service time, one above it leaves a growing backlog.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import spec  # noqa: E402

sys.path.insert(0, str(spec.BENCH / "reference"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cell = spec.workload(args.workload)
    spec.use_program()
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep.py measures on the chip; no TPU found")
    spec.enable_compile_cache()
    job = spec.load_module("jobs", cell["mix"]["job"])
    host = lambda name: contextlib.nullcontext()  # noqa: E731
    st = job.setup(cell, args.seed, host)
    for rate in args.rates:
        c = dict(cell, mix=dict(cell["mix"], rate_per_s=rate))
        w = job.window(st, c, args.seconds, host)
        e = job.end_to_end(c, w, {})
        ttft = np.asarray([x for x in w["ttft"] if np.isfinite(x)])
        print(json.dumps({
            "rate": rate, "requests": w["attempted"],
            "unfinished": w["failed"], "wall_s": w["wall"],
            "drain_s": w["wall"] - args.seconds, "ticks": w["steps"],
            "ttft_p50_ms": 1e3 * float(np.median(ttft)) if ttft.size
            else None, **e, "notes": w["notes"]}), flush=True)


if __name__ == "__main__":
    main()
