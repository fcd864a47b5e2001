"""Readings that the limits of a training cell are set from (not run by
the benchmark itself).

    python3 bench/calibrate.py --workload <cell> --seconds S --seeds 1 2 3 ... [--controls]

In one process, for each seed: set-up and a window of S seconds exactly
as a benchmark run takes them, compared with the plain reference (the
lower reading); with ``--controls`` also each of the mix's ``controls``
(the reference computed with fp8 operands in the program's place, and
for training the planted fault of half of each batch left out, the mean
taken over the rest), compared with the same reference.  A training step
that leaves the state unchanged reads 1 on ``update_norm_gap`` by
construction and needs no run.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import spec  # noqa: E402

sys.path.insert(0, str(spec.BENCH / "reference"))


def readings(cell, seed, seconds, controls):
    spec.use_program()
    job = spec.load_module("jobs", cell["mix"]["job"])
    host = lambda name: contextlib.nullcontext()  # noqa: E731
    t = time.perf_counter()
    st = job.setup(cell, seed, host)
    work = job.window(st, cell, seconds, host)
    job.release(st)
    ref = job.reference(st, cell, seed)
    out = {"seed": seed, "program": job.compare(st, ref),
           "failed": work["failed"], "readings": {
               "program": st.get("prog"), "reference": ref}}
    if controls:
        for name, planted in cell["mix"]["controls"].items():
            out[name] = job.control(st, cell, seed, ref, **planted)
    out["seconds"] = time.perf_counter() - t
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--controls", action="store_true")
    args = ap.parse_args()
    cell = spec.workload(args.workload)
    spec.use_program()
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("calibrate.py measures on the chip; no TPU found")
    spec.enable_compile_cache()
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.seconds,
                                  args.controls)), flush=True)


if __name__ == "__main__":
    main()
