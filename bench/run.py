"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell by name (``BENCHMARK.json``, ``bench/workloads/``,
``bench/configs/``, ``bench/traffic/``), refuses to run without a TPU
with the cell's chips, keeps JAX's compile cache in the checkout's
``.jax_cache``, and hands the cell to its job driver
(``bench/jobs/<job>.py``): set-up (weights and inputs from the seed,
every shape warmed), the measured window, the peak memory, then the
comparison with the plain reference that decides ``correct``.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces the window with the JAX profiler and reports its per-layer
metrics, each read by ``bench/metrics/<metric>.py`` from the reduced
trace.  The last line of standard output is one JSON object; the last
lines of standard error are the numbers compared, beside their limits.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import spec  # noqa: E402

sys.path.insert(0, str(spec.BENCH / "reference"))


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw trace here (for reading it by hand)")
    return ap.parse_args(argv)


def device_info(jax) -> dict:
    """As JAX reports it; ``memory_peak_reserved_bytes`` is the fullest
    chip's peak of memory reserved for programs' scratch."""
    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats),
            "memory_peak_reserved_bytes": max(
                int(s.get("peak_bytes_reserved", 0)) for s in stats)}


def require_chips(jax, chips: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(3)


def judge(numbers: dict, limits: dict):
    """(correct, {name: {value, limit}}) over the cell's limits."""
    checks, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name, math.inf)
        checks[name] = {"value": v, "limit": limit}
        ok = ok and math.isfinite(v) and v <= limit
    return ok, checks


def per_layer(cell, trace, steps, work, jax):
    import flops
    ctx = {"trace": trace, "cell": cell, "steps": steps, "work": work,
           "flops": flops, "peaks": flops.peaks(jax.devices()[0].device_kind),
           "model": spec.load_module("reference",
                                     cell["config_file"]["reference"])}
    out = {}
    for m in spec.metrics_for(cell["name"], "per_layer"):
        reader = spec.load_module("metrics", m["name"])
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": reader.UNIT}
    return out


def main(argv=None, program=None, chips_check=True) -> dict:
    args = parse(argv)
    cell = spec.workload(args.workload)
    spec.use_program()
    import jax
    import repro  # noqa: F401  (the system under test must be here)
    if chips_check:
        require_chips(jax, cell["chips"])
    spec.enable_compile_cache()
    job = spec.load_module("jobs", cell["mix"]["job"])
    host = (jax.profiler.TraceAnnotation if args.trace
            else (lambda name: contextlib.nullcontext()))

    st = job.setup(cell, args.seed, host, program=program)
    setup_s = time.perf_counter() - T_START
    tdir = str(spec.ROOT / ".bench_trace" / cell["name"])
    if args.trace:
        import xtrace as trace_lib
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir, profiler_options=trace_lib.options())
    work = job.window(st, cell, args.seconds, host,
                      traced=bool(args.trace))
    if args.trace:
        jax.profiler.stop_trace()
    device = device_info(jax)
    job.release(st)
    ref = job.reference(st, cell, args.seed)
    numbers = job.compare(st, ref)
    correct, checks = judge(numbers, cell["limits"])

    result = {"correct": correct, "attempted": work["attempted"],
              "failed": work["failed"]}
    if args.trace:
        tr = trace_lib.Trace.load(tdir)
        result["metrics"] = per_layer(cell, tr, work["steps"], work, jax)
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.idle_gaps()}
        if args.keep_trace:
            shutil.copytree(tdir, args.keep_trace, dirs_exist_ok=True)
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        e2e = job.end_to_end(cell, work, device)
        e2e["setup_s"] = setup_s
        units = {m["name"]: m["unit"]
                 for m in spec.metrics_for(cell["name"], "end_to_end")}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in e2e.items() if k in units}
    result["device"] = device
    result["where"] = numbers.get("_where", {})
    result["checks"] = checks
    for line in work.get("notes", []):
        print(line, file=sys.stderr)
    for name, c in checks.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
