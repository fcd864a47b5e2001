"""Record the small scoped TPU traces that ``test_scopes.py`` reduces (run
on the chip; the files are kept under ``bench/tests/data/scoped_trace``).

    python3 bench/tests/record_scoped_trace.py <out dir>

Three tiny training cells (``conftest.TINY``'s configurations and
``TRAIN_MIX``, batch 4), each driven as ``bench/run.py --trace 1``
drives a cell: the job's set-up, then a traced window of the mix's
``trace_steps`` steps of ``Run.step`` inside the harness's ``bench.*``
spans.  ``xlstm-tiny.ft`` has the mLSTM and sLSTM scans,
``minicpm-tiny.ft`` attention, and ``minicpm-tiny.ft.jnp`` the same cell
with the sampled dW on its dot_general route.  Writes one trace
directory per cell, its ``.xplane.pb`` kept without the ``/host:metadata``
plane (the programs' HLO, most of the bytes, which no reader reads), and
``cells.json``: each cell as the readers take it, with the steps traced
and the device kind.
"""
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import scopes  # noqa: E402
import spec  # noqa: E402
import xtrace  # noqa: E402
from conftest import TINY, TRAIN_MIX, _config  # noqa: E402

CELLS = {"xlstm-tiny.ft": ("xlstm-tiny", "pallas"),
         "minicpm-tiny.ft": ("minicpm-tiny", "pallas"),
         "minicpm-tiny.ft.jnp": ("minicpm-tiny", "jnp")}


def strip(log_dir):
    """Keep only the trace's ``.xplane.pb``, less its ``/host:metadata``
    plane (top-level XSpace fields are copied byte for byte)."""
    path = xtrace.xplane_file(log_dir)
    for f in os.listdir(os.path.dirname(path)):
        if not f.endswith(".xplane.pb"):
            os.remove(os.path.join(os.path.dirname(path), f))
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out, at = bytearray(), 0
    for field, payload in scopes._fields(buf, 0, len(buf)):
        # XSpace's fields are all length-delimited: a field's bytes run
        # from the previous one's end to its payload's end
        if (field != scopes.XSPACE_PLANES
                or scopes._plane(buf, payload)[0] != "/host:metadata"):
            out += buf[at:payload[1]]
        at = payload[1]
    with open(path, "wb") as f:
        f.write(out)
    return path


def main():
    out = sys.argv[1]
    spec.use_program()
    import jax
    from repro.core.kernel_config import KernelConfig
    if jax.devices()[0].platform != "tpu":
        sys.exit("needs a TPU")
    job = spec.load_module("jobs", "train")
    policy = job.policy
    cells = {}
    for name, (config, route) in CELLS.items():
        reg, ref, changes = TINY[config]
        cell = {"name": name, "config": config, "batch": 4,
                "config_file": _config(config, reg, ref, changes),
                "mix": TRAIN_MIX}
        job.policy = lambda mix, route=route: policy(mix).with_kernel(
            KernelConfig(backend=route))
        st = job.setup(cell, 1, jax.profiler.TraceAnnotation)
        tdir = os.path.join(out, name)
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir, profiler_options=xtrace.options())
        work = job.window(st, cell, 60.0, jax.profiler.TraceAnnotation,
                          traced=True)
        jax.profiler.stop_trace()
        job.release(st)
        cells[name] = {**cell, "steps": work["steps"],
                       "device_kind": jax.devices()[0].device_kind}
        f = strip(tdir)
        print(name, f, os.path.getsize(f), flush=True)
    with open(os.path.join(out, "cells.json"), "w") as f:
        json.dump(cells, f, indent=1)


if __name__ == "__main__":
    main()
