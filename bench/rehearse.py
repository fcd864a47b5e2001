"""Compile-only rehearsal of a cell's programs at their real sizes for a
described TPU v5e (nothing runs; no chip needed).

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload <cell> [--batch N ...]

For a training cell it compiles the program's train step (the function
``Run.step`` jits) for one chip of a described ``v5e:2x2`` and prints
``memory_analysis()``: argument, temporary and output bytes.  Their sum
is an upper bound on the step's peak (it counts every buffer as live at
once); it is the basis of each training cell's batch.  For a serving
cell it compiles the slot decode step and the prefill-chunk steps.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import spec  # noqa: E402

sys.path.insert(0, str(spec.BENCH / "reference"))


def _sharded(tree, sharding):
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, f"{k}_size_in_bytes"))
           for k in ("argument", "temp", "output", "alias")}
    out["sum_gib"] = (out["argument"] + out["temp"] + out["output"]
                      - out["alias"]) / 2**30
    return out


def train(cell, cfg, batch, chip):
    import jax
    import jax.numpy as jnp
    from repro.core.kernel_config import KernelConfig
    from repro.launch import train_steps
    from repro.train import optim

    jobs = spec.load_module("jobs", "train")
    policy = jobs.policy(cell["mix"]).with_kernel(
        KernelConfig(backend="pallas", interpret=False))
    fn = train_steps.make_train_step(
        cfg, policy, optim.AdamWConfig(),
        optim.make_schedule("constant", cell["mix"]["lr"], warmup=1))
    state, _ = train_steps.abstract_train_state(cfg)
    s = cell["mix"]["seq_len"]
    b = {"tokens": jax.ShapeDtypeStruct((batch, s), jnp.int32),
         "labels": jax.ShapeDtypeStruct((batch, s), jnp.int32)}
    t = time.time()
    c = jax.jit(fn).lower(_sharded(state, chip), _sharded(b, chip)).compile()
    rec = _mem(c)
    rec.update(batch=batch, compile_s=round(time.time() - t, 1),
               tpu_custom_calls=c.as_text().count("tpu_custom_call"))
    return rec


def serve(cell, cfg, batch, chip):
    import jax
    import jax.numpy as jnp
    from repro.launch import train_steps
    from repro.serve import pool as pool_lib
    from repro.serve.spec import ServeSpec

    mix = cell["mix"]
    sspec = ServeSpec(arch=cfg.name, reduced=False, **mix["serve_spec"])
    params = jax.eval_shape(
        lambda k: spec.load_module("reference", cell["config_file"][
            "reference"]).init(cell["config_file"]["config"], k),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    pool = jax.eval_shape(lambda: pool_lib.init_pool(cfg, sspec))
    m = sspec.max_slots
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    dec = jax.jit(train_steps.make_slot_serve_step(cfg, sspec.policy))
    t = time.time()
    c = dec.lower(_sharded(params, chip), _sharded(pool, chip),
                  _sharded(i32(m, sspec.pages_per_slot), chip),
                  *[_sharded(x, chip) for x in (
                      i32(m), i32(m), jax.ShapeDtypeStruct((m,), bool),
                      jax.ShapeDtypeStruct((m, 2), jnp.uint32), i32(m),
                      jax.ShapeDtypeStruct((m,), jnp.float32))]).compile()
    rec = {"decode": _mem(c), "decode_compile_s": round(time.time() - t, 1)}
    pre = jax.jit(train_steps.make_slot_prefill_step(
        cfg, sspec.policy, sspec.prefill_chunk, True))
    t = time.time()
    c = pre.lower(_sharded(params, chip), _sharded(pool, chip),
                  _sharded(i32(sspec.pages_per_slot), chip),
                  _sharded(i32(), chip), _sharded(i32(sspec.prefill_chunk),
                                                  chip),
                  _sharded(i32(), chip)).compile()
    rec.update(prefill=_mem(c), prefill_compile_s=round(time.time() - t, 1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, nargs="*")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.workload(args.workload)
    cfg = spec.arch_config(cell["config_file"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    job = {"train": train, "serve": serve}[cell["mix"]["job"]]
    for b in args.batch or [cell.get("batch", 0)]:
        print(json.dumps({"workload": args.workload,
                          **job(cell, cfg, b, chip)}), flush=True)


if __name__ == "__main__":
    main()
