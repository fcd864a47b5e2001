"""The fine-tuning job: ``Run.step`` driven as ``Run.fit`` drives it.

Set-up builds one ``Run`` (the compiled scheduled step and its state),
puts the benchmark's weights (made on the device from the seed, in one
jitted call) into its state, and takes the first ``check_steps`` steps
through the window's own call and feed: they compile the step and are
what ``correct`` compares.  The window then keeps stepping the same
object for ``--seconds``, whole steps, and ends on the state being
ready.  After the window the peak memory is read, the program's state
freed, and the plain reference follows the first steps from the same
weights, batches and sampling keys.
"""
from __future__ import annotations

import gc
import time

import check
import gen
import spec


def policy(mix: dict):
    from repro.core import WTACRSConfig
    from repro.models import common as cm
    if mix["estimator"] == "exact":
        return cm.Policy()
    return cm.Policy(wtacrs=WTACRSConfig(kind=mix["estimator"],
                                         budget=mix["budget"]))


def _run_spec(cfg, cell, run_seed):
    from repro.api import DataSpec, RunSpec
    mix = cell["mix"]
    return RunSpec(arch=cfg.name, reduced=False, policy=policy(mix),
                   seed=run_seed, steps=1 << 30, batch_size=cell["batch"],
                   lr=mix["lr"], lr_schedule="constant", warmup=1,
                   data=DataSpec(seq_len=mix["seq_len"],
                                 n_samples=cell["batch"]))


def keys(run_seed: int, n: int) -> list:
    """The program's per-step sampling keys, by its documented rule:
    fold_in(fold_in(PRNGKey(seed), 7), step)."""
    import jax
    base = jax.random.fold_in(jax.random.PRNGKey(run_seed), 7)
    return [jax.random.key_data(jax.random.fold_in(base, s))
            for s in range(n)]


def setup(cell, seed, trace_host, program=None):
    """Build the Run, load the weights, take the checked steps.
    ``program`` (tests only) wraps the Run's step: ``program(run)`` is
    the step the checked steps and the window drive."""
    import jax
    from repro.api import Run

    cfgfile, mix = cell["config_file"], cell["mix"]
    cfg = spec.arch_config(cfgfile)
    model = spec.load_module("reference", cfgfile["reference"])
    run_seed = spec.derive_seed(seed, "run")
    wkey = jax.random.PRNGKey(spec.derive_seed(seed, "weights"))
    feed = gen.TokenRows(cfg.vocab_size, mix["seq_len"],
                         spec.derive_seed(seed, "tokens"),
                         mix["zipf_exponent"], mix["zipf_offset"])
    run = Run(_run_spec(cfg, cell, run_seed))
    run.init()
    params = jax.jit(lambda k: model.init(cfgfile["config"], k))(wkey)
    run.state = dict(run.state, params=params)
    del params
    step = program(run) if program else run.step
    b1 = run.spec.optimizer.b1
    prog = {}
    for s in range(mix["check_steps"]):
        with trace_host("bench.batch"):
            batch = feed.batch_at(s, cell["batch"])
        with trace_host("bench.step"):
            step(batch)
        if s == 0:
            prog["grad1"] = {k: v / (1 - b1) for k, v in
                             check.leaf_norms(run.state["opt"].m).items()}
    p0_change = jax.jit(lambda p, k: jax.tree.map(
        lambda a, b: a - b, p, model.init(cfgfile["config"], k)))
    prog["change"] = check.leaf_norms(p0_change(run.state["params"], wkey))
    prog["losses"] = [h["loss"] for h in run.history]
    jax.block_until_ready(run.state)
    return {"run": run, "step": step, "feed": feed, "prog": prog,
            "model": model, "run_seed": run_seed, "wkey": wkey}


def window(st, cell, seconds, trace_host, traced=False):
    """Whole steps until ``seconds`` have passed (a traced window stops
    after the mix's ``trace_steps``), ending on the state being ready."""
    import math

    import jax
    max_steps = cell["mix"]["trace_steps"] if traced else None
    run, step, feed = st["run"], st["step"], st["feed"]
    s0 = int(run.state["step"])
    n, failed = 0, 0
    t0 = time.perf_counter()
    while True:
        with trace_host("bench.batch"):
            batch = feed.batch_at(s0 + n, cell["batch"])
        with trace_host("bench.step"):
            m = step(batch)
        n += 1
        failed += not math.isfinite(m["loss"])
        if (time.perf_counter() - t0 >= seconds
                or (max_steps is not None and n >= max_steps)):
            break
    jax.block_until_ready(run.state)
    wall = time.perf_counter() - t0
    return {"attempted": n, "failed": failed, "steps": n, "wall": wall,
            "notes": [f"window: {n} steps in {wall!r} s"]}


def release(st):
    """Free the program's state (the reference runs after it)."""
    st.pop("run", None)
    st.pop("step", None)
    gc.collect()


def reference(st, cell, seed, prec="f32", drop_half=False):
    mix = cell["mix"]
    n = mix["check_steps"]
    batches = [st["feed"].batch_at(s, cell["batch"]) for s in range(n)]
    budget = None if mix["estimator"] == "exact" else mix["budget"]
    return check.reference_train(
        st["model"], cell["config_file"]["config"], st["wkey"], batches,
        keys(st["run_seed"], n), mix["lr"], budget, prec,
        min(cell["reference_rows"], cell["batch"]), drop_half=drop_half)


def compare(st, ref):
    return check.compare_train(st["prog"], ref)


def control(st, cell, seed, ref, **planted):
    """The numbers of the reference run with ``planted`` (a lower
    precision, or half of each batch left out) in the program's place."""
    return check.compare_train(reference(st, cell, seed, **planted), ref)


def tokens_per_step(cell) -> int:
    return cell["batch"] * cell["mix"]["seq_len"]


def end_to_end(cell, work, device) -> dict:
    """Tokens/s over the window; peak HBM = the chip's peak of live
    buffers plus its peak of memory reserved for programs' scratch (the
    runtime reserves a program's scratch when it loads, outside
    ``peak_bytes_in_use``)."""
    return {"train_tokens_per_s":
            work["steps"] * tokens_per_step(cell) / work["wall"],
            "train_peak_hbm_gib": (device["memory_peak_bytes"]
                                   + device["memory_peak_reserved_bytes"])
            / 2**30}
