"""Chip smoke test: WTA-CRS fine-tuning of full-width xLSTM-125M on one
TPU, checked against the plain-jnp kernel route, then serving from the
trained weights.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # (data 2, model 2) mesh vs one chip

Phases (one process; it starts no children):

  train      ``Run.fit`` of the published xlstm-125m config, WTA-CRS at
             budget 0.3, batch 4 x 512 tokens, 4 steps.  Every loss must
             be finite, and the compiled step must hold a
             ``tpu_custom_call`` (the Pallas row-norm kernel ran
             natively, not through the interpreter).
  reference  the same spec and seed with ``KernelConfig(backend="jnp")``.
             Step 0 builds its plans from the same weights and key on
             both routes (the forward is exact), so its gradients differ
             only by rounding and by the few rows a plan picks otherwise
             where the routes' row norms round differently: the worst
             leaf's relative difference must be within ``GRAD_RTOL``,
             and two controls (every gradient halved, every gradient
             zeroed) must be rejected by that bound.  Step-0 losses must
             be equal; later losses agree within ``LOSS_RTOL``.
  serve      ``run.serve`` answers 4 greedy requests (32-token prompts,
             16 new tokens); each must return 16 in-vocab tokens.  The
             number that match a solo ``run.generate`` is printed.

``--four-chips`` runs only the four-chip paths and what they are
compared with, in this process: one sampled linear at full width on a
(data 2, model 2) mesh against one chip, same inputs and key (gradients
within ``GRAD_RTOL``, same controls); then the training spec with
``mesh="host", model_parallel=2`` on that mesh and on one chip.  The
mesh run must start from bit-equal weights and keep losses within
``LOSS_RTOL``; its step-0 gradient readings are reported, not checked
(see ``compare_four``).

Exits non-zero when JAX finds no TPU, and when any phase fails.  The
last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
from importlib import metadata

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.api import DataSpec, Run, RunSpec  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import WTACRSConfig  # noqa: E402
from repro.core.kernel_config import KernelConfig  # noqa: E402
from repro.core.linear import wtacrs_linear  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import common as cm  # noqa: E402
from repro.train.optim import AdamWConfig  # noqa: E402

ARCH = "xlstm-125m"
BATCH, SEQ, STEPS = 4, 512, 4
# The decisive check: the worst parameter leaf's relative L2 difference
# of the step-0 gradient.  A dW that is halved reads 0.5 and a zero dW
# reads 1 on its leaf.  Both comparisons run identical forward passes
# (kernel vs jnp route; one sharded linear vs one device from the same
# inputs), so only rounding and the odd plan row differ.
GRAD_RTOL = 0.1
# A coarse guard only: over 4 steps the loss moves by less than this
# bound, so it cannot tell a wrong dW from a right one.  Later steps
# drift apart because plans built from slightly different weights pick
# different rows.
LOSS_RTOL = 1e-2
N_REQUESTS, PROMPT_LEN, MAX_NEW = 4, 32, 16


class PhaseError(RuntimeError):
    """A phase's check failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def run_spec(arch: str, reduced: bool, *, kernel=None, mesh=None,
             model_parallel: int = 1, seq: int = SEQ) -> RunSpec:
    policy = cm.Policy(wtacrs=WTACRSConfig(kind="wta_crs", budget=0.3,
                                           min_rows=4))
    return RunSpec(arch=arch, reduced=reduced, policy=policy, kernel=kernel,
                   batch_size=BATCH, steps=STEPS, mesh=mesh,
                   model_parallel=model_parallel,
                   data=DataSpec(seq_len=seq, n_samples=4 * BATCH))


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


@dataclasses.dataclass
class Trained:
    """A finished train phase.  ``params0`` and ``grads0``: host copies
    of the initial weights and of the step-0 gradient."""
    run: Run
    losses: list
    params0: dict
    grads0: dict


def step0_grads(run):
    """The gradient of the step just taken from a fresh state, read back
    from AdamW's first moment: from m = 0, one step leaves m = (1 - b1) g
    (gradient clipping off, so g is the raw gradient)."""
    opt = run.spec.optimizer
    check(isinstance(opt, AdamWConfig) and opt.grad_clip_norm == 0
          and int(run.state["step"]) == 1,
          "step0_grads needs unclipped AdamW state one step from init")
    return jax.tree.map(lambda m: np.asarray(m) / (1 - opt.b1),
                        jax.device_get(run.state["opt"].m))


def train_phase(arch: str, reduced: bool, *, kernel=None, mesh=None,
                model_parallel: int = 1, seq: int = SEQ) -> Trained:
    """Fit ``STEPS`` steps, keeping the initial weights and the step-0
    gradient.  Losses must be finite."""
    run = Run(run_spec(arch, reduced, kernel=kernel, mesh=mesh,
                       model_parallel=model_parallel, seq=seq))
    run.init()
    params0 = jax.device_get(run.state["params"])
    run.step(run.dataset.batch_at(0, run.spec.batch_size))
    grads0 = step0_grads(run)
    run.fit()
    losses = [h["loss"] for h in run.history]
    check(len(losses) == STEPS, f"expected {STEPS} losses, got {losses}")
    check(all(math.isfinite(x) for x in losses),
          f"non-finite training loss: {losses}")
    return Trained(run, losses, params0, grads0)


def kernel_calls(run) -> int:
    """Count ``tpu_custom_call``s in the compiled train step (the step is
    in the compile cache, so this does not compile it again)."""
    fn = next(iter(run.step_fn.compiled.values()))
    batch = {k: jnp.asarray(v) for k, v
             in run.dataset.batch_at(0, run.spec.batch_size).items()}
    if not run.use_znorm_cache:
        batch.pop("sample_ids", None)
    text = fn.lower(run.state, batch).compile().as_text()
    n = text.count("tpu_custom_call")
    check(n > 0, "the compiled train step holds no tpu_custom_call: the "
                 "Pallas kernels did not run natively")
    return n


def compare_losses(losses, ref, *, rtol: float = LOSS_RTOL,
                   first_exact: bool = True) -> float:
    """Largest |loss - ref|; raise unless step 0 is equal (when
    ``first_exact``) and every step is within ``rtol``."""
    diffs = [abs(a - b) for a, b in zip(losses, ref)]
    check(len(losses) == len(ref), f"{len(losses)} vs {len(ref)} steps")
    if first_exact:
        check(losses[0] == ref[0],
              f"step-0 losses differ: {losses[0]!r} vs {ref[0]!r}; the "
              f"forward pass is exact and must agree")
    for i, (a, b) in enumerate(zip(losses, ref)):
        check(abs(a - b) <= rtol * abs(b),
              f"step {i}: loss {a!r} vs reference {b!r} differ by more "
              f"than rtol={rtol}")
    return max(diffs)


def leaf_rel_diffs(grads, ref) -> list:
    """[(||g - r|| / ||r||, leaf path)] over leaves, largest first."""
    out = []
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(ref)):
        r = np.asarray(r, np.float64)
        num = float(np.linalg.norm(np.asarray(g, np.float64) - r))
        den = float(np.linalg.norm(r))
        rel = num / den if den else (0.0 if num == 0 else math.inf)
        out.append((rel, jax.tree_util.keystr(path)))
    return sorted(out, reverse=True)


def compare_grads(grads, ref, *, rtol: float = GRAD_RTOL) -> dict:
    """Check step-0 gradients against a reference; return the readings.

    ``rel``/``leaf``: the worst leaf; ``next``: the two after it.
    ``controls``: the worst-leaf reading for faults the check exists to
    catch (every leaf halved, every leaf zeroed); each must exceed
    ``rtol``, or the check could not fail."""
    diffs = leaf_rel_diffs(grads, ref)
    (rel, leaf), rest = diffs[0], diffs[1:3]
    controls = {
        "halved": leaf_rel_diffs(jax.tree.map(lambda g: 0.5 * g, grads),
                                 ref)[0][0],
        "zeroed": leaf_rel_diffs(jax.tree.map(np.zeros_like, grads),
                                 ref)[0][0]}
    for name, c in controls.items():
        check(c > rtol, f"control {name!r} reads {c!r}, within "
                        f"rtol={rtol}: the gradient check cannot fail")
    check(rel <= rtol, f"step-0 gradients differ by {rel!r} (relative "
                       f"L2, leaf {leaf}; next {rest}), more than "
                       f"rtol={rtol}")
    return {"rel": rel, "leaf": leaf, "next": rest, "controls": controls}


def reference_phase(arch: str, reduced: bool, trained: Trained, *,
                    seq: int = SEQ):
    """Same spec and seed on the jnp backward; return (ref losses, largest
    loss difference, gradient readings)."""
    ref = train_phase(arch, reduced, kernel=KernelConfig(backend="jnp"),
                      seq=seq)
    grads = compare_grads(trained.grads0, ref.grads0)
    return ref.losses, compare_losses(trained.losses, ref.losses), grads


def serve_phase(run, *, n_requests: int = N_REQUESTS,
                prompt_len: int = PROMPT_LEN, max_new: int = MAX_NEW):
    """Serve greedy requests from the run's weights; return (outputs,
    number that equal a solo ``run.generate``)."""
    vocab = run.cfg.vocab_size
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, size=prompt_len, dtype=np.int32)
               for _ in range(n_requests)]
    with run.serve(max_slots=4, page_size=16, max_len=128).start() as sess:
        handles = [sess.submit(p, max_new=max_new) for p in prompts]
        outs = [h.result(timeout=600) for h in handles]
    for i, toks in enumerate(outs):
        check(len(toks) == max_new,
              f"request {i} returned {len(toks)} tokens, not {max_new}")
        check(all(0 <= t < vocab for t in toks),
              f"request {i} returned out-of-vocab tokens: {toks}")
    solo = [np.asarray(run.generate(jnp.asarray(p)[None], max_new))[0]
            .tolist() for p in prompts]
    return outs, sum(o == s for o, s in zip(outs, solo))


def _device_line() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _print_peaks(label: str) -> None:
    peaks = [peak_bytes(d) for d in jax.local_devices()]
    print(f"[{label}] peak device memory (bytes, per device): {peaks}",
          flush=True)


def _print_grads(label: str, g: dict) -> None:
    print(f"[{label}] step-0 gradient, worst leaf relative L2 difference: "
          f"{g['rel']!r} at {g['leaf']} (rtol {GRAD_RTOL}; next "
          f"{g['next']}); "
          f"controls rejected: {g['controls']}", flush=True)


def one_chip(arch: str) -> None:
    trained = train_phase(arch, reduced=False)
    print(f"[train] {arch} B={BATCH} S={SEQ} losses: {trained.losses}",
          flush=True)
    print(f"[train] tpu_custom_calls in the compiled step: "
          f"{kernel_calls(trained.run)}", flush=True)
    _print_peaks("train")

    ref, diff, grads = reference_phase(arch, False, trained)
    print(f"[reference] jnp backward losses: {ref}", flush=True)
    print(f"[reference] largest |loss difference|: {diff!r} "
          f"(step 0 equal, rtol {LOSS_RTOL})", flush=True)
    _print_grads("reference", grads)
    _print_peaks("reference")

    outs, same = serve_phase(trained.run)
    for i, toks in enumerate(outs):
        print(f"[serve] request {i}: {len(toks)} tokens {toks}", flush=True)
    print(f"[serve] {same}/{len(outs)} requests equal a solo "
          f"run.generate", flush=True)
    _print_peaks("serve")


def sharded_linear_phase(arch: str, reduced: bool, *, kernel=None,
                         seq: int = SEQ) -> dict:
    """One sampled linear (d_model -> 2 d_model, the run's batch and
    sequence) on the (data 2, model 2) mesh against one device, from the
    same inputs and key: its plan (row norms per data shard) and the
    partitioned dW contraction with its sum over the data axes must give
    the one-device input and weight gradients.  Return the gradient
    readings."""
    d = get_config(arch, reduced=reduced).d_model
    cfg = WTACRSConfig(kind="wta_crs", budget=0.3, min_rows=4)
    if kernel is not None:
        cfg = cfg.with_kernel(kernel)
    kh, kw, kg, key = jax.random.split(jax.random.PRNGKey(0), 4)
    h = jax.random.normal(kh, (BATCH, seq, d), jnp.bfloat16)
    w = jax.random.normal(kw, (d, 2 * d), jnp.float32) / math.sqrt(d)
    g = jax.random.normal(kg, (BATCH, seq, 2 * d), jnp.float32)

    def loss(h, w):
        z = wtacrs_linear(h, w.astype(h.dtype), key, cfg=cfg)
        return jnp.sum(z.astype(jnp.float32) * g)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1)))
    single = jax.device_get(grad(h, w))
    mesh = mesh_lib.make_host_mesh(2)
    with jax.set_mesh(mesh):
        sharded = jax.device_get(grad(
            jax.device_put(h, NamedSharding(mesh, P("data"))),
            jax.device_put(w, NamedSharding(mesh, P(None, "model")))))
    return compare_grads(list(sharded), list(single))


def four_chip_phase(arch: str, reduced: bool, *, kernel=None,
                    seq: int = SEQ):
    """The spec on a (data 2, model 2) mesh, then on one device; return
    both.  The mesh run's peaks are printed before the second run."""
    check(len(jax.devices()) == 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    sharded = train_phase(arch, reduced, kernel=kernel, mesh="host",
                          model_parallel=2, seq=seq)
    print(f"[four-chips] mesh {dict(sharded.run.mesh.shape)} losses: "
          f"{sharded.losses}", flush=True)
    _print_peaks("four-chips")
    single = train_phase(arch, reduced, kernel=kernel, seq=seq)
    print(f"[one-chip] losses: {single.losses}", flush=True)
    return sharded, single


def compare_four(sharded: Trained, single: Trained) -> dict:
    """The mesh run must start from the one-device run's weights
    (bit-equal) and keep its losses within ``LOSS_RTOL``.  Its step-0
    gradients are returned per leaf, not checked: the mesh rounds the
    forward differently, and at full width a sound run's step-0 gradient
    moves by more than its own norm when every weight moves by a relative
    1e-6, so no bound separates a sound run from a faulty one (the
    sharded sampled path is checked by ``sharded_linear_phase``, from
    identical inputs).  Return the step-0 loss difference, the largest
    loss difference and every leaf's reading."""
    init_diff = max(float(np.max(np.abs(a - b))) for a, b in zip(
        jax.tree.leaves(sharded.params0), jax.tree.leaves(single.params0)))
    check(init_diff == 0, f"the mesh and one-device runs start from "
                          f"different weights (max |diff| {init_diff!r})")
    return {"step0_loss_diff": abs(sharded.losses[0] - single.losses[0]),
            "loss_diff": compare_losses(sharded.losses, single.losses,
                                        first_exact=False),
            "leaves": leaf_rel_diffs(sharded.grads0, single.grads0)}


def four_chips(arch: str) -> None:
    lin = sharded_linear_phase(arch, False)
    _print_grads("sharded-linear", lin)
    res = compare_four(*four_chip_phase(arch, False))
    print("[four-chips] initial weights bit-equal to one chip", flush=True)
    print(f"[four-chips] step-0 |loss difference| vs one chip: "
          f"{res['step0_loss_diff']!r}; largest |loss difference|: "
          f"{res['loss_diff']!r} (rtol {LOSS_RTOL})", flush=True)
    print(f"[four-chips] step-0 gradient vs one chip, worst leaves "
          f"(reported, not checked): {res['leaves'][:3]}", flush=True)
    _print_peaks("one-chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded training and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found platform {platform!r}, not 'tpu'; "
              f"this test runs only on a TPU", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    dev = _device_line()
    print(f"device_kind: {dev['kind']}  devices: {dev['count']}", flush=True)
    print(f"jax {jax.__version__}  libtpu {metadata.version('libtpu')}",
          flush=True)

    if args.four_chips:
        four_chips(ARCH)
    else:
        one_chip(ARCH)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
