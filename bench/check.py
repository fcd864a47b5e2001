"""The comparisons that decide ``correct``, and the reference training
driver they compare against.

Training: the program's first ``check_steps`` optimizer steps (the
warm-up, through the window's own call and feed) against the plain
reference from the same weights, batches and sampling keys.  A cell
compares the numbers its workload file lists under ``limits``:

* ``loss_gap``: |loss - ref loss| / |ref loss| over the steps;
* ``grad_norm_gap``: the first step's gradient as the optimizer got it
  (AdamW's first moment after one step over 1 - b1), per leaf
  | ||g|| - ||g_ref|| | over the larger of that leaf's ||g_ref|| and the
  median leaf's;
* ``update_norm_gap``: the same of the parameters' change over the
  steps, ||p_n - p_0||;
* ``grad_norm_gap_median``, ``update_norm_gap_median``: the median leaf's
  gap instead of the worst.

Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of the leaf numbers (rounding moves them under
Adam).
"""
from __future__ import annotations

import math

import numpy as np

FLOOR = 1e-3


def leaf_norms(tree) -> dict:
    """{leaf path: L2 norm} computed on the device in one call."""
    import jax
    import jax.numpy as jnp
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return dict(zip(paths, map(float, jax.device_get(norms))))


def used_leaves(ref_grad: dict) -> list:
    med = float(np.median(list(ref_grad.values())))
    return [k for k, v in ref_grad.items() if v >= FLOOR * med]


def leaf_gaps(prog: dict, ref: dict, leaves: list) -> dict:
    """{leaf: | ||prog|| - ||ref|| | / max(||ref||, median leaf's)}."""
    med = float(np.median([ref[k] for k in leaves]))
    return {k: (abs(prog[k] - ref[k]) / max(ref[k], med)
                if math.isfinite(prog[k]) else math.inf) for k in leaves}


def norm_gap(prog: dict, ref: dict, leaves: list):
    """(worst gap, its leaf)."""
    gaps = leaf_gaps(prog, ref, leaves)
    at = max(gaps, key=gaps.get)
    return gaps[at], at


def loss_gap(prog: list, ref: list) -> float:
    gaps = [abs(a - b) / abs(b) if math.isfinite(a) else math.inf
            for a, b in zip(prog, ref)]
    return max(gaps) if len(prog) == len(ref) else math.inf


def compare_train(prog: dict, ref: dict) -> dict:
    """{number: value} from the program's and the reference's readings
    (``losses``, ``grad1`` and ``change`` norms by leaf)."""
    leaves = used_leaves(ref["grad1"])
    g, g_at = norm_gap(prog["grad1"], ref["grad1"], leaves)
    u, u_at = norm_gap(prog["change"], ref["change"], leaves)
    med = lambda x: float(np.median(list(x.values())))  # noqa: E731
    return {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
            "grad_norm_gap": g, "update_norm_gap": u,
            "grad_norm_gap_median": med(leaf_gaps(prog["grad1"],
                                                  ref["grad1"], leaves)),
            "update_norm_gap_median": med(leaf_gaps(prog["change"],
                                                    ref["change"], leaves)),
            "_where": {"grad_norm_gap": g_at, "update_norm_gap": u_at,
                       "leaves_used": len(leaves),
                       "leaves": len(ref["grad1"])}}


def reference_train(model, cfgd: dict, init_key, batches: list,
                    step_keys: list, lr: float, budget, prec_mode: str,
                    rows: int, drop_half: bool = False) -> dict:
    """Run the plain reference for len(batches) AdamW steps from the
    weights ``model.init`` makes from ``init_key``.  ``budget`` None =
    exact dW.  ``rows``: sequences per block (gradients are summed
    block by block, so any batch fits).  ``drop_half``: a planted fault,
    the mean taken over the first half of each batch only."""
    import jax
    import jax.numpy as jnp

    from ref_common import Precision, adamw, nll_sum

    prec = Precision(prec_mode)
    b_all, s = batches[0]["tokens"].shape

    @jax.jit
    def grad_block(params, tokens, labels, step_key, r0):
        def f(p):
            lg = model.logits(cfgd, p, tokens, prec, budget=budget,
                              step_key=step_key, rows=(r0, b_all))
            return nll_sum(lg, labels)
        return jax.value_and_grad(f)(params)

    @jax.jit
    def update(params, grads, m, v, count, scale):
        grads = jax.tree.map(lambda g: g * scale, grads)
        return adamw(params, grads, m, v, count, lr), grads

    p0 = jax.jit(lambda k: model.init(cfgd, k))(init_key)
    params = p0
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.float32)
    n_rows = b_all // 2 if drop_half else b_all
    losses, grad1 = [], None
    for bt, sk in zip(batches, step_keys):
        tot, acc = 0.0, None
        for r0 in range(0, n_rows, rows):
            l, g = grad_block(params, jnp.asarray(bt["tokens"][r0:r0 + rows]),
                              jnp.asarray(bt["labels"][r0:r0 + rows]),
                              sk, jnp.int32(r0))
            tot = tot + l
            acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
        n_tok = float(n_rows * s)
        (params, m, v, count), g = update(params, acc, m, v, count,
                                          1.0 / n_tok)
        losses.append(float(tot) / n_tok)
        if grad1 is None:
            grad1 = leaf_norms(g)
    change = leaf_norms(jax.tree.map(jnp.subtract, params, p0))
    return {"losses": losses, "grad1": grad1, "change": change}
