"""Paper Fig. 3 + Theorem 2 + variance analysis benchmarks.

  * theorem2_condition: fraction of (layer, sample) column-row
    distributions from a live model where Eq. 7 holds at k = 0.3|D| —
    the paper's Fig. 3 claim that the condition holds "for most layers".
  * variance_reduction: measured Var[WTA-CRS]/Var[CRS] at budget 0.3/0.1
    on activation-shaped matrices (paper: WTA-CRS strictly lower).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import common
from benchmarks.common import emit
from repro.configs import get_config
from repro.core import (column_row_probabilities, crs_variance,
                        empirical_estimator_stats, registered_estimators,
                        theorem2_condition)
from repro.core.config import EstimatorKind, WTACRSConfig
from repro.models import common as cm
from repro.models import registry


def _finetuned_model():
    """Briefly fine-tuned reduced model + a padded batch (the paper pads
    to max length, Appendix F — padding drives Eq. 3's concentration)."""
    import numpy as np
    from repro.train import data, optim
    from repro.launch import train_steps

    cfg = get_config("qwen2.5-3b", reduced=True)
    ds = data.SyntheticLM(vocab_size=cfg.vocab_size, seq_len=64,
                          n_samples=32, seed=3, branching=2)
    state = train_steps.init_train_state(cfg, jax.random.PRNGKey(0))
    step = jax.jit(train_steps.make_train_step(
        cfg, cm.Policy(), optim.AdamWConfig(),
        optim.linear_warmup_constant(3e-3, warmup=5)))
    it = ds.epoch(8)
    for s in range(common.smoke_or(6, 25)):
        try:
            b = next(it)
        except StopIteration:
            it = ds.epoch(8, shuffle_seed=s)
            b = next(it)
        state, _ = step(state, {k: jnp.asarray(v) for k, v in b.items()
                                if k != "sample_ids"})
    batch = {k: jnp.asarray(v) for k, v in ds.batch(np.arange(4)).items()}
    # ~70% padding, like GLUE sentences padded to 128 (Appendix F)
    batch["tokens"] = batch["tokens"].at[:, 20:].set(0)
    batch["labels"] = batch["labels"].at[:, 20:].set(-100)   # pad mask
    return state["params"], batch, cfg


def run():
    import numpy as np

    params, batch, cfg = _finetuned_model()
    # Eq. 3's distribution is p_i ∝ ||H_i||*||dZ_i||.  Post-RMSNorm rows
    # have ~constant norms by construction, so the concentration the
    # paper measures (Fig. 3) lives in the GRADIENT norms — padded
    # positions carry no loss.  Collect per-token ||dZ||^2 through the
    # gradient-norm tap with a per-token (R,B,S) znorm input.
    from repro.core.config import WTACRSConfig, EstimatorKind
    from repro.train import znorm as znorm_lib

    tags = znorm_lib.collect_linear_tags(cfg)
    b, s = batch["tokens"].shape
    znorms = {t: jnp.ones((cfg.n_repeats, b, s), jnp.float32)
              for t in tags}
    pol = cm.Policy(wtacrs=WTACRSConfig(kind=EstimatorKind.WTA_CRS,
                                        budget=0.3, min_rows=4))
    (_, _), gz = jax.value_and_grad(
        lambda p_, z_: registry.loss_fn(cfg, p_, batch, pol,
                                        key=jax.random.PRNGKey(9),
                                        znorms=z_),
        argnums=1, has_aux=True)(params, znorms)

    holds, total, masses = 0, 0, []
    for t in tags[:common.smoke_or(2, 6)]:
        zsq = np.asarray(gz[t])                     # (R, B, S) squared
        for r in range(zsq.shape[0]):
            for bi in range(min(2, b)):
                z = np.sqrt(np.maximum(zsq[r, bi], 0.0))
                if z.sum() <= 0:
                    continue
                p = column_row_probabilities(
                    jnp.ones((s,)), jnp.asarray(z))
                k = max(2, int(0.3 * s))
                ok, _, mass = theorem2_condition(p, k)
                holds += int(ok)
                masses.append(float(mass))
                total += 1
    emit("fig3_theorem2_condition_holds", 0.0,
         f"frac={holds / max(total, 1):.3f} over {total} live Eq.3 "
         f"distributions (grad-norm term, padded fine-tuned batch); "
         f"mean_mass_at_cstar={np.mean(masses):.3f}")

    # power-law column scales (the concentration real activations show)
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (32, 256))
    zipf = 1.0 / (1.0 + jnp.arange(256, dtype=jnp.float32)) ** 0.8
    x = x * jax.random.permutation(jax.random.fold_in(key, 1),
                                   zipf * 256 / jnp.sum(zipf))[None, :]
    y = jax.random.normal(jax.random.fold_in(key, 2), (256, 64))
    trials = common.smoke_or(200, 1500)
    for budget in (0.3, 0.1):
        _, v_crs = empirical_estimator_stats(
            x, y, WTACRSConfig(kind=EstimatorKind.CRS, budget=budget),
            jax.random.PRNGKey(4), trials)
        _, v_wta = empirical_estimator_stats(
            x, y, WTACRSConfig(kind=EstimatorKind.WTA_CRS, budget=budget),
            jax.random.PRNGKey(5), trials)
        emit(f"thm2_variance_ratio@{budget}", 0.0,
             f"var_wta/var_crs={float(v_wta / v_crs):.3f}")

    p = column_row_probabilities(jnp.linalg.norm(x, axis=0),
                                 jnp.linalg.norm(y, axis=1))
    emit("crs_closed_form_variance", 0.0,
         f"value={float(crs_variance(x, y, p, 76)):.3g}")

    # registry sweep: variance of EVERY registered unbiased estimator
    # (incl. ones added outside core, e.g. stratified_crs) vs CRS at 0.3
    _, v_ref = empirical_estimator_stats(
        x, y, WTACRSConfig(kind="crs", budget=0.3),
        jax.random.PRNGKey(6), trials)
    for name, spec in sorted(registered_estimators().items()):
        if spec.biased:
            continue
        _, v = empirical_estimator_stats(
            x, y, WTACRSConfig(kind=name, budget=0.3),
            jax.random.PRNGKey(6), trials)
        emit(f"registry_variance_vs_crs@{name}", 0.0,
             f"var/var_crs={float(v / v_ref):.3f}")
