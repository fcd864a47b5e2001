"""repro.api façade: RunSpec validation footguns, hand-wired
equivalence (bit-matching loss traces), the microbatch+znorm-cache
lift, and checkpoint→restore round-trips that keep the controller band
state (no budget-trajectory reset)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import DataSpec, Run, RunSpec
from repro.configs import get_config
from repro.core import ESSProportional, PolicyRules, Rule, WTACRSConfig
from repro.core.config import EstimatorKind, NormSource
from repro.launch import train_steps
from repro.models import common as cm
from repro.train import checkpoint, data, optim, znorm

KEY = jax.random.PRNGKey(0)
ARCH = "qwen2.5-3b"
DATA = DataSpec(seq_len=16, n_samples=32)


def _plain_policy(budget=0.3):
    return cm.Policy(wtacrs=WTACRSConfig(kind=EstimatorKind.WTA_CRS,
                                         budget=budget, min_rows=2))


def _cached_policy(budget=0.3):
    return cm.Policy(wtacrs=WTACRSConfig(
        kind=EstimatorKind.WTA_CRS, budget=budget, min_rows=2,
        norm_source=NormSource.CACHED_GRAD))


def _ctrl_policy(warmup=1):
    return cm.Policy(rules=PolicyRules.of(Rule.of(
        "*mlp*",
        WTACRSConfig(kind=EstimatorKind.WTA_CRS, budget=0.3, min_rows=2,
                     norm_source=NormSource.CACHED_GRAD),
        ESSProportional(b_min=0.1, b_max=0.6, levels=6, warmup=warmup))))


def _spec(policy, **kw):
    kw.setdefault("arch", ARCH)
    kw.setdefault("steps", 4)
    kw.setdefault("batch_size", 4)
    kw.setdefault("data", DATA)
    return RunSpec(policy=policy, **kw)


class TestRunSpecValidation:
    def test_cached_grad_without_cache_rejected_at_construction(self):
        with pytest.raises(ValueError, match="CACHED_GRAD"):
            _spec(_cached_policy(), znorm_cache=False)

    def test_controller_without_cache_rejected_at_construction(self):
        # ACTIVATION_ONLY + controller: the cache is needed purely for
        # the tap statistics, and forcing it off is still rejected
        pol = cm.Policy(rules=PolicyRules.of(Rule.of(
            "*mlp*",
            WTACRSConfig(kind=EstimatorKind.WTA_CRS, budget=0.3,
                         min_rows=2),
            ESSProportional(b_min=0.1, b_max=0.6))))
        with pytest.raises(ValueError, match="controllers"):
            _spec(pol, znorm_cache=False)

    def test_controller_without_stats_rejected_at_construction(self):
        with pytest.raises(ValueError, match="budget_stats"):
            _spec(_ctrl_policy(), budget_stats=False)

    def test_wiring_derived_from_policy(self):
        s = _spec(_plain_policy())
        assert not s.use_znorm_cache and not s.track_budget_stats
        s = _spec(_cached_policy())
        assert s.use_znorm_cache and not s.track_budget_stats
        s = _spec(_ctrl_policy())
        assert s.use_znorm_cache and s.track_budget_stats

    def test_explicit_cache_warms_under_activation_only(self):
        # znorm_cache=True with an ACTIVATION_ONLY policy: legal (warms
        # the cache through the tap without driving probabilities)
        assert _spec(_plain_policy(), znorm_cache=True).use_znorm_cache

    def test_basic_shape_errors(self):
        with pytest.raises(ValueError, match="microbatches"):
            _spec(_plain_policy(), batch_size=4, microbatches=3)
        with pytest.raises(ValueError, match="lr_schedule"):
            _spec(_plain_policy(), lr_schedule="nope")
        with pytest.raises(ValueError, match="checkpoint_dir"):
            _spec(_plain_policy(), checkpoint_every=5)
        with pytest.raises(ValueError, match="n_samples"):
            _spec(_plain_policy(), batch_size=64)


class TestHandWiredEquivalence:
    """The façade must be sugar, not a fork: with the same seed and the
    same batches its per-step loss trace bit-matches the hand-wired
    ``make_scheduled_train_step`` path."""

    def _hand_wired_losses(self, policy, spec, use_cache):
        cfg = get_config(spec.arch, reduced=True)
        tags = (znorm.collect_linear_tags(cfg, policy=policy)
                if use_cache else None)
        # jitted, as Run.init builds it
        state = jax.jit(lambda key: train_steps.init_train_state(
            cfg, key, znorm_tags=tags, n_dataset=spec.data.n_samples))(
            jax.random.PRNGKey(spec.seed))
        step = train_steps.make_scheduled_train_step(
            cfg, policy, spec.optimizer, spec.make_lr_schedule(),
            use_znorm_cache=use_cache, microbatches=1, data_axes=None)
        ds = spec.data.build(cfg)
        losses = []
        for s in range(spec.steps):
            b = {k: jnp.asarray(v)
                 for k, v in ds.batch_at(s, spec.batch_size).items()}
            if not use_cache:
                b.pop("sample_ids")
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        return losses

    def test_loss_trace_bit_matches_without_cache(self):
        pol = _plain_policy()
        spec = _spec(pol)
        ref = self._hand_wired_losses(pol, spec, use_cache=False)
        run = Run(spec)
        run.fit()
        assert [h["loss"] for h in run.history] == ref

    def test_loss_trace_bit_matches_with_cache(self):
        pol = _cached_policy()
        spec = _spec(pol)
        ref = self._hand_wired_losses(pol, spec, use_cache=True)
        run = Run(spec)
        run.fit()
        assert [h["loss"] for h in run.history] == ref


class TestMicrobatchZnormCache:
    """The ``microbatches > 1`` + ``use_znorm_cache`` combination the
    low level used to reject: per-microbatch gather/scatter inside the
    accumulation scan."""

    def _one_sampled_layer_policy(self):
        # exactly one sampled tag: every dZ upstream of it is exact, so
        # the microbatched taps relate to the full-batch taps by the
        # loss-normalization factor alone
        return cm.Policy(
            wtacrs=WTACRSConfig(kind=EstimatorKind.EXACT),
            rules=PolicyRules.of(
                ("*mlp_wo", WTACRSConfig(
                    kind=EstimatorKind.WTA_CRS, budget=0.5, min_rows=2,
                    norm_source=NormSource.CACHED_GRAD))))

    def test_lifted_and_taps_scale_like_per_microbatch_loss(self):
        cfg = get_config(ARCH, reduced=True)
        pol = self._one_sampled_layer_policy()
        tags = znorm.collect_linear_tags(cfg, policy=pol)
        assert tags, "need at least one sampled tag"
        state = train_steps.init_train_state(cfg, KEY, znorm_tags=tags,
                                             n_dataset=8)
        ds = data.SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16,
                              n_samples=8, seed=0, branching=2)
        batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0, 4).items()}
        ids = np.asarray(batch["sample_ids"])

        step1 = jax.jit(train_steps.make_train_step(
            cfg, pol, optim.AdamWConfig(),
            optim.linear_warmup_constant(1e-3), use_znorm_cache=True,
            microbatches=1))
        step2 = jax.jit(train_steps.make_train_step(
            cfg, pol, optim.AdamWConfig(),
            optim.linear_warmup_constant(1e-3), use_znorm_cache=True,
            microbatches=2))
        s1, m1 = step1(state, batch)
        s2, m2 = step2(state, batch)
        assert np.isfinite(float(m2["loss"]))
        # equal-sized microbatches with fully-valid labels: the mean of
        # the two microbatch losses IS the full-batch loss
        np.testing.assert_allclose(float(m2["loss"]), float(m1["loss"]),
                                   rtol=1e-5)
        for t in tags:
            c1 = np.asarray(s1["znorm"][t])[:, ids]
            c2 = np.asarray(s2["znorm"][t])[:, ids]
            assert not np.allclose(c2, 1.0), "cache never written"
            # microbatch loss normalizes over half the tokens -> dZ (and
            # the tap norms) scale by exactly the microbatch count
            np.testing.assert_allclose(c2, 2.0 * c1, rtol=1e-3)

    def test_budget_stats_cadence_independent_of_microbatches(self):
        """Controller warmup/EMA timing is a function of optimizer
        steps, not the microbatch (memory) knob: ONE stats update per
        step, and — the atoms being normalized — the same stat values
        as the single-batch step up to float rounding."""
        cfg = get_config(ARCH, reduced=True)
        pol = self._one_sampled_layer_policy()
        tags = znorm.collect_linear_tags(cfg, policy=pol)
        state = train_steps.init_train_state(cfg, KEY, znorm_tags=tags,
                                             n_dataset=8,
                                             budget_stats=True)
        ds = data.SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16,
                              n_samples=8, seed=0, branching=2)
        batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0, 4).items()}
        mk = lambda m: jax.jit(train_steps.make_train_step(
            cfg, pol, optim.AdamWConfig(),
            optim.linear_warmup_constant(1e-3), use_znorm_cache=True,
            microbatches=m))
        s1, _ = mk(1)(state, batch)
        s2, _ = mk(2)(state, batch)
        for t in tags:
            assert float(s2["budget_stats"][t][znorm.STAT_COUNT]) == 1.0
            np.testing.assert_allclose(
                np.asarray(s2["budget_stats"][t]),
                np.asarray(s1["budget_stats"][t]), rtol=1e-4, atol=1e-6)

    def test_facade_runs_microbatched_cache(self):
        run = Run(_spec(_cached_policy(), microbatches=2))
        run.fit()
        assert np.isfinite(run.history[-1]["loss"])


class TestScheduleState:
    def test_json_roundtrip(self):
        st = train_steps.ScheduleState(
            budgets={0: 0.3, 2: 0.5}, replans=3,
            trajectory=[{"step": 0, "rule": 0, "pattern": "*",
                         "budget": 0.3, "prev": None}])
        assert train_steps.ScheduleState.from_json(st.to_json()) == st

    def test_version_mismatch_rejected(self):
        bad = train_steps.ScheduleState().to_json()
        bad["version"] = 99
        with pytest.raises(ValueError, match="version"):
            train_steps.ScheduleState.from_json(bad)

    def test_restored_budgets_must_match_policy_rules(self):
        cfg = get_config(ARCH, reduced=True)
        st = train_steps.ScheduleState(budgets={7: 0.3})
        with pytest.raises(ValueError, match="policy changed"):
            train_steps.make_scheduled_train_step(
                cfg, _ctrl_policy(), optim.AdamWConfig(),
                optim.linear_warmup_constant(1e-3),
                schedule_state=st, use_znorm_cache=True)

    def test_initial_pin_recorded_on_first_invocation_not_step0(self):
        """Regression: initial controller pins were only logged when
        ``step == 0``, so a run resumed at step > 0 without a restored
        trajectory never recorded its baseline."""
        cfg = get_config(ARCH, reduced=True)
        pol = _ctrl_policy(warmup=10)     # holds: no replan noise
        tags = znorm.collect_linear_tags(cfg, policy=pol)
        state = train_steps.init_train_state(cfg, KEY, znorm_tags=tags,
                                             n_dataset=8,
                                             budget_stats=True)
        state = dict(state, step=jnp.asarray(5, jnp.int32))
        step = train_steps.make_scheduled_train_step(
            cfg, pol, optim.AdamWConfig(),
            optim.linear_warmup_constant(1e-3), use_znorm_cache=True)
        ds = data.SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16,
                              n_samples=8, seed=0, branching=2)
        batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0, 4).items()}
        step(state, batch)
        assert step.budget_trajectory, "no initial pin recorded"
        rec = step.budget_trajectory[0]
        assert rec["step"] == 5 and rec["prev"] is None

    def test_host_step_known_for_the_counter_it_returned(self):
        """The driver knows the counter its last call returned without
        reading it from the device; any other counter is read."""
        run = Run(_spec(_plain_policy()))
        run.fit(steps=2)
        fn = run.step_fn
        assert fn._last_step[0] is run.state["step"]
        assert fn.host_step(run.state) == 2 == int(run.state["step"])
        assert [h["step"] for h in run.history] == [0, 1]
        run.state = dict(run.state, step=jnp.asarray(9, jnp.int32))
        assert fn.host_step(run.state) == 9
        run.step(run.dataset.batch_at(9, 4))
        assert run.history[-1]["step"] == 9
        assert fn.host_step(run.state) == 10 == int(run.state["step"])


class TestRunStateRecord:
    def test_missing_record_is_none(self):
        assert checkpoint.unpack_run_state({"metadata": {}}) is None
        assert checkpoint.unpack_run_state({}) is None

    def test_version_mismatch_rejected(self):
        meta = checkpoint.pack_run_state({"version": 1, "budgets": {},
                                          "replans": 0, "trajectory": []})
        meta[checkpoint.RUN_STATE_KEY]["version"] = 99
        with pytest.raises(ValueError, match="version"):
            checkpoint.unpack_run_state({"metadata": meta})


class TestCheckpointRestore:
    def test_kill_resume_is_bit_faithful_and_trajectory_continues(
            self, tmp_path):
        """A controller-carrying run killed mid-flight and resumed via
        Run.restore must reproduce the uninterrupted run exactly:
        params/opt/znorm/budget_stats bit-equal, metrics history equal,
        and the budget trajectory CONTINUED from the restored band
        position (no reset to initial_budget)."""
        pol = _ctrl_policy(warmup=1)
        base = dict(policy=pol, steps=6, batch_size=4, data=DATA,
                    arch=ARCH)
        ref = Run(RunSpec(**base))
        ref.fit()
        # the reference controller actually moved, so a reset would show
        changes = [r for r in ref.schedule_state.trajectory
                   if r["prev"] is not None]
        assert changes, "controller never moved; test is vacuous"

        spec = RunSpec(**base, checkpoint_dir=str(tmp_path))
        a = Run(spec)
        a.fit(steps=3)
        a.save()
        b = Run.restore(spec)
        assert int(b.state["step"]) == 3
        # restored band position, not initial_budget
        assert b.schedule_state.budgets == {
            i: next(r["budget"] for r in
                    reversed(ref.schedule_state.trajectory)
                    if r["rule"] == i and r["step"] < 3)
            for i in b.schedule_state.budgets}
        b.fit()

        assert (b.schedule_state.trajectory
                == ref.schedule_state.trajectory)
        assert ([h["loss"] for h in b.history]
                == [h["loss"] for h in ref.history])
        eq = jax.tree.map(
            lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()),
            ref.state, b.state)
        assert all(jax.tree.leaves(eq))

    def test_resume_without_checkpoint_starts_fresh(self, tmp_path):
        spec = _spec(_plain_policy(),
                     checkpoint_dir=str(tmp_path / "none"))
        run = Run.resume(spec)
        assert run.state is None and run.history == []

    def test_report_after_restore_covers_whole_run(self, tmp_path):
        spec = _spec(_ctrl_policy(warmup=1), steps=4,
                     checkpoint_dir=str(tmp_path))
        a = Run(spec)
        a.fit(steps=2)
        a.save()
        b = Run.restore(spec)
        b.fit()
        rep = b.report()
        assert "4 steps" in rep and "§Budgets" in rep


class TestQuickstartBudget:
    def test_quickstart_fits_in_30_non_argparse_lines(self):
        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "examples", "quickstart.py")
        with open(path) as f:
            src = f.read()
        # strip the module docstring
        body = src.split('"""')[2]
        n = 0
        for line in body.splitlines():
            s = line.strip()
            if (not s or s.startswith("#") or "argparse" in s
                    or s.startswith("ap.") or s.startswith("args =")):
                continue
            n += 1
        assert n <= 30, f"quickstart.py has {n} non-argparse code lines"
