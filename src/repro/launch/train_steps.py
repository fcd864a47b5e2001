"""Step builders: pjit train/prefill/serve steps + shard_map DP variant.

These are the functions the trainer jits and the dry-run lowers.  All of
them are pure (state, batch) -> (state, metrics) transformations; the
distribution strategy is carried entirely by in/out shardings (GSPMD) or
shard_map specs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro import optim as optim_lib
from repro import spans
from repro.optim import update as optim_state_update
from repro.core import controller as controller_lib
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as shard_lib
from repro.models import common as cm
from repro.models import registry
from repro.train import compression, optim, znorm


def init_train_state(cfg: ArchConfig, key: jax.Array,
                     znorm_tags=None, n_dataset: int = 0,
                     budget_stats: bool = False,
                     opt=None, opt_ranks=None) -> Dict[str, Any]:
    """``budget_stats``: also track the per-tag controller statistics
    (only useful — and only paid for — when the policy carries adaptive
    budget controllers; see ``repro.core.controller``).

    ``opt``: ``None``/``AdamWConfig`` keeps the legacy dense
    ``AdamWState``; an ``repro.optim.OptimSpec`` initializes the
    path-keyed layout state (its rank-controller statistics ride
    ``budget_stats`` regardless of the znorm flags — they come from the
    optimizer update, not the znorm tap).  ``opt_ranks``: current rank
    per dynamic rule (a resumed driver's band positions)."""
    params, _ = registry.init_params(cfg, key)
    legacy = opt is None or isinstance(opt, optim.AdamWConfig)
    state = {
        "params": params,
        "opt": (optim.adamw_init(params) if legacy
                else optim_lib.init(opt, params, ranks=opt_ranks)),
        "step": jnp.zeros((), jnp.int32),
        "base_key": jax.random.key_data(jax.random.fold_in(key, 7)),
    }
    if znorm_tags:
        state["znorm"] = znorm.init_cache(cfg, znorm_tags, n_dataset)
        if budget_stats:
            state["budget_stats"] = znorm.init_stats(znorm_tags)
    if not legacy:
        rank_stats = optim_lib.init_rank_stats(opt)
        if rank_stats:
            state.setdefault("budget_stats", {}).update(rank_stats)
    return state


def abstract_train_state(cfg: ArchConfig, znorm_tags=None,
                         n_dataset: int = 0, budget_stats: bool = False,
                         opt=None, opt_ranks=None):
    """(ShapeDtypeStructs, logical axes info) without allocation."""
    params, axes = registry.abstract_params(cfg)
    legacy = opt is None or isinstance(opt, optim.AdamWConfig)
    opt_abs = (jax.eval_shape(optim.adamw_init, params) if legacy
               else jax.eval_shape(
                   lambda p: optim_lib.init(opt, p, ranks=opt_ranks),
                   params))
    state = {
        "params": params,
        "opt": opt_abs,
        "step": jax.ShapeDtypeStruct((), jnp.int32),
        "base_key": jax.ShapeDtypeStruct((2,), jnp.uint32),
    }
    if znorm_tags:
        state["znorm"] = {
            t: jax.ShapeDtypeStruct((cfg.n_repeats, n_dataset), jnp.float32)
            for t in znorm_tags}
        if budget_stats:
            state["budget_stats"] = {
                t: jax.ShapeDtypeStruct((znorm.N_STATS,), jnp.float32)
                for t in znorm_tags}
    if not legacy:
        rank_keys = opt.rank_stat_keys()
        if rank_keys:
            state.setdefault("budget_stats", {}).update({
                k: jax.ShapeDtypeStruct((znorm.N_STATS,), jnp.float32)
                for k in rank_keys})
    return state, axes


def train_state_shardings(cfg, state, axes, mesh):
    """Shardings for the full train state (opt mirrors params)."""
    rules = shard_lib.arch_rules(cfg, mesh)
    p_sh = shard_lib.param_shardings(axes, state["params"], mesh,
                                     rules=rules)
    rep = shard_lib.replicated(mesh)
    sh = {
        "params": p_sh,
        "opt": (optim.AdamWState(rep, p_sh, p_sh)
                if isinstance(state["opt"], optim.AdamWState)
                else optim_lib.state_shardings(
                    state["opt"], state["params"], p_sh, rep)),
        "step": rep,
        "base_key": rep,
    }
    if "znorm" in state:
        sh["znorm"] = {t: rep for t in state["znorm"]}
    if "budget_stats" in state:
        sh["budget_stats"] = {t: rep for t in state["budget_stats"]}
    return sh


def make_train_step(cfg: ArchConfig, policy: cm.Policy,
                    opt_cfg,
                    schedule: Callable[[jax.Array], jax.Array],
                    use_znorm_cache: bool = False,
                    microbatches: int = 1,
                    data_axes: Optional[tuple] = None):
    """(state, batch) -> (state, metrics).  Paper-faithful WTA-CRS step.

    ``opt_cfg``: a legacy ``optim.AdamWConfig`` (dense ``AdamWState``,
    unchanged) or an ``repro.optim.OptimSpec`` (path-keyed layout
    state; rank-controller statistics land in
    ``state["budget_stats"]`` under ``optim:rank:*`` keys).

    With ``use_znorm_cache`` the batch must carry ``sample_ids`` and the
    state a ``znorm`` cache; gradient-norm taps refresh it every step
    (Algorithm 1).  Configure the sampled layers with
    ``norm_source=NormSource.CACHED_GRAD`` so the cache actually drives
    the probabilities (ACTIVATION_ONLY ignores it by contract but still
    warms it through the tap).  ``microbatches`` > 1 scans gradient
    accumulation over the leading batch split (activation memory /
    global batch trade); combined with the cache, each microbatch
    gathers the cache columns for its own sample ids and scatters its
    tap back inside the accumulation scan.

    Policies with budget schedules: this builder compiles ONE policy
    resolution (``policy.step`` as given).  Use
    ``make_scheduled_train_step`` to re-resolve per trainer step.

    ``data_axes``: mesh axes carrying the batch dim.  REQUIRED under SPMD
    with microbatches > 1: without an explicit constraint GSPMD may shard
    the microbatch (loop) dim of the reshaped batch across data devices,
    making every device compute multiple shards' tokens (measured 8x FLOP
    inflation on the 16x16 mesh).
    """
    # static per-build: the update only reports captured-energy stats
    # when the spec carries rank-controller rules
    track_rank_energy = (isinstance(opt_cfg, optim_lib.OptimSpec)
                         and bool(opt_cfg.controller_rule_indices()))

    def loss_with_znorms(params, znorms, batch, key):
        with jax.named_scope(spans.FORWARD):
            return registry.loss_fn(cfg, params, batch, policy, key=key,
                                    znorms=znorms)

    def grads_of(params, znorms, batch, key):
        if use_znorm_cache:
            (loss, aux), (gp, gz) = jax.value_and_grad(
                loss_with_znorms, argnums=(0, 1), has_aux=True)(
                params, znorms, batch, key)
        else:
            (loss, aux), gp = jax.value_and_grad(
                loss_with_znorms, argnums=0, has_aux=True)(
                params, None, batch, key)
            gz = None
        return loss, aux, gp, gz

    def train_step(state, batch):
        params = state["params"]
        step = state["step"]
        key = jax.random.wrap_key_data(state["base_key"])
        key = jax.random.fold_in(key, step)

        znorms = None
        if use_znorm_cache and microbatches == 1:
            znorms = znorm.gather(state["znorm"], batch["sample_ids"])
        model_batch = {k: v for k, v in batch.items()
                       if k != "sample_ids"}

        new_cache = new_stats = None
        if microbatches == 1:
            loss, aux, gp, gz = grads_of(params, znorms, model_batch, key)
        else:
            def split(path, x):
                name = str(path[-1].key) if path else ""
                bdim = 1 if name == "positions3" else 0
                b = x.shape[bdim] // microbatches
                y = x.reshape(x.shape[:bdim] + (microbatches, b)
                              + x.shape[bdim + 1:])
                y = jnp.moveaxis(y, bdim, 0)
                if data_axes:
                    parts = [None] * y.ndim
                    parts[bdim + 1] = data_axes   # batch dim after move
                    y = jax.lax.with_sharding_constraint(y, P(*parts))
                return y

            mb = jax.tree_util.tree_map_with_path(split, model_batch)
            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            keys = jax.random.split(key, microbatches)

            if use_znorm_cache:
                # Per-microbatch gather/scatter: each microbatch reads
                # the cache columns for ITS sample ids and scatters its
                # tap back before the next one runs.  Sample ids within
                # a batch are disjoint, so the result is identical to
                # gathering everything from the pre-step cache.
                ids = batch["sample_ids"].reshape(microbatches, -1)
                seq = (model_batch["tokens"].shape[-1]
                       if "tokens" in model_batch else None)
                active = znorm.sampling_active_tags(
                    policy, state["znorm"], seq_len=seq)

                def acc_step(carry, xs):
                    g_acc, loss_acc, cache = carry
                    mb_i, ids_i, k_i = xs
                    zn_i = znorm.gather(cache, ids_i)
                    loss, _, gp_i, gz_i = grads_of(params, zn_i, mb_i, k_i)
                    g_acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32)
                        / microbatches, g_acc, gp_i)
                    cache = znorm.scatter(cache, ids_i, gz_i,
                                          active_tags=active)
                    return (g_acc, loss_acc + loss / microbatches,
                            cache), gz_i

                carry0 = (g0, 0.0, state["znorm"])
                (gp, loss, new_cache), taps = jax.lax.scan(
                    acc_step, carry0, (mb, ids, keys))
                if "budget_stats" in state:
                    # ONE stats update per optimizer step, over the full
                    # batch's taps: the controller EMA/warmup cadence
                    # must not depend on the microbatch (memory) knob.
                    # The stat atoms are scale-invariant (normalized),
                    # so the per-microbatch loss normalization cancels.
                    tap_full = {
                        t: jnp.moveaxis(y, 0, 1).reshape(y.shape[1], -1)
                        for t, y in taps.items()}
                    budgets = {t: policy.config_for(t).budget
                               for t in state["budget_stats"]
                               if not optim_lib.is_rank_stat_key(t)}
                    new_stats = znorm.update_stats(
                        state["budget_stats"], tap_full, budgets,
                        active_tags=active)
            else:
                def acc_step(carry, xs):
                    g_acc, loss_acc = carry
                    mb_i, k_i = xs
                    loss, aux, gp, _ = grads_of(params, None, mb_i, k_i)
                    g_acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32)
                        / microbatches, g_acc, gp)
                    return (g_acc, loss_acc + loss / microbatches), None

                (gp, loss), _ = jax.lax.scan(acc_step, (g0, 0.0),
                                             (mb, keys))
            aux, gz = {}, None

        lr = schedule(step)
        with jax.named_scope(spans.OPTIMIZER):
            if isinstance(opt_cfg, optim_lib.OptimSpec):
                new_params, new_opt, om, rank_energy = optim_state_update(
                    gp, state["opt"], params, lr, opt_cfg)
            else:
                new_params, new_opt, om = optim.adamw_update(
                    gp, state["opt"], params, lr, opt_cfg)
                rank_energy = {}
        new_state = dict(state, params=new_params, opt=new_opt,
                         step=step + 1)
        if use_znorm_cache and microbatches > 1:
            new_state["znorm"] = new_cache
            if new_stats is not None:
                new_state["budget_stats"] = new_stats
        elif use_znorm_cache:
            seq = (model_batch["tokens"].shape[-1]
                   if "tokens" in model_batch else None)
            active = znorm.sampling_active_tags(policy, state["znorm"],
                                                seq_len=seq)
            new_state["znorm"] = znorm.scatter(
                state["znorm"], batch["sample_ids"], gz,
                active_tags=active)
            if "budget_stats" in state:
                # resolved budgets are static per compile, like the
                # shapes they produce
                budgets = {t: policy.config_for(t).budget
                           for t in state["budget_stats"]
                           if not optim_lib.is_rank_stat_key(t)}
                new_state["budget_stats"] = znorm.update_stats(
                    state["budget_stats"], gz, budgets,
                    active_tags=active)
        if track_rank_energy and "budget_stats" in new_state:
            new_state["budget_stats"] = optim_lib.update_rank_stats(
                new_state["budget_stats"], rank_energy)
        metrics = {"loss": loss, "lr": lr, **om}
        return new_state, metrics

    return train_step


@dataclasses.dataclass
class ScheduleState:
    """Host-side, checkpointable state of the scheduled-step driver.

    Everything the driver accumulates across steps lives here — the
    controller-pinned budget per rule (the hysteresis band position),
    the re-plan counter, and the budget trajectory log — so a killed
    run restored through :func:`make_scheduled_train_step`'s
    ``schedule_state`` argument continues its budget trajectory exactly
    where it stopped instead of resetting every controller to its
    initial budget.  ``to_json``/``from_json`` round-trip through the
    checkpoint manifest's metadata record
    (``repro.train.checkpoint.pack_run_state``).
    """

    VERSION = 2

    budgets: Dict[int, float] = dataclasses.field(default_factory=dict)
    replans: int = 0
    trajectory: List[dict] = dataclasses.field(default_factory=list)
    # v2: optimizer-rank band positions (rank per dynamic OptimSpec
    # rule) + their trajectory — empty for AdamWConfig / static specs
    ranks: Dict[int, int] = dataclasses.field(default_factory=dict)
    rank_trajectory: List[dict] = dataclasses.field(default_factory=list)

    def to_json(self) -> dict:
        return {"version": self.VERSION,
                "budgets": {str(i): float(b)
                            for i, b in self.budgets.items()},
                "replans": int(self.replans),
                "trajectory": [dict(r) for r in self.trajectory],
                "ranks": {str(i): int(r)
                          for i, r in self.ranks.items()},
                "rank_trajectory": [dict(r)
                                    for r in self.rank_trajectory]}

    @classmethod
    def from_json(cls, d: dict) -> "ScheduleState":
        v = d.get("version")
        if v not in (1, cls.VERSION):
            raise ValueError(
                f"schedule-state record version {v!r} is not "
                f"{cls.VERSION}; this checkpoint was written by an "
                f"incompatible driver")
        return cls(budgets={int(i): float(b)
                            for i, b in d["budgets"].items()},
                   replans=int(d["replans"]),
                   trajectory=[dict(r) for r in d["trajectory"]],
                   ranks={int(i): int(r)
                          for i, r in d.get("ranks", {}).items()},
                   rank_trajectory=[dict(r) for r
                                    in d.get("rank_trajectory", [])])


class ScheduledStepFn:
    """(state, batch) -> (state, metrics) with budget schedules AND
    adaptive budget controllers resolved against the live step counter.

    Sampling budgets fix static residual shapes, so a schedule cannot be
    traced — instead the policy is re-resolved at the CONCRETE step read
    from ``state["step"]`` (a host sync, skipped when the counter is the
    one this driver's last call returned: see ``host_step``) and one
    compiled step is cached per resolved
    schedule signature.  Piecewise-constant schedules therefore bound
    the number of recompiles by their plateau count; schedule-free
    policies compile exactly once.

    Controller-carrying rules (``Rule.controller``, see
    ``repro.core.controller``) additionally read the per-tag statistics
    the cached step accumulates in ``state["budget_stats"]`` (one more
    host device_get per step, a few floats per tag — the same cost class
    as the step counter).  A controller's decision is pinned into the
    policy via ``with_rule_budgets`` so the compiled step sees a plain
    static budget; re-planning (a new signature -> ``plans.build_plan``
    shapes change -> compile) happens exactly when a controller crosses
    its hysteresis band.

    All cross-step driver state lives in ``self.schedule_state`` (a
    :class:`ScheduleState`): pass a restored one to resume a killed run
    with its band positions and trajectory intact.  Introspection:

      * ``step_fn.compiled``           — signature -> compiled step
      * ``step_fn.replans``            — controller-driven budget changes
      * ``step_fn.budget_trajectory``  — [{step, rule, budget, prev}, ...]
        (initial pins carry ``prev=None``, are logged on the first
        invocation at whatever step that is, and do not count as
        re-plans)
    """

    def __init__(self, cfg: ArchConfig, policy: cm.Policy,
                 opt_cfg,
                 schedule: Callable[[jax.Array], jax.Array],
                 jit: bool = True,
                 schedule_state: Optional[ScheduleState] = None,
                 **train_step_kwargs):
        self._cfg = cfg
        self._policy = policy
        self._opt_cfg = opt_cfg
        self._schedule = schedule
        self._jit = jit
        self._train_step_kwargs = train_step_kwargs
        self.compiled: Dict[tuple, Callable] = {}
        self._last_step = None   # (the step counter last returned, its int)

        rules = policy.rules.rules if policy.rules is not None else ()
        self._rules = rules
        self._ctrl_idx = (policy.rules.controller_rule_indices()
                          if policy.rules is not None else ())
        # same default-first base config as PolicyRules.resolve/signature
        base_cfg = (policy.rules.default
                    if policy.rules is not None
                    and policy.rules.default is not None else policy.wtacrs)
        self.schedule_state = (schedule_state if schedule_state is not None
                               else ScheduleState())
        if not self.schedule_state.budgets:
            self.schedule_state.budgets = {
                i: rules[i].controller.initial_budget(
                    rules[i].static_budget(base_cfg))
                for i in self._ctrl_idx}
        elif set(self.schedule_state.budgets) != set(self._ctrl_idx):
            raise ValueError(
                f"restored schedule state pins budgets for controller "
                f"rules {sorted(self.schedule_state.budgets)} but the "
                f"policy's controller rules are "
                f"{sorted(self._ctrl_idx)}; the policy changed between "
                f"save and restore")
        self._stats_needed = any(
            getattr(rules[i].controller, "needs_stats", True)
            for i in self._ctrl_idx)
        if self._stats_needed and not train_step_kwargs.get(
                "use_znorm_cache"):
            # without the cache the tap never refreshes budget_stats:
            # every count stays 0, controllers hold forever, and the
            # "adaptive" run silently trains at its initial budget —
            # fail loudly now
            raise ValueError(
                "policy has stats-driven budget-controller rules; pass "
                "use_znorm_cache=True (and init the state with "
                "znorm_tags and budget_stats=True) so the tap "
                "statistics they feed on actually update")
        # tags GOVERNED by each controller rule under first-match-wins —
        # a bare pattern match would also feed a controller stats from
        # tags an earlier rule owns.  Stat keys are fixed per state
        # structure, so resolve once.
        self.owned_tags: Dict[int, list] = {}

        # --- optimizer rank dynamics (repro.optim.OptimSpec) ---------
        self._opt_spec = (opt_cfg
                          if isinstance(opt_cfg, optim_lib.OptimSpec)
                          else None)
        spec = self._opt_spec
        self._rank_dyn = (spec.dynamic_rule_indices()
                          if spec is not None else ())
        self._rank_ctrl = (spec.controller_rule_indices()
                           if spec is not None else ())
        if not self.schedule_state.ranks:
            if self._rank_dyn:
                self.schedule_state.ranks = dict(spec.initial_ranks())
        elif set(self.schedule_state.ranks) != set(self._rank_dyn):
            raise ValueError(
                f"restored schedule state pins ranks for optimizer "
                f"rules {sorted(self.schedule_state.ranks)} but the "
                f"spec's dynamic rank rules are "
                f"{sorted(self._rank_dyn)}; the optimizer spec changed "
                f"between save and restore")

    @property
    def replans(self) -> int:
        return self.schedule_state.replans

    @property
    def budget_trajectory(self) -> List[dict]:
        return self.schedule_state.trajectory

    def _owned(self, stats_keys):
        if not self.owned_tags:
            self.owned_tags.update({i: [] for i in self._ctrl_idx})
            for t in stats_keys:
                for i, r in enumerate(self._rules):
                    if r.matches(t):
                        if i in self.owned_tags:
                            self.owned_tags[i].append(t)
                        break
        return self.owned_tags

    def host_step(self, state) -> int:
        """``state["step"]`` as an int.  When it is the very counter this
        driver's last call returned, its value is known (a step adds one)
        and the device is not asked: arrays are immutable."""
        last = self._last_step
        if last is not None and state["step"] is last[0]:
            return last[1]
        return int(state["step"])

    def __call__(self, state, batch):
        step = self.host_step(state)
        st = self.schedule_state
        rule_budgets = None
        stats_host = None
        if (self._ctrl_idx and self._stats_needed) or self._rank_ctrl:
            stats_host = (jax.device_get(state["budget_stats"])
                          if "budget_stats" in state else {})
        if self._ctrl_idx:
            if self._stats_needed and "budget_stats" not in state:
                raise ValueError(
                    "policy has stats-driven budget-controller rules "
                    "but the train state carries no 'budget_stats'; "
                    "init the state with znorm_tags and "
                    "budget_stats=True (the controllers feed on the "
                    "znorm cache's tap statistics) and pass "
                    "use_znorm_cache=True")
            if stats_host is None:
                stats_host = (jax.device_get(state["budget_stats"])
                              if "budget_stats" in state else {})
            owned = self._owned(
                [t for t in stats_host
                 if not optim_lib.is_rank_stat_key(t)])
            for i in self._ctrl_idx:
                r = self._rules[i]
                agg = controller_lib.TagStats.aggregate(stats_host,
                                                        tags=owned[i])
                nb = float(r.controller.propose(agg, st.budgets[i], step))
                if not any(rec["rule"] == i for rec in st.trajectory):
                    # initial pin, logged on the FIRST invocation at
                    # whatever step that happens (a resumed run without
                    # a restored trajectory still records its baseline)
                    st.trajectory.append(
                        {"step": step, "rule": i, "pattern": r.pattern,
                         "budget": st.budgets[i], "prev": None})
                if nb != st.budgets[i]:
                    st.replans += 1
                    st.trajectory.append(
                        {"step": step, "rule": i, "pattern": r.pattern,
                         "budget": nb, "prev": st.budgets[i]})
                    st.budgets[i] = nb
            rule_budgets = tuple(st.budgets.get(i)
                                 for i in range(len(self._rules)))
        state = self._apply_rank_dynamics(state, step, stats_host)
        pol = self._policy.at_step(step)
        if rule_budgets is not None:
            pol = pol.with_rule_budgets(rule_budgets)
        sig = pol.schedule_signature()
        if st.ranks:
            sig = sig + tuple(sorted(st.ranks.items()))
        fn = self.compiled.get(sig)
        if fn is None:
            fn = make_train_step(self._cfg, pol, self._opt_cfg,
                                 self._schedule,
                                 **self._train_step_kwargs)
            if self._jit:
                fn = jax.jit(fn)
            self.compiled[sig] = fn
        new_state, metrics = fn(state, batch)
        self._last_step = (new_state["step"], step + 1)
        return new_state, metrics

    def _apply_rank_dynamics(self, state, step: int, stats_host):
        """Resolve rank schedules/controllers at the concrete step and
        migrate the optimizer state on band crossings (pad/truncate
        the low-rank subspaces; one recompile per change through the
        signature-keyed cache, exactly like a budget re-plan)."""
        if not self._rank_dyn:
            return state
        spec, st = self._opt_spec, self.schedule_state
        changed: Dict[int, int] = {}
        for i in self._rank_dyn:
            rule = spec.rules[i]
            if rule.schedule is not None:
                want = int(rule.schedule.rank_at(step))
            else:
                vec = (stats_host or {}).get(optim_lib.rank_stat_key(i))
                agg = (controller_lib.TagStats.from_vector(vec)
                       if vec is not None else None)
                want = int(rule.controller.propose(agg, st.ranks[i],
                                                   step))
            if not any(rec["rule"] == i for rec in st.rank_trajectory):
                st.rank_trajectory.append(
                    {"step": step, "rule": i, "pattern": rule.pattern,
                     "rank": st.ranks[i], "prev": None})
            if want != st.ranks[i]:
                st.replans += 1
                st.rank_trajectory.append(
                    {"step": step, "rule": i, "pattern": rule.pattern,
                     "rank": want, "prev": st.ranks[i]})
                changed[i] = want
                st.ranks[i] = want
        if changed:
            state = dict(state, opt=optim_lib.migrate_ranks(
                spec, state["opt"], state["params"], changed))
        return state


def make_scheduled_train_step(cfg: ArchConfig, policy: cm.Policy,
                              opt_cfg,
                              schedule: Callable[[jax.Array], jax.Array],
                              jit: bool = True,
                              schedule_state: Optional[ScheduleState] = None,
                              **train_step_kwargs) -> ScheduledStepFn:
    """Build a :class:`ScheduledStepFn` (see its docstring).

    ``schedule_state``: a restored :class:`ScheduleState` to resume a
    controller-carrying run bit-faithfully; ``None`` starts fresh at
    every controller's initial budget.
    """
    return ScheduledStepFn(cfg, policy, opt_cfg, schedule, jit=jit,
                           schedule_state=schedule_state,
                           **train_step_kwargs)


def make_prefill_step(cfg: ArchConfig, policy: cm.Policy):
    def prefill_step(params, batch):
        return registry.prefill(cfg, params, batch, policy)
    return prefill_step


def make_serve_step(cfg: ArchConfig, policy: cm.Policy):
    def serve_step(params, token, pos, states):
        logits, new_states = registry.decode_step(
            cfg, params, token, pos, states, policy)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, new_states
    return serve_step


def make_prefill_chunk_step(cfg: ArchConfig, policy: cm.Policy,
                            chunk_len: int):
    """Prefill ``chunk_len`` prompt tokens for an aligned batch in ONE
    jitted call: a ``lax.scan`` of ``registry.decode_step`` over the
    chunk.  Bit-identical to the old one-jitted-call-per-token loop (the
    exact same decode steps run in the exact same order) but with the
    per-token dispatch overhead amortized ``chunk_len``-fold.  One
    compile per distinct chunk length; ``Run.prefill`` slices prompts
    into full chunks + one remainder, so at most two compiles per
    prompt length class."""

    def chunk_step(params, tokens, start, states):
        # tokens: (B, chunk_len); start: scalar position of tokens[:, 0]

        def body(carry, xs):
            states = carry
            tok, off = xs
            _, states = registry.decode_step(
                cfg, params, tok, start + off, states, policy)
            return states, None

        states, _ = jax.lax.scan(
            body, states,
            (jnp.moveaxis(tokens, 1, 0), jnp.arange(chunk_len)))
        return states

    return chunk_step


# ---------------------------------------------------------------------------
# Slot-pool serving steps (continuous batching; see repro.serve)
# ---------------------------------------------------------------------------

def make_slot_serve_step(cfg: ArchConfig, policy: cm.Policy,
                         top_k: int = 0):
    """One batched decode step over the whole slot pool.

    Gathers every slot's paged KV into contiguous decode-layout caches,
    runs ONE ``decode_step`` with per-slot positions (the heterogeneous
    batch: every row at its own ``pos``), samples next tokens with
    per-request keys/temperatures, and scatters each row's written K/V
    token back into its own page (inactive rows land on the scratch
    page, recurrent state holds under the ``active`` mask).

    Signature: ``(params, pool, page_table, token, pos, active, keys,
    n_gen, temperature) -> (next_token, logits, pool)`` — all dynamic,
    so one compile serves every batch composition."""
    from repro.serve import pool as pool_lib
    from repro.serve import sampling as sampling_lib

    def slot_serve_step(params, pool, page_table, token, pos, active,
                        keys, n_gen, temperature):
        states = pool_lib.gather_decode_states(cfg, pool, page_table)
        logits, new_states = registry.decode_step(
            cfg, params, token, pos, states, policy)
        ks = sampling_lib.step_keys(keys, n_gen)
        next_token = sampling_lib.sample_logits(logits, ks, temperature,
                                                top_k=top_k)
        pool = pool_lib.scatter_decode_update(
            cfg, pool, new_states, page_table, pos, active)
        return next_token, logits, pool

    return slot_serve_step


def make_slot_prefill_step(cfg: ArchConfig, policy: cm.Policy,
                           chunk_len: int, fresh: bool):
    """Prefill ``chunk_len`` prompt tokens for ONE slot of the pool.

    Gathers the slot's decode-layout state (batch = 1), scans
    ``decode_step`` over the chunk — numerically identical to the
    aligned-batch prefill and to token-by-token decode, so chunk size
    never changes served tokens — and scatters the state back into the
    slot's pages.  ``fresh`` (static) marks a request's FIRST chunk:
    recurrent state starts from the block init constants instead of the
    evicted predecessor's leftovers (stale KV needs no reset; attention
    masks beyond the slot's live length)."""
    from repro.serve import pool as pool_lib

    def slot_prefill_step(params, pool, page_table_row, slot, tokens,
                          start):
        # tokens: (chunk_len,); start: scalar position of tokens[0]
        states = pool_lib.gather_slot_states(cfg, pool, page_table_row,
                                             slot, fresh)

        def body(carry, xs):
            states = carry
            tok, off = xs
            _, states = registry.decode_step(
                cfg, params, tok[None], start + off, states, policy)
            return states, None

        states, _ = jax.lax.scan(body, states,
                                 (tokens, jnp.arange(chunk_len)))
        pool = pool_lib.scatter_slot_states(cfg, pool, states,
                                            page_table_row, slot)
        return pool

    return slot_prefill_step


def make_slot_reset_step(cfg: ArchConfig):
    """Reset one slot's recurrent state to the block init constants.

    Needed for single-token prompts (zero prefill chunks run before the
    first decode step, so nothing else would clear the evicted
    predecessor's conv/SSM state out of the slot)."""
    from repro.serve import pool as pool_lib

    def slot_reset_step(pool, page_table_row, slot):
        states = pool_lib.gather_slot_states(cfg, pool, page_table_row,
                                             slot, fresh=True)
        return pool_lib.scatter_slot_states(cfg, pool, states,
                                            page_table_row, slot)

    return slot_reset_step


# ---------------------------------------------------------------------------
# shard_map DP step with explicit (compressed) gradient all-reduce
# ---------------------------------------------------------------------------

def make_shardmap_dp_step(cfg: ArchConfig, policy: cm.Policy,
                          opt_cfg,
                          schedule, mesh,
                          compress: compression.Mode = "none"):
    """Pure data-parallel step with the gradient reduction written out
    explicitly (psum with optional bf16/int8 compression) instead of left
    to GSPMD.  Params are replicated; used for the compression bench and
    as the template for cross-pod DCI-frugal reductions.
    """
    dp = mesh_lib.data_axes(mesh)

    def local_step(state, batch):
        params = state["params"]
        key = jax.random.wrap_key_data(state["base_key"])
        key = jax.random.fold_in(key, state["step"])
        # fold in the data-shard index so estimator sampling decorrelates
        idx = jnp.zeros((), jnp.int32)
        for a in dp:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        key = jax.random.fold_in(key, idx)
        loss, gp = jax.value_and_grad(
            lambda p: registry.loss_fn(cfg, p, batch, policy, key=key)[0]
        )(params)
        gp = compression.pmean_tree(gp, dp, compress)
        loss = jax.lax.pmean(loss, dp)
        lr = schedule(state["step"])
        if isinstance(opt_cfg, optim_lib.OptimSpec):
            new_params, new_opt, om, _ = optim_state_update(
                gp, state["opt"], params, lr, opt_cfg)
        else:
            new_params, new_opt, om = optim.adamw_update(
                gp, state["opt"], params, lr, opt_cfg)
        new_state = dict(state, params=new_params, opt=new_opt,
                         step=state["step"] + 1)
        return new_state, {"loss": loss, "lr": lr, **om}

    state_spec = P()
    batch_spec = P(dp)
    return jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(state_spec, batch_spec),
        out_specs=(state_spec, state_spec),
        check_vma=False)
