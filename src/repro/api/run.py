"""The Run session: a live training/serving session built from a RunSpec.

One object owns everything the hand-wired path spread over eight call
sites: tag enumeration, state init (cache + stats sized from the
policy), the scheduled step driver and its compile cache, controller
band state, checkpointing with a versioned run-state record, the serve
path, and reporting.  Algorithm 1 becomes::

    run = Run(RunSpec(arch="xlstm-125m", policy=policy, steps=200,
                      checkpoint_dir="/tmp/ck", checkpoint_every=25))
    run.fit()                      # or: run.step(batch) per batch
    print(run.report())

Kill it anywhere and ``Run.resume(spec)`` continues bit-faithfully:
params, optimizer, znorm cache, budget statistics AND the scheduled
driver's controller band positions all come back (the band state used
to live in a closure and silently reset to ``initial_budget`` on
resume; it now rides the checkpoint manifest as a versioned record).
"""
from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro import optim as optim_lib
from repro import spans
from repro.api.spec import RunSpec
from repro.configs import get_config
from repro.launch import mesh as mesh_lib
from repro.launch import report as report_lib
from repro.launch import train_steps
from repro.models import registry
from repro.train import checkpoint, optim as adamw_lib, znorm


class Run:
    """A training/serving session.  See module docstring.

    Attributes of note: ``state`` (the train-state pytree), ``history``
    (per-step float metrics), ``step_fn`` (the scheduled driver —
    ``step_fn.compiled`` / ``.replans`` / ``.budget_trajectory`` expose
    the re-plan economy), ``tags`` (the znorm-cache tag list, empty when
    the policy needs no cache).
    """

    def __init__(self, spec: RunSpec):
        self.spec = spec
        self.cfg = get_config(spec.arch, reduced=spec.reduced)
        # One kernel-dispatch decision for the whole run: RunSpec.kernel
        # maps over every config the policy can resolve to.
        self.policy = (spec.policy if spec.kernel is None
                       else spec.policy.with_kernel(spec.kernel))
        self.use_znorm_cache = spec.use_znorm_cache
        self.track_budget_stats = spec.track_budget_stats
        self.dataset = spec.data.build(self.cfg)
        self.tags: List[str] = (
            znorm.collect_linear_tags(self.cfg, policy=self.policy)
            if self.use_znorm_cache else [])
        self.mesh = (mesh_lib.make_host_mesh(spec.model_parallel)
                     if spec.mesh == "host" else None)
        self.state: Optional[Dict[str, Any]] = None
        self.history: List[dict] = []
        self.schedule_state = train_steps.ScheduleState()
        self._step_fn: Optional[train_steps.ScheduledStepFn] = None
        self._serve_fn = None
        self._prefill_fns: Dict[int, Any] = {}
        self._sample_fn = None
        self._async_ckpt: Optional[checkpoint.AsyncCheckpointer] = None
        self._dryrun_rec: Optional[dict] = None

    # ------------------------------------------------------------------
    # state lifecycle
    # ------------------------------------------------------------------

    def init(self) -> "Run":
        """Allocate the train state (idempotent).  Built under ``jit`` on
        one device and on a mesh alike, so both start from the same
        weights (eager and jitted initializers round differently); on a
        mesh it is built on its shards, never whole on one device."""
        if self.state is None:
            init = functools.partial(
                train_steps.init_train_state, self.cfg,
                znorm_tags=self.tags if self.use_znorm_cache else None,
                n_dataset=self.spec.data.n_samples,
                budget_stats=self.track_budget_stats,
                opt=self.spec.optimizer,
                opt_ranks=self.schedule_state.ranks or None)
            key = jax.random.PRNGKey(self.spec.seed)
            sh = (None if self.mesh is None
                  else self._shardings(jax.eval_shape(init, key)))
            self.state = jax.jit(init, out_shardings=sh)(key)
        return self

    def _shardings(self, state):
        _, axes = registry.abstract_params(self.cfg)
        return train_steps.train_state_shardings(self.cfg, state, axes,
                                                 self.mesh)

    def _shard(self, state):
        if self.mesh is None:
            return state
        return jax.device_put(state, self._shardings(state))

    def _abstract_state(self, opt=None, opt_ranks=None):
        state, _ = train_steps.abstract_train_state(
            self.cfg,
            znorm_tags=self.tags if self.use_znorm_cache else None,
            n_dataset=self.spec.data.n_samples,
            budget_stats=self.track_budget_stats,
            opt=self.spec.optimizer if opt is None else opt,
            opt_ranks=opt_ranks)
        return state

    @property
    def step_fn(self) -> train_steps.ScheduledStepFn:
        """The scheduled step driver (built on first use, shared by
        every ``step``/``fit`` call so the compile cache and controller
        band state persist)."""
        if self._step_fn is None:
            data_axes = self.spec.data_axes
            if (data_axes is None and self.mesh is not None
                    and self.spec.microbatches > 1):
                data_axes = mesh_lib.data_axes(self.mesh)
            self._step_fn = train_steps.make_scheduled_train_step(
                self.cfg, self.policy, self.spec.optimizer,
                self.spec.make_lr_schedule(), jit=self.spec.jit,
                schedule_state=self.schedule_state,
                use_znorm_cache=self.use_znorm_cache,
                microbatches=self.spec.microbatches,
                data_axes=data_axes)
        return self._step_fn

    # ------------------------------------------------------------------
    # training
    # ------------------------------------------------------------------

    def step(self, batch) -> Dict[str, float]:
        """One optimizer step on one batch (dict of arrays; a
        ``sample_ids`` entry is consumed by the znorm cache and dropped
        automatically when the policy needs none)."""
        self.init()
        with spans.host_span(spans.STEP_PUT):
            b = {k: jnp.asarray(v) for k, v in batch.items()}
        if not self.use_znorm_cache:
            b.pop("sample_ids", None)
        elif "sample_ids" not in b:
            raise ValueError(
                "this run's policy needs the znorm cache, so every "
                "batch must carry 'sample_ids' (dataset sample indices; "
                "DataSpec-built datasets provide them)")
        s = self.step_fn.host_step(self.state)
        # the ambient mesh lets the Pallas kernels run per data shard
        # (kernels.ops); the compiler cannot partition them itself
        mesh = (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())
        with mesh, spans.host_span(spans.STEP_DISPATCH):
            self.state, metrics = self.step_fn(self.state, b)
        with spans.host_span(spans.STEP_FETCH):
            m = {k: float(v) for k, v in jax.device_get(metrics).items()}
        self.history.append({"step": s, **m})
        return m

    def fit(self, dataset=None, steps: Optional[int] = None,
            log_every: int = 0) -> List[dict]:
        """Train from the state's current step to ``steps`` (default
        ``spec.steps``), checkpointing every ``spec.checkpoint_every``
        steps.  ``dataset`` overrides the spec-built corpus; it must
        expose ``batch_at(step, batch_size)`` (stateless step-indexed
        batches are what make kill/resume replay exact)."""
        self.init()
        ds = dataset if dataset is not None else self.dataset
        if (dataset is not None and self.use_znorm_cache
                and getattr(ds, "n_samples", None) is not None
                and ds.n_samples > self.spec.data.n_samples):
            raise ValueError(
                f"override dataset has {ds.n_samples} samples but the "
                f"znorm cache was sized to spec.data.n_samples "
                f"= {self.spec.data.n_samples}; out-of-range sample_ids "
                f"would silently clamp onto the last cache column.  Set "
                f"DataSpec(n_samples=...) to cover the dataset.")
        total = self.spec.steps if steps is None else steps
        start = int(self.state["step"])
        t0 = time.perf_counter()
        for s in range(start, total):
            m = self.step(ds.batch_at(s, self.spec.batch_size))
            if log_every and (s % log_every == 0 or s == total - 1):
                dt = (time.perf_counter() - t0) / max(s - start + 1, 1)
                print(f"step {s:5d}  loss {m['loss']:.4f}  "
                      f"lr {m['lr']:.2e}  {dt * 1e3:.0f} ms/step")
            if (self.spec.checkpoint_every
                    and (s + 1) % self.spec.checkpoint_every == 0):
                self.save(block=False)
        if self._async_ckpt is not None:
            self._async_ckpt.wait()
        return self.history

    # ------------------------------------------------------------------
    # checkpointing
    # ------------------------------------------------------------------

    def _run_state_metadata(self) -> dict:
        # snapshot history: the async checkpointer serializes on a
        # worker thread while fit() keeps appending to the live list
        opt = self.spec.optimizer
        layouts = (list(opt.layouts_used())
                   if isinstance(opt, optim_lib.OptimSpec)
                   else ["adamw"])
        return checkpoint.pack_run_state(
            self.schedule_state.to_json(),
            arch=self.spec.arch,
            optim_layouts=layouts,
            history=[dict(h) for h in self.history])

    def save(self, block: bool = True) -> None:
        """Checkpoint state + the versioned run-state record (driver
        band positions, trajectory, metrics history).  ``block=False``
        overlaps the disk write with subsequent steps."""
        if not self.spec.checkpoint_dir:
            raise ValueError("RunSpec.checkpoint_dir is not set")
        self.init()
        step = int(self.state["step"])
        if block:
            if self._async_ckpt is not None:
                self._async_ckpt.wait()
            checkpoint.save(self.spec.checkpoint_dir, step, self.state,
                            metadata=self._run_state_metadata(),
                            keep=self.spec.checkpoint_keep)
        else:
            if self._async_ckpt is None:
                self._async_ckpt = checkpoint.AsyncCheckpointer(
                    self.spec.checkpoint_dir,
                    keep=self.spec.checkpoint_keep)
            self._async_ckpt.save(step, self.state,
                                  metadata=self._run_state_metadata())

    @classmethod
    def restore(cls, spec: RunSpec, step: Optional[int] = None) -> "Run":
        """Rebuild a Run from its latest (or given-step) checkpoint:
        params, optimizer, znorm cache, budget statistics, metrics
        history AND the scheduled driver's controller band state — the
        budget trajectory continues instead of resetting to every
        controller's ``initial_budget``.

        Optimizer-state compatibility: the manifest records which
        layouts wrote the checkpoint.  A legacy dense-AdamW checkpoint
        restores under an all-dense ``OptimSpec`` (converted in place);
        any other mismatch — unknown layout names, factored/low-rank
        spec against a dense checkpoint or vice versa — fails with an
        explicit error instead of a pytree-structure crash."""
        if not spec.checkpoint_dir:
            raise ValueError("RunSpec.checkpoint_dir is not set")
        run = cls(spec)
        if step is None:
            step = checkpoint.latest_step(spec.checkpoint_dir)
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoints under {spec.checkpoint_dir}")
        manifest = checkpoint.read_manifest(spec.checkpoint_dir, step)
        rec = checkpoint.unpack_run_state(manifest)
        if rec is not None:
            if "schedule_state" in rec:
                run.schedule_state = train_steps.ScheduleState.from_json(
                    rec["schedule_state"])
            run.history = [dict(h) for h in rec.get("history", [])]
            unknown = [l for l in rec.get("optim_layouts", [])
                       if l not in optim_lib.KNOWN_LAYOUTS + ("adamw",)]
            if unknown:
                raise ValueError(
                    f"checkpoint step {step} was written with unknown "
                    f"optimizer-state layout(s) {unknown}; this reader "
                    f"knows {sorted(optim_lib.KNOWN_LAYOUTS)} (plus "
                    f"legacy 'adamw').  Upgrade repro to restore it.")
        # dense-AdamW checkpoints key their moments as opt/m/...; the
        # layout subsystem keys opt/leaves/<path>/<slot> ("opt/count"
        # exists in both, so it cannot discriminate)
        keys = manifest.get("keys", ())
        legacy_ckpt = (any(k.startswith(("opt/m/", "opt/v/"))
                           for k in keys)
                       and not any(k.startswith("opt/leaves/")
                                   for k in keys))
        spec_opt = spec.optimizer
        if legacy_ckpt and isinstance(spec_opt, optim_lib.OptimSpec):
            if not spec_opt.all_dense:
                raise ValueError(
                    f"checkpoint step {step} holds legacy dense-AdamW "
                    f"optimizer state but the spec's OptimSpec resolves "
                    f"to {spec_opt.layouts_used()}; factored/low-rank "
                    f"moments cannot be reconstructed from dense ones. "
                    f"Restore with an all-dense spec (or AdamWConfig) "
                    f"and switch layouts on a fresh run.")
            template = run._abstract_state(opt=adamw_lib.AdamWConfig())
            state, step = checkpoint.restore(spec.checkpoint_dir,
                                             template, step=step)
            state["opt"] = optim_lib.from_legacy_adamw(state["opt"],
                                                       state["params"])
        elif not legacy_ckpt and not isinstance(spec_opt,
                                                optim_lib.OptimSpec):
            raise ValueError(
                f"checkpoint step {step} was written by an OptimSpec "
                f"(path-keyed optimizer state) but the spec carries a "
                f"legacy AdamWConfig; restore with "
                f"OptimSpec.from_adamw(cfg) to keep the layouts.")
        else:
            template = run._abstract_state(
                opt_ranks=run.schedule_state.ranks or None)
            state, step = checkpoint.restore(spec.checkpoint_dir,
                                             template, step=step)
        run.state = run._shard(state)
        return run

    @classmethod
    def resume(cls, spec: RunSpec, step: Optional[int] = None) -> "Run":
        """``restore`` when a checkpoint exists, else a fresh Run — the
        crash-rerun-the-same-command entry point."""
        if (spec.checkpoint_dir
                and checkpoint.latest_step(spec.checkpoint_dir)
                is not None):
            return cls.restore(spec, step=step)
        return cls(spec)

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------

    def _serve(self):
        if self._serve_fn is None:
            fn = train_steps.make_serve_step(self.cfg, self.policy)
            self._serve_fn = jax.jit(fn) if self.spec.jit else fn
        return self._serve_fn

    def _prefill_chunk_fn(self, chunk_len: int):
        fn = self._prefill_fns.get(chunk_len)
        if fn is None:
            fn = train_steps.make_prefill_chunk_step(
                self.cfg, self.policy, chunk_len)
            if self.spec.jit:
                fn = jax.jit(fn)
            self._prefill_fns[chunk_len] = fn
        return fn

    def prefill(self, prompts, gen: int = 0):
        """Stream a (B, S) prompt batch into decode caches with
        ``S + gen`` token headroom, ``spec.prefill_chunk`` tokens per
        jitted call (a scan of decode steps — bit-identical to the old
        one-call-per-token loop, minus S dispatches).  Returns
        ``(last_token, pos, states)`` ready for :meth:`decode`."""
        self.init()
        prompts = jnp.asarray(prompts)
        s = prompts.shape[1]
        states = registry.decode_state_init(
            self.cfg, prompts.shape[0], s + gen)
        t, chunk = 0, self.spec.prefill_chunk
        while t < s - 1:
            n = min(chunk, s - 1 - t)
            states = self._prefill_chunk_fn(n)(
                self.state["params"], prompts[:, t:t + n],
                jnp.asarray(t), states)
            t += n
        return prompts[:, -1], s - 1, states

    def decode(self, token, pos, states):
        """One greedy decode step: ``(next_token, logits, states)``."""
        self.init()
        return self._serve()(self.state["params"], token,
                             jnp.asarray(pos), states)

    def generate(self, prompts, gen: int, temperature: float = 0.0,
                 seed: int = 0, top_k: int = 0) -> jax.Array:
        """Continuation: (B, S) prompts -> (B, gen) token ids.

        ``temperature == 0`` (default) is greedy argmax; > 0 samples,
        optionally ``top_k``-truncated, deterministically under a fixed
        ``seed``.  Randomness is keyed per (seed, row, step) through
        ``repro.serve.sampling``, the SAME keying the continuous-batching
        service uses with the batch row as request uid — so a request
        served through a churning slot pool reproduces bit-identically
        here with its uid as row index."""
        from repro.serve import sampling
        tok, pos, states = self.prefill(prompts, gen=gen)
        b = prompts.shape[0]
        base = jnp.stack([sampling.request_key(seed, r)
                          for r in range(b)])
        temp = jnp.full((b,), temperature, jnp.float32)
        if self._sample_fn is None or self._sample_fn[0] != top_k:
            fn = functools.partial(sampling.sample_logits, top_k=top_k)
            self._sample_fn = (top_k,
                               jax.jit(fn) if self.spec.jit else fn)
        sample = self._sample_fn[1]
        out = []
        for g, t in enumerate(range(pos, pos + gen)):
            _, logits, states = self.decode(tok, t, states)
            keys = sampling.step_keys(base, jnp.full((b,), g, jnp.int32))
            tok = sample(logits, keys, temp)
            out.append(tok)
        return jnp.stack(out, axis=1)

    def serve(self, spec: Optional["ServeSpec"] = None, **overrides):
        """Open a continuous-batching :class:`~repro.serve.ServeSession`
        on this run's params.

        ``spec``: a full :class:`~repro.api.spec.ServeSpec`; or pass
        field overrides (``max_slots=8, page_size=16, ...``) and one is
        built on this run's (arch, reduced, policy).  Start the async
        loop and submit::

            with run.serve(max_slots=4).start() as sess:
                tokens = sess.submit(prompt, max_new=16).result(60)
        """
        from repro.serve import ServeSession
        from repro.serve.spec import ServeSpec
        self.init()
        if spec is None:
            overrides.setdefault("arch", self.spec.arch)
            overrides.setdefault("reduced", self.spec.reduced)
            overrides.setdefault("policy", self.policy)
            overrides.setdefault("prefill_chunk", self.spec.prefill_chunk)
            overrides.setdefault("jit", self.spec.jit)
            spec = ServeSpec(**overrides)
        elif overrides:
            raise ValueError("pass either a ServeSpec or field "
                             "overrides, not both")
        return ServeSession(spec, self.state["params"],
                            policy=self.policy)

    # ------------------------------------------------------------------
    # analysis
    # ------------------------------------------------------------------

    def dryrun(self, shape: str = "train_4k", mesh: str = "single"
               ) -> dict:
        """Lower+compile this run's (arch, policy) on a production mesh
        cell and keep the record for :meth:`report`."""
        from repro.launch.dryrun import lower_cell
        rec, _, _ = lower_cell(self.spec.arch, shape, mesh == "multi",
                               policy=self.policy,
                               microbatches=(self.spec.microbatches
                                             if self.spec.microbatches > 1
                                             else None))
        self._dryrun_rec = rec
        return rec

    def report(self) -> str:
        """Markdown report: §Run metrics summary, §Budgets controller
        trajectory + re-plan economy, §Optimizer memory (OptimSpec
        runs), §Roofline (when ``dryrun`` ran)."""
        n_steps = int(self.state["step"]) if self.state is not None else 0
        n_compiles = (len(self._step_fn.compiled)
                      if self._step_fn is not None else 0)
        optim_rec = None
        if isinstance(self.spec.optimizer, optim_lib.OptimSpec):
            params, _ = registry.abstract_params(self.cfg)
            optim_rec = optim_lib.memory_report(
                self.spec.optimizer, params,
                ranks=self.schedule_state.ranks or None)
        return report_lib.run_report(
            n_steps=n_steps,
            budget_records=self.schedule_state.trajectory,
            n_compiles=n_compiles, history=self.history,
            roofline_rec=self._dryrun_rec, optim_rec=optim_rec,
            rank_records=self.schedule_state.rank_trajectory)
