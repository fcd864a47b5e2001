"""Common model substrate: boxed params, norms, rotary embeddings, and the
WTA-CRS linear context threaded through every block.

Param convention: model init functions build trees whose leaves are
``Boxed(value, axes)`` where ``axes`` is a tuple of *logical* axis names
(e.g. ("embed", "mlp")).  ``unbox`` splits into (params, logical_axes)
twin trees; the launcher maps logical names -> mesh axes (repro.launch.
sharding).
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import estimator_registry as est_registry
from repro.core.config import EstimatorKind, WTACRSConfig
from repro.core.linear import wtacrs_linear
from repro.core.lora import LoRAConfig, lora_linear
from repro.core.policy import PolicyRules


@jax.tree_util.register_pytree_node_class
class Boxed:
    """Parameter + logical-axis annotation.  A pytree node whose axes are
    static aux data, so vmap/eval_shape/scan treat only ``value`` as data.
    """

    __slots__ = ("value", "axes")

    def __init__(self, value: Any, axes: Tuple[Optional[str], ...]):
        self.value = value
        self.axes = tuple(axes)

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)

    def __repr__(self):
        return f"Boxed({self.value!r}, axes={self.axes})"


def unbox(tree):
    params = jax.tree.map(lambda b: b.value, tree,
                          is_leaf=lambda x: isinstance(x, Boxed))
    axes = jax.tree.map(lambda b: b.axes, tree,
                        is_leaf=lambda x: isinstance(x, Boxed))
    return params, axes


def dense_init(key, shape, axes, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) > 1 else shape[0]
    if scale is None:
        scale = 1.0 / jnp.sqrt(fan_in)
    v = jax.random.normal(key, shape, jnp.float32) * scale
    return Boxed(v.astype(dtype), axes)


def zeros_init(shape, axes, dtype):
    return Boxed(jnp.zeros(shape, dtype), axes)


def ones_init(shape, axes, dtype):
    return Boxed(jnp.ones(shape, dtype), axes)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps: float):
    # variance in f32 via a reducing einsum — never materializes an f32
    # copy of x (XLA:CPU hoists such converts out of scan backward loops,
    # doubling the stored residuals)
    sq = jnp.einsum("...d,...d->...", x, x,
                    preferred_element_type=jnp.float32)
    var = sq[..., None] / x.shape[-1]
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * gamma.astype(x.dtype)


def layer_norm(x, gamma, beta, eps: float):
    mu = (jnp.einsum("...d->...", x, preferred_element_type=jnp.float32)
          / x.shape[-1])[..., None]
    xc = x - mu.astype(x.dtype)
    var = (jnp.einsum("...d,...d->...", xc, xc,
                      preferred_element_type=jnp.float32)
           / x.shape[-1])[..., None]
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return xc * inv * gamma.astype(x.dtype) + beta.astype(x.dtype)


def init_norm(cfg, dtype):
    if cfg.norm_type == "layernorm":
        return {"gamma": ones_init((cfg.d_model,), ("embed",), dtype),
                "beta": zeros_init((cfg.d_model,), ("embed",), dtype)}
    return {"gamma": ones_init((cfg.d_model,), ("embed",), dtype)}


def apply_norm(cfg, p, x):
    if "beta" in p:
        return layer_norm(x, p["gamma"], p["beta"], cfg.norm_eps)
    return rms_norm(x, p["gamma"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard + multimodal M-RoPE)
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)                       # (head_dim/2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, Dh); positions: (B, S) int32."""
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (B,S,Dh/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_rope_interleaved(x: jax.Array, positions: jax.Array,
                           theta: float) -> jax.Array:
    """RoPE on adjacent pairs: (x[2i], x[2i+1]) turns by position *
    theta^(-2i/Dh) (DeepSeek-V3's ``rope_interleave``).  x: (B, S, H, Dh);
    positions: (B, S) int32."""
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (B,S,Dh/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    xr = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    x1, x2 = xr[..., 0], xr[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float,
                sections: Tuple[int, int, int] = None) -> jax.Array:
    """Qwen2-VL multimodal RoPE: positions3 (3, B, S) = (t, h, w) ids.

    The head_dim/2 frequency slots are partitioned into three contiguous
    sections (temporal, height, width); each section rotates by its own
    position stream (arXiv:2409.12191).
    """
    half = x.shape[-1] // 2
    if sections is None:
        t = half // 2
        hw = (half - t) // 2
        sections = (t, hw, half - t - hw)
    freqs = rope_frequencies(x.shape[-1], theta)             # (half,)
    sec_id = jnp.repeat(jnp.arange(3), jnp.array(sections),
                        total_repeat_length=half)            # (half,)
    # pos_per_slot: (B, S, half) choosing the right position stream per slot
    pos = jnp.take(positions3.astype(jnp.float32),
                   sec_id, axis=0)                           # (half, B, S)
    pos = jnp.moveaxis(pos, 0, -1)                           # (B, S, half)
    angles = pos * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# The per-forward context: policy + rng + gradient-norm cache plumbing
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Policy:
    """What estimator/adapters apply to this forward pass.

    ``wtacrs`` is the network-wide default estimator config — the
    trivial one-rule case.  ``rules`` (optional) layers per-tag
    overrides and budget schedules on top: every ``Ctx.linear`` resolves
    its fully-prefixed tag through ``config_for``, so e.g. attention
    projections can stay exact while MLPs sample aggressively.  ``step``
    is the concrete trainer step the rules' budget schedules resolve
    against (static per compilation: budgets fix residual shapes; see
    ``launch.train_steps.make_scheduled_train_step``).  ``rule_budgets``
    pins one budget per rule (aligned with ``rules.rules``, ``None`` =
    unpinned): the scheduled-step driver resolves controller-carrying
    rules against live znorm statistics and bakes the decision in here,
    so the compiled step sees a plain static budget.
    """
    wtacrs: WTACRSConfig = WTACRSConfig(kind=EstimatorKind.EXACT)
    lora: LoRAConfig = LoRAConfig()
    rules: Optional[PolicyRules] = None
    step: int = 0
    rule_budgets: Optional[Tuple[Optional[float], ...]] = None
    remat: str = "none"            # none | full | wtacrs_names
    flash_block: int = 512
    flash_mode: str = "full"       # full | triangular (perf-iterated)
    # MoE dispatch sharding constraint (expert_axis, capacity_axes).
    # Without it GSPMD replicates the capacity dim across the data axis,
    # multiplying expert FLOPs by |data| (EXPERIMENTS.md §Perf, dbrx).
    moe_pspec: Optional[Tuple] = None
    # WTA-CRS sampling groups over the expert capacity dim; set to the
    # data-axis size so per-expert plans stay shard-local
    moe_groups: int = 1

    def config_for(self, tag: str) -> WTACRSConfig:
        """Estimator config for one fully-prefixed linear tag."""
        if self.rules is None:
            return self.wtacrs
        return self.rules.resolve(tag, step=self.step,
                                  fallback=self.wtacrs,
                                  rule_budgets=self.rule_budgets)

    def at_step(self, step: int) -> "Policy":
        """Resolve budget schedules against a concrete trainer step."""
        return dataclasses.replace(self, step=int(step))

    def with_rule_budgets(self, budgets) -> "Policy":
        """Pin per-rule budgets (driver-resolved controller decisions)."""
        budgets = None if budgets is None else tuple(budgets)
        return dataclasses.replace(self, rule_budgets=budgets)

    def with_kernel(self, kernel) -> "Policy":
        """Apply one :class:`~repro.core.kernel_config.KernelConfig` to
        every estimator config this policy can resolve to: the default
        ``wtacrs``, the rules' ``default``, and each rule's explicit
        config.  This is how ``RunSpec.kernel`` threads one
        kernel-dispatch decision through the whole policy."""
        wtacrs = self.wtacrs.with_kernel(kernel)
        rules = self.rules
        if rules is not None:
            new_rules = tuple(
                r if r.config is None
                else dataclasses.replace(r, config=r.config.with_kernel(kernel))
                for r in rules.rules)
            default = (None if rules.default is None
                       else rules.default.with_kernel(kernel))
            rules = dataclasses.replace(rules, rules=new_rules,
                                        default=default)
        return dataclasses.replace(self, wtacrs=wtacrs, rules=rules)

    def schedule_signature(self) -> Tuple[float, ...]:
        """Jit-cache key: changes exactly when a schedule crosses a
        plateau boundary or a controller decision re-pins a budget
        (empty for static policies)."""
        if self.rules is None:
            return ()
        return self.rules.schedule_signature(self.step,
                                             rule_budgets=self.rule_budgets,
                                             fallback=self.wtacrs)


def _tag_seed(tag: str) -> int:
    return zlib.crc32(tag.encode()) & 0x7FFFFFFF


# Sampled-dimension tag metadata.  A linear whose input is (..., S, D)
# draws one plan per leading index over the S (token) dim; a 2-D input
# (N, D) is a single flattened sample over all N rows (e.g. the MoE
# router over batch*seq, or an expert FFN over capacity slots).  The
# distinction matters to consumers that assume per-dataset-sample
# structure — the znorm cache scatters taps by sample id and would
# silently mis-scatter a rows-sampled tag — so it is recorded alongside
# the tag and asserted on, instead of being an implicit convention.
SAMPLED_DIM_TOKEN = "token"   # per-sample plans over the token dim
SAMPLED_DIM_ROWS = "rows"     # one plan over all (flattened) rows

# Module-level tag sink: when active, every Ctx.linear records its tag
# (and the dimension it samples over, in the twin dims dict).  Used by
# repro.train.znorm to enumerate the WTA-CRS'd linears of an
# architecture (the keys of the gradient-norm cache).
_TAG_SINK: Optional[list] = None
_TAG_DIMS: Optional[dict] = None


class tag_recorder:
    """Records every Ctx.linear tag in trace order; ``.dims`` maps each
    recorded tag to its sampled dimension (SAMPLED_DIM_*)."""

    def __enter__(self):
        global _TAG_SINK, _TAG_DIMS
        self._old = (_TAG_SINK, _TAG_DIMS)
        _TAG_SINK = []
        _TAG_DIMS = {}
        self.dims = _TAG_DIMS
        return _TAG_SINK

    def __exit__(self, *exc):
        global _TAG_SINK, _TAG_DIMS
        _TAG_SINK, _TAG_DIMS = self._old
        return False


@dataclasses.dataclass
class Ctx:
    """Threaded through blocks; routes every linear through the policy.

    znorms maps linear tags -> per-token gradient-norm estimates with the
    token shape of the current activation (e.g. (B, S)).  Missing tag ->
    activation-only probabilities.
    """
    policy: Policy
    key: Optional[jax.Array] = None
    znorms: Optional[Dict[str, jax.Array]] = None
    collect_tags: Optional[list] = None    # tag-recording mode
    compute_dtype: Optional[Any] = None    # weights cast to this at use
    tag_prefix: str = ""                   # disambiguates unit positions

    def _key_for(self, tag: str):
        if self.key is None:
            return None
        return jax.random.fold_in(self.key, _tag_seed(tag))

    def _record_tag(self, tag: str, sampled_dim: str) -> None:
        if _TAG_SINK is not None and tag not in _TAG_SINK:
            _TAG_SINK.append(tag)
        if _TAG_DIMS is not None:
            prev = _TAG_DIMS.setdefault(tag, sampled_dim)
            if prev != sampled_dim:
                raise ValueError(
                    f"linear tag {tag!r} sampled over {sampled_dim!r} but "
                    f"was previously recorded sampling over {prev!r}; one "
                    f"tag must sample one dimension")
        if self.collect_tags is not None and tag not in self.collect_tags:
            self.collect_tags.append(tag)

    def _znorm_for(self, tag: str, h):
        if self.znorms is None or tag not in self.znorms:
            return None
        zn = self.znorms[tag]
        lead = h.shape[:-1]
        if zn.shape != lead:   # broadcast per-sample cache over positions
            zn = jnp.broadcast_to(
                zn.reshape(zn.shape + (1,) * (len(lead) - zn.ndim)), lead)
        return zn

    def linear(self, tag: str, h, w, bias=None, lora=None):
        """Estimator (+optionally LoRA) linear.  w: Boxed-free raw array.

        The estimator config is resolved per fully-prefixed tag through
        ``Policy.config_for`` (per-layer rules + budget schedules)."""
        tag = self.tag_prefix + tag
        self._record_tag(tag, SAMPLED_DIM_TOKEN if h.ndim >= 3
                         else SAMPLED_DIM_ROWS)
        cfg = self.policy.config_for(tag)
        if self.compute_dtype is not None:
            w = w.astype(self.compute_dtype)
            if bias is not None:
                bias = bias.astype(self.compute_dtype)
        zn = self._znorm_for(tag, h)
        if lora is not None and self.policy.lora.enabled:
            return lora_linear(h, w, lora["lora_a"], lora["lora_b"],
                               self.policy.lora, key=self._key_for(tag),
                               znorm=zn, cfg=cfg, bias=bias)
        return wtacrs_linear(h, w, key=self._key_for(tag), znorm=zn,
                             cfg=cfg, bias=bias)

    def linear_shared(self, tags, h, ws, biases=None):
        """Shared-plan multi-linear (one stored H' for all of ``ws``).

        Per-tag resolution: sharing a plan requires all tags to resolve
        to the SAME config whose estimator supports shared plans; when
        rules split the group (e.g. attn_q sampled, attn_k exact) each
        weight falls back to its own independent linear."""
        full_tags = [self.tag_prefix + t for t in tags]
        for tag in full_tags:
            self._record_tag(tag, SAMPLED_DIM_TOKEN if h.ndim >= 3
                             else SAMPLED_DIM_ROWS)
        cfgs = [self.policy.config_for(t) for t in full_tags]
        if self.compute_dtype is not None:
            ws = [w.astype(self.compute_dtype) for w in ws]
            if biases is not None:
                biases = [None if b is None else
                          b.astype(self.compute_dtype) for b in biases]
        zn = self._znorm_for(full_tags[0], h)

        shareable = (self.key is not None
                     and all(c == cfgs[0] for c in cfgs)
                     and not cfgs[0].is_exact
                     and est_registry.get_estimator(
                         cfgs[0].kind).supports_shared)
        if not shareable:
            outs = []
            for i, w in enumerate(ws):
                bias = None if biases is None else biases[i]
                outs.append(wtacrs_linear(
                    h, w, key=self._key_for(full_tags[i]),
                    znorm=self._znorm_for(full_tags[i], h),
                    cfg=cfgs[i], bias=bias))
            return tuple(outs)
        from repro.core.linear import wtacrs_linear_shared
        return wtacrs_linear_shared(
            h, ws, key=self._key_for("+".join(full_tags)), znorm=zn,
            cfg=cfgs[0], biases=biases)

    def fold(self, i) -> "Ctx":
        """Sub-context for layer/repeat i (folds the PRNG key)."""
        key = None if self.key is None else jax.random.fold_in(self.key, i)
        return dataclasses.replace(self, key=key)


EXACT_POLICY = Policy()
