"""Model API dispatch + input specs for every (arch x shape) cell.

``input_specs(cfg, shape)`` returns jax.ShapeDtypeStruct stand-ins for
every model input of that cell — weak-type-correct, shardable, zero
allocation — which is what the multi-pod dry-run lowers against.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, InputShape
from repro.models import common as cm
from repro.models import encdec, lm


def init_params(cfg: ArchConfig, key: jax.Array):
    """Returns (params, logical_axes) twin trees."""
    boxed = (encdec.init_params(cfg, key) if cfg.is_encdec
             else lm.init_params(cfg, key))
    return cm.unbox(boxed)


def abstract_params(cfg: ArchConfig):
    """(ShapeDtypeStruct params, logical_axes) without any allocation."""
    boxed = jax.eval_shape(
        lambda k: (encdec.init_params(cfg, k) if cfg.is_encdec
                   else lm.init_params(cfg, k)),
        jax.random.PRNGKey(0))
    # eval_shape keeps Boxed as a pytree node: leaves are shapes; rebuild
    params = jax.tree.map(lambda b: b.value, boxed,
                          is_leaf=lambda x: isinstance(x, cm.Boxed))
    axes = jax.tree.map(lambda b: b.axes, boxed,
                        is_leaf=lambda x: isinstance(x, cm.Boxed))
    return params, axes


def forward(cfg, params, batch, policy, key=None, znorms=None):
    if cfg.is_encdec:
        return encdec.forward(cfg, params, batch, policy, key, znorms)
    return lm.forward(cfg, params, batch, policy, key, znorms)


def loss_fn(cfg, params, batch, policy, key=None, znorms=None):
    if cfg.is_encdec:
        return encdec.loss(cfg, params, batch, policy, key, znorms)
    return lm.lm_loss(cfg, params, batch, policy, key, znorms)


def prefill(cfg, params, batch, policy):
    if cfg.is_encdec:
        raise NotImplementedError(
            "enc-dec prefill == prime_cross_cache + decode loop")
    return lm.prefill(cfg, params, batch, policy)


def decode_state_init(cfg, batch_size: int, max_len: int):
    if cfg.is_encdec:
        return encdec.decode_state_init(cfg, batch_size, max_len,
                                        enc_len=max_len // 2)
    return lm.decode_state_init(cfg, batch_size, max_len)


def decode_step(cfg, params, token, pos, states, policy):
    """``pos``: scalar (aligned batch) or (B,) per-slot positions
    (continuous batching; decoder-only LMs only)."""
    if cfg.is_encdec:
        return encdec.decode_step(cfg, params, token, pos, states, policy)
    return lm.decode_step(cfg, params, token, pos, states, policy)


def block_decode_init(cfg, btype: str, batch_size: int, max_len: int):
    """Un-stacked decode state of one block type (serve-pool builder)."""
    if cfg.is_encdec:
        raise NotImplementedError(
            "enc-dec decode state is monolithic (decode_state_init); "
            "the per-block slot pool serves decoder-only LMs")
    return lm.block_decode_init(cfg, btype, batch_size, max_len)


def serve_compatible(cfg: ArchConfig) -> Tuple[bool, str]:
    """Whether the continuous-batching serve path supports this arch,
    with the reason when it does not (surfaced by ``ServeSpec`` at
    construction instead of erroring mid-serve)."""
    if cfg.is_encdec:
        return False, (
            "encoder-decoder arch: decode requires a primed per-batch "
            "cross-attention cache and a shared scalar position, which "
            "the ragged slot pool cannot provide; serve decoder-only "
            "LMs (dense/MoE/SSM/hybrid/VLM)")
    if cfg.is_mla:
        return False, (
            "latent attention (MLA): the slot pool pages one (KVH, Dh) "
            "geometry for both K and V, while MLA caches a kv_lora_rank-"
            "wide latent and one shared rope key per token; serving MLA "
            "is out of scope")
    return True, ""


# ---------------------------------------------------------------------------
# Input specs
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def train_batch_specs(cfg: ArchConfig, batch: int, seq: int
                      ) -> Dict[str, Any]:
    """ShapeDtypeStructs for one training/prefill batch."""
    if cfg.family == "vlm":
        s_vis = int(seq * cfg.vis_tokens_frac)
        s_vis = max(8, (s_vis // 8) * 8)     # aligned, never zero
        s_txt = seq - s_vis
        return {
            "tokens": _sds((batch, s_txt), jnp.int32),
            "labels": _sds((batch, s_txt), jnp.int32),
            "patches": _sds((batch, s_vis, cfg.d_model), cfg.cdtype),
            "positions3": _sds((3, batch, seq), jnp.int32),
        }
    if cfg.is_encdec:
        s_half = seq // 2
        return {
            "frames": _sds((batch, s_half, cfg.d_model), cfg.cdtype),
            "tokens": _sds((batch, s_half), jnp.int32),
            "labels": _sds((batch, s_half), jnp.int32),
        }
    return {
        "tokens": _sds((batch, seq), jnp.int32),
        "labels": _sds((batch, seq), jnp.int32),
    }


def decode_specs(cfg: ArchConfig, batch: int, kv_len: int):
    """(token, pos, states) specs for one serve step."""
    token = _sds((batch,), jnp.int32)
    pos = _sds((), jnp.int32)
    states = jax.eval_shape(
        lambda: decode_state_init(cfg, batch, kv_len))
    return token, pos, states


def input_specs(cfg: ArchConfig, shape: InputShape):
    """Dry-run entry: all input ShapeDtypeStructs for this cell."""
    if shape.kind in ("train", "prefill"):
        return train_batch_specs(cfg, shape.global_batch, shape.seq_len)
    return decode_specs(cfg, shape.global_batch, shape.seq_len)


def make_synthetic_batch(cfg: ArchConfig, batch: int, seq: int,
                         key: jax.Array) -> Dict[str, Any]:
    """Concrete random batch matching train_batch_specs (tests/examples)."""
    specs = train_batch_specs(cfg, batch, seq)
    out = {}
    for name, s in specs.items():
        # crc32, not hash(): str hashes are salted per process
        # (PYTHONHASHSEED), which would break batch reproducibility.
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) % (2 ** 31))
        if s.dtype == jnp.int32 and name in ("tokens", "labels"):
            out[name] = jax.random.randint(k, s.shape, 0, cfg.vocab_size,
                                           jnp.int32)
        elif name == "positions3":
            pos = jnp.arange(s.shape[-1])[None, None]
            out[name] = jnp.broadcast_to(pos, s.shape).astype(jnp.int32)
        else:
            out[name] = jax.random.normal(k, s.shape, jnp.float32).astype(
                s.dtype)
    return out
