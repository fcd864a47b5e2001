"""Architecture configuration schema + input-shape definitions.

Every assigned architecture instantiates ``ArchConfig`` in its own module
(``repro/configs/<id>.py``) with the exact published hyper-parameters, and
provides a ``reduced()`` variant of the same family for CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax.numpy as jnp

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
           "float16": jnp.float16}


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | vlm | ssm | audio | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 0                # 0 -> d_model // n_heads
    # block stacking: the repeating unit; n_layers must divide evenly
    pattern: Tuple[str, ...] = ("attn",)
    # attention
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    pos_mode: str = "rope"         # rope | mrope | learned | none
    # mlp
    mlp_type: str = "swiglu"       # swiglu | gelu | relu2
    # moe
    n_experts: int = 0             # experts held here (the chip's share)
    moe_top_k: int = 0
    capacity_factor: float = 1.25
    moe_d_ff: int = 0              # expert width; 0 -> d_ff
    n_shared_experts: int = 0      # run for every token: one SwiGLU of
    #                                n_shared_experts * moe_d_ff
    router_experts: int = 0        # experts routed over; 0 -> n_experts
    expert_offset: int = 0         # held: [offset, offset + n_experts)
    router: str = "softmax"        # softmax (capacity dispatch) | sigmoid
    #                                (DeepSeek-V3 noaux_tc, no drops)
    routed_scaling: float = 1.0    # sigmoid router's weight scale
    first_dense_layers: int = 0    # leading dense layers (in n_layers),
    #                                run once before the scanned pattern
    # latent attention (MLA, DeepSeek-V2/V3): kv_lora_rank 0 = none
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False  # rotate (x0,x1),(x2,x3).. pairs
    # ssm / recurrent (mamba2, xlstm)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_head_dim: int = 64
    # norm / residual
    norm_type: str = "rmsnorm"     # rmsnorm | layernorm
    norm_eps: float = 1e-5
    residual_scale: float = 1.0    # depth scaling (MiniCPM)
    tie_embeddings: bool = False
    # encoder-decoder (whisper): encoder depth; n_layers is the decoder depth
    encoder_layers: int = 0
    # modality frontend stub: input_specs() provides precomputed embeddings
    frontend: str = "none"         # none | audio_frames | vision_patches
    vis_tokens_frac: float = 0.25  # VLM: fraction of seq that is patches
    # numerics
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    max_learned_pos: int = 4096

    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head else self.d_model // self.n_heads

    @property
    def n_repeats(self) -> int:
        n = self.n_layers - self.first_dense_layers
        assert n % len(self.pattern) == 0, (
            f"{self.name}: n_layers={self.n_layers} less "
            f"{self.first_dense_layers} dense not divisible by "
            f"pattern of length {len(self.pattern)}")
        return n // len(self.pattern)

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def qk_head_dim(self) -> int:
        """Query/key head width (MLA: nope + rope parts)."""
        if self.is_mla:
            return self.qk_nope_dim + self.qk_rope_dim
        return self.head_dim

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def n_routed(self) -> int:
        """Width of the router: the experts every token chooses among."""
        return self.router_experts or self.n_experts

    @property
    def pdtype(self):
        return _DTYPES[self.param_dtype]

    @property
    def cdtype(self):
        return _DTYPES[self.compute_dtype]

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic (state-based) sequence mixing => long_500k runs."""
        return any(b in ("mamba", "mlstm", "slstm") for b in self.pattern)

    def _attn_params(self) -> int:
        d, h = self.d_model, self.n_heads
        if self.is_mla:
            r, dn, dv = self.kv_lora_rank, self.qk_nope_dim, self.v_head_dim
            return (d * h * self.qk_head_dim + d * (r + self.qk_rope_dim)
                    + r + r * h * (dn + dv) + h * dv * d)
        dh = self.head_dim
        return d * (h * dh) + 2 * d * (self.n_kv_heads * dh) + (h * dh) * d

    def _mlp_params(self, d_ff: int) -> int:
        return (3 if self.mlp_type == "swiglu" else 2) * self.d_model * d_ff

    def _moe_params(self, n_experts: int) -> int:
        """One expert layer holding ``n_experts``: router (and its bias
        for a sigmoid router), experts, shared experts."""
        d, f = self.d_model, self.expert_d_ff
        router = d * self.n_routed
        if self.router == "sigmoid":
            router += self.n_routed
        shared = (self._mlp_params(self.n_shared_experts * f)
                  if self.n_shared_experts else 0)
        return router + n_experts * 3 * d * f + shared

    def n_params(self) -> int:
        """Parameters held here (the experts of this share), norms
        left out (used for 6ND model-FLOPs)."""
        d = self.d_model
        per_attn = self._attn_params()
        per_mlp = self._mlp_params(self.d_ff)
        total = self.first_dense_layers * (per_attn + per_mlp)
        for b in self.pattern * self.n_repeats:
            if b in ("attn", "xattn", "shared_attn"):
                total += per_attn + per_mlp
                if b == "xattn":
                    total += per_attn  # cross-attention projections
            elif b == "attn_moe":
                total += per_attn + self._moe_params(self.n_experts)
            elif b == "mamba":
                d_in = self.ssm_expand * d
                # in_proj (d -> 2*di + 2*N + H), conv, out_proj
                nh = d_in // self.ssm_head_dim
                total += d * (2 * d_in + 2 * self.ssm_state + nh) \
                    + (d_in + 2 * self.ssm_state) * self.ssm_conv \
                    + d_in * d
            elif b == "mlstm":
                d_in = self.ssm_expand * d
                # up (d -> 2di), q/k/v (di x di), gates, down (di -> d)
                total += 2 * d * d_in + 3 * d_in * d_in \
                    + 2 * d_in * self.n_heads + d_in * d
            elif b == "slstm":
                dh_ = d // self.n_heads
                # w_in (d -> 4d), recurrent R (H, dh, 4dh), down (d -> d)
                total += 4 * d * d + self.n_heads * dh_ * 4 * dh_ + d * d
        total += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        if self.is_encdec:
            # encoder blocks + decoder cross-attention + learned positions
            total += self.encoder_layers * (per_attn + per_mlp)
            total += self.n_layers * per_attn
            total += 2 * self.max_learned_pos * d
        return total

    def n_active_params(self) -> int:
        """Active parameters per token (MoE: the top-k experts count,
        wherever they are held, and the shared experts)."""
        if self.n_experts == 0:
            return self.n_params()
        n_moe = sum(b == "attn_moe" for b in self.pattern) * self.n_repeats
        expert = 3 * self.d_model * self.expert_d_ff
        return self.n_params() + n_moe * expert * (self.moe_top_k
                                                   - self.n_experts)


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


def shape_applicable(cfg: ArchConfig, shape: InputShape) -> Tuple[bool, str]:
    """Whether an (arch x shape) cell runs, with the reason if skipped."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return False, ("pure full-attention architecture: 500k-token decode "
                       "needs sub-quadratic sequence mixing (DESIGN.md "
                       "S Arch-applicability)")
    return True, ""
