"""Unified model configuration covering all assigned architecture families.

One dataclass describes dense / MoE / SSM / hybrid / enc-dec / VLM backbones;
the per-arch files in ``repro/configs`` instantiate it with the exact
published hyperparameters plus a ``reduced()`` variant for CPU smoke tests.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # 'dense' | 'moe' | 'ssm' | 'hybrid' | 'encdec' | 'vlm'
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: int | None = None
    mlp: str = "swiglu"  # 'swiglu' | 'relu2' | 'gelu'
    attn: str = "gqa"  # 'gqa' | 'mla' | 'none'
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # MiniCPM's muP scalings (1.0 leaves a model's programs unchanged):
    # embeddings x embed_scale; each attention and MLP branch x
    # residual_scale before its residual add; the final hidden state x
    # logit_scale before the LM head
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0

    # --- MoE ---------------------------------------------------------------
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # GShard dispatch group size: per-expert capacity C = cf*k*group/E, and
    # the dispatch einsum costs 2*T*(E*C)*d = 2.5*k*T*group*d FLOPs — small
    # groups keep it negligible when the expert axis cannot shard (grok: 8
    # experts on a 16-way axis -> replicated dispatch; §Perf B4)
    moe_group_size: int = 4096

    # --- MLA (multi-head latent attention; minicpm3 / deepseek family) -----
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    # --- SSM (mamba2 SSD) ---------------------------------------------------
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_ngroups: int = 1
    ssm_chunk: int = 128

    # --- hybrid (zamba2): one shared attn+mlp block every `attn_every` ssm
    # layers; the shared block's params are reused at each invocation site.
    attn_every: int = 0

    # --- enc-dec (whisper) --------------------------------------------------
    n_enc_layers: int = 0

    # --- modality frontend (stub provides precomputed embeddings) ----------
    frontend: str = "none"  # 'none' | 'audio_stub' | 'patch_stub'
    n_frontend_tokens: int = 0  # patch/frame token count for vlm prefill mix

    max_seq: int = 131_072
    dtype: Any = jnp.bfloat16

    # sharding rule overrides for this arch (e.g. FSDP for >=100B)
    sharding_overrides: Mapping[str, Any] | None = None
    # remat / grad-accum defaults used by the training step at scale
    remat: bool = True
    microbatches: int = 1

    # ------------------------------------------------------------------
    @property
    def padded_vocab_size(self) -> int:
        """Vocab padded to a multiple of 512 (Megatron practice) so the
        embedding/unembedding and the training logits always divide the
        16-way model axis (and 32-way model x pod products).  Logits in the
        padded tail are masked to -inf in the loss and in decode argmax."""
        return -(-self.vocab_size // 512) * 512

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic sequence mixing (SSM/hybrid) — eligible for the
        long_500k shape cell."""
        return self.family in ("ssm", "hybrid")

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def gated_mlp(self) -> bool:
        return self.mlp == "swiglu"

    @property
    def mla_qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # Rough parameter count used for multicast volume & roofline MODEL_FLOPS.
    def approx_params(self) -> int:
        from repro.models.transformer import param_template

        from repro.distributed.sharding import param_count

        return param_count(param_template(self))

    def approx_active_params(self) -> int:
        """Active parameters per token (MoE: routed experts only)."""
        total = self.approx_params()
        if self.n_experts and self.top_k:
            from repro.models.transformer import param_template
            from repro.distributed.sharding import param_count

            # expert params scale by top_k / n_experts
            tmpl = param_template(self)
            expert = tmpl["layers"].get("moe") if isinstance(tmpl.get("layers"), dict) else None
            if expert is not None:
                e_count = param_count(expert)
                total = total - e_count + (e_count * self.top_k) // self.n_experts
        return total
