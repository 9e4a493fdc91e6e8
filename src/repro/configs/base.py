"""Model / run configuration.

One `ModelConfig` describes any architecture in the assigned pool (dense, MoE,
SSM, hybrid, audio-encoder, VLM). Per-arch files in this package instantiate it
with the exact assigned hyperparameters and cite their source.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    topk: int
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # shared dense ffn alongside experts (qwen3 style shared expert): 0 = none
    d_shared_ff: int = 0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 -> ceil(d_model/16)


@dataclasses.dataclass(frozen=True)
class XLSTMConfig:
    # layer pattern: 1 = mLSTM, 0 = sLSTM; tiled across n_layers
    pattern: tuple = (1, 0)
    mlstm_proj_factor: float = 2.0
    slstm_proj_factor: float = 4.0 / 3.0
    chunk_size: int = 256


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    citation: str = ""

    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    xlstm: Optional[XLSTMConfig] = None

    # attention
    rope_theta: float = 1e6
    sliding_window: int = 0          # 0 = full attention
    causal: bool = True              # False for encoder-only (hubert)

    # hybrid (jamba): attention mixer every `attn_every` layers (else mamba);
    # MoE ffn every `moe_every` layers (else dense d_ff)
    attn_every: int = 0
    moe_every: int = 0

    # modality frontend stubs
    n_patches: int = 0               # vlm: number of precomputed patch embeddings
    audio_frontend: bool = False     # audio: input is frame embeddings, not tokens

    # ffn style: gated SwiGLU (llama lineage) vs plain GELU MLP (GPT/BERT)
    mlp_gated: bool = True

    # KV-cache storage: "native" (compute dtype) | "int8" (per-token-head
    # absmax quantization; ~2x cache memory at serve time)
    kv_cache_dtype: str = "native"

    # numerics
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # MiniCPM's published scalings; None (every other model) emits no op. The
    # token embeddings times `scale_emb`; each residual branch (attention,
    # mamba, ffn, moe) times `residual_scale`; the final normed state times
    # `logit_scale` before the head. Constants of the published model: a
    # depth or width override leaves them as they are.
    scale_emb: Optional[float] = None
    residual_scale: Optional[float] = None
    logit_scale: Optional[float] = None

    # which attention implementation ("xla" for dry-run lowering, "pallas" on TPU)
    attn_impl: str = "xla"
    # remat policy for the scanned layer stack: "none" | "full" | "dots"
    remat: str = "full"

    def __post_init__(self):
        assert self.d_model % self.n_heads == 0, (self.name, self.d_model, self.n_heads)
        assert self.n_heads % max(self.n_kv_heads, 1) == 0

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads

    @property
    def is_encoder_only(self) -> bool:
        return not self.causal

    @property
    def supports_decode(self) -> bool:
        return self.causal

    def supports_long_context(self) -> bool:
        """True if decode at 500k tokens is sub-quadratic/bounded-memory."""
        if self.arch_type in ("ssm", "hybrid"):
            return True
        return self.sliding_window > 0

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    def layer_is_attn(self, i: int) -> bool:
        if self.arch_type == "ssm":
            return False
        if self.arch_type == "hybrid":
            # jamba: 1 attention layer per `attn_every` (offset mid-period)
            return i % self.attn_every == self.attn_every // 2
        return True

    def layer_is_moe(self, i: int) -> bool:
        if self.moe is None:
            return False
        if self.arch_type == "hybrid":
            return i % self.moe_every == self.moe_every - 1
        return True

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self, n_layers: int = 2, d_model: int = 256, vocab: int = 512) -> "ModelConfig":
        """Smoke-test variant: same family, tiny dims (see brief: <=4 experts)."""
        n_heads = min(self.n_heads, 4)
        n_kv = max(1, min(self.n_kv_heads, n_heads))
        while n_heads % n_kv:
            n_kv -= 1
        d_model = max(d_model, n_heads * 32)
        moe = None
        if self.moe is not None:
            moe = dataclasses.replace(self.moe, n_experts=4, topk=min(self.moe.topk, 2), d_shared_ff=0)
        period = max(self.attn_every, self.moe_every, 1)
        n_layers = max(n_layers, period if self.arch_type == "hybrid" else n_layers)
        if self.xlstm is not None:
            n_layers = max(n_layers, len(self.xlstm.pattern))
        return dataclasses.replace(
            self,
            n_layers=n_layers,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            d_ff=4 * d_model if self.d_ff else 0,
            vocab_size=vocab,
            moe=moe,
            n_patches=min(self.n_patches, 16) if self.n_patches else 0,
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else 0,
            param_dtype="float32",
            compute_dtype="float32",
            remat="none",
        )


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}
