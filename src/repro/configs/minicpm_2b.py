"""minicpm-2b [dense] — MiniCPM 2.4B, llama-like, trained with the WSD
(warmup-stable-decay) schedule which repro.optim.schedules implements.
[arXiv:2404.06395; openbmb/MiniCPM-2B-sft-bf16]

Its numerics differ from Llama's by three published scalars (config.json:
scale_emb 12, scale_depth 1.4, dim_model_base 256): the embeddings times
12, each residual branch times scale_depth / sqrt(40), the final normed
state times dim_model_base / 2304. The multipliers are taken at the
published depth and width, so a stack cut to fewer layers (a pipeline
stage of the published model) keeps them. A width override keeps them too:
they are then not the muP values of that width (dim_model_base / d_model)."""
import math

from repro.configs.base import ModelConfig

N_LAYERS, D_MODEL = 40, 2304
SCALE_EMB, SCALE_DEPTH, DIM_MODEL_BASE = 12.0, 1.4, 256

CONFIG = ModelConfig(
    name="minicpm-2b",
    arch_type="dense",
    n_layers=N_LAYERS,
    d_model=D_MODEL,
    n_heads=36,
    n_kv_heads=36,
    d_ff=5760,
    vocab_size=122753,
    tie_embeddings=True,
    sliding_window=8192,
    scale_emb=SCALE_EMB,
    residual_scale=SCALE_DEPTH / math.sqrt(N_LAYERS),
    logit_scale=DIM_MODEL_BASE / D_MODEL,
    citation="arXiv:2404.06395",
)
