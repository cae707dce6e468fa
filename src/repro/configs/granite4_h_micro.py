"""granite-4.0-h-micro [hybrid] — hf:ibm-granite/granite-4.0-h-micro (hf tier).

40 layers by ``layer_types``: attention at layers 5, 15, 25 and 35, Mamba2
everywhere else (64 heads x 64, d_inner 4096, d_state 128, one group, conv
width 4 with bias, chunk 256). Attention is GQA 32/8 at head_dim 64 with no
position embedding (NoPE). Every layer's mixer is followed by a SwiGLU MLP
of 8192. Granite's multipliers: embeddings x12, softmax scale 1/64,
residual branches x0.22, logits / 8. Tied 100,352-row vocabulary.
"""
from repro.configs.base import ModelConfig

LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba" for i in range(40))

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    vocab_size=100352,
    activation="silu",
    norm="rmsnorm",
    tie_embeddings=True,
    pos_embedding="none",
    embedding_multiplier=12.0,
    attention_multiplier=1.0 / 64,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    ssm_state=128,
    ssm_expand=2,
    ssm_conv_width=4,
    ssm_n_groups=1,
    ssm_conv_bias=True,
    layer_types=LAYER_TYPES,
    source="hf:ibm-granite/granite-4.0-h-micro; hf",
)
