"""granite-4.0-h-micro — hybrid: Mamba-2 layers and GQA attention layers.

[hf: ibm-granite/granite-4.0-h-micro config.json, model_type
granitemoehybrid] 40L d_model=2048: 36 Mamba-2 layers (64 heads of 64,
state 128, 1 group, conv 4, chunk 256) and GQA attention at layers 5, 15,
25 and 35 (32 query heads of 64, 8 KV heads, no position embedding),
each layer with its own weights and a SwiGLU MLP of 8192 after its mixer;
vocab 100352, tied embeddings.  Embeddings scale by 12, every residual
branch by 0.22, attention scores by 1/64, logits by 1/8.  About 3.19B
parameters.
"""

from repro.models.config import ModelConfig, SSMConfig

_ATTENTION = (5, 15, 25, 35)

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab=100352,
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, n_groups=1,
                  conv_width=4, chunk=256),
    layer_types=tuple("attention" if i in _ATTENTION else "mamba"
                      for i in range(40)),
    rope=False,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    tie_embeddings=True,
    max_seq_len=131072,
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro",
)
