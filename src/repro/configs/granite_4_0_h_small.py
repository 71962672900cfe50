"""granite-4.0-h-small [hf:ibm-granite/granite-4.0-h-small; hf]

Granite 4.0-H Small (32B-A9B, model type ``granitemoehybrid``): 40 layers,
d_model 4096, Mamba-2 mixers everywhere but attention at layers 5, 15, 25
and 35 (a period of 10: 9 Mamba, 1 attention).  Mamba-2: 128 heads of 64,
state 128, one group, conv 4, expand 2, chunk 256.  Attention: GQA, 32
query and 8 KV heads of 128, no position embedding (NoPE), scale 1/128.
Every mixer is followed by an MoE layer of 72 experts of width 768, top-10,
plus a shared expert of width 1536 (two of 768).  Multipliers: embedding
12, residual 0.22, logits divided by 16.  Vocabulary 100352, tied.
"""
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

_PATTERN = ("mamba",) * 5 + ("attn",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    moe=MoEConfig(n_experts=72, top_k=10, n_shared_experts=2, d_expert=768),
    ssm=SSMConfig(state_dim=128, head_dim=64, expand=2, conv_width=4,
                  chunk_size=256, n_groups=1),
    layer_pattern=_PATTERN,
    position_embedding="none",
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attention_multiplier=1.0 / 128,
    tie_embeddings=True,
    source="[hf:ibm-granite/granite-4.0-h-small; hf]",
)

# two periods of one Mamba and one attention layer; each MoE layer holds 3
# of its 8 experts (ids 2..4), as one chip of an expert-parallel group
SMOKE = ModelConfig(
    name="granite-4.0-h-small-smoke",
    family="hybrid",
    n_layers=4,
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    d_ff=32,
    vocab_size=512,
    moe=MoEConfig(n_experts=8, top_k=3, n_shared_experts=2, d_expert=32,
                  n_held=3, first_held=2),
    ssm=SSMConfig(state_dim=16, head_dim=16, expand=2, conv_width=4,
                  chunk_size=8, n_groups=1),
    layer_pattern=("mamba", "attn"),
    position_embedding="none",
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attention_multiplier=1.0 / 16,
    tie_embeddings=True,
)
