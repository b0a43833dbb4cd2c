"""granite-4.0-h-micro — Mamba-2 and attention layers in one dense stack
[hf:ibm-granite/granite-4.0-h-micro, config.json; model_type
granitemoehybrid].

40L d_model=2048: Mamba-2 mixers in 36 layers and GQA attention (32 query,
8 kv heads of 64, no positional encoding) at layers 5, 15, 25 and 35; every
layer's mixer is followed by a SwiGLU MLP of width 8192 (no experts).
Mamba-2: 64 heads of 64 (expand 2), state 128, 1 group, conv 4 with bias,
chunk 256, no projection bias.  Vocabulary 100,352, tied embeddings.
Granite's scalars: the embedding times 12, each residual branch times
0.22, attention scale 1/64, logits over 8.  RMSNorm eps 1e-5.
"""

import dataclasses

from repro.configs.base import ArchConfig

ATTENTION_LAYERS = (5, 15, 25, 35)

CONFIG = ArchConfig(
    name="granite-4.0-h-micro",
    family="dense",
    source="hf:ibm-granite/granite-4.0-h-micro",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100_352,
    layer_types=tuple(
        "attention" if i in ATTENTION_LAYERS else "mamba" for i in range(40)
    ),
    attention_multiplier=0.015625,
    pos_embed="none",
    ssm_state=128,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_chunk=256,
    conv_width=4,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    rms_norm_eps=1e-5,
    tie_embeddings=True,
)


def reduced() -> ArchConfig:
    """Two Mamba-2 layers around one attention layer, at toy widths."""
    return dataclasses.replace(
        CONFIG,
        num_layers=3,
        layer_types=("mamba", "attention", "mamba"),
        d_model=64,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        d_ff=128,
        vocab_size=256,
        ssm_state=16,
        ssm_head_dim=16,
        ssm_chunk=8,
    )
