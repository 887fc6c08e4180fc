"""granite-4.0-h-micro — Mamba-2 layers with a few GQA layers
(GraniteMoeHybrid; ibm-granite/granite-4.0-h-micro config.json).

40L d_model=2048 in the pattern ``mmmmmammmm`` four times: 36 Mamba-2
layers (64 SSD heads of 64, state 128, one B/C group, conv 4 with bias)
and 4 attention layers (32 heads, GQA kv=8, head_dim 64, no positional
encoding, softmax scale 1/64), each followed by a SwiGLU MLP of 8192.
Embeddings x12, residual branches x0.22, logits /8; tied, vocab=100352.
"""
from ..models.config import ModelConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    arch_id="granite-4.0-h-micro", family="hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, d_ff=8192,
    vocab_size=100352, tie_embeddings=True, norm_eps=1e-5,
    ssm_state=128, ssm_expand=2, ssm_head_dim=64, ssm_conv=4,
    ssm_chunk=256, ssm_groups=1, ssm_conv_bias=True,
    layer_types=_PERIOD * 4, embedding_multiplier=12.0,
    residual_multiplier=0.22, attn_scale=0.015625, logits_scaling=8.0,
    rope=False,
    source="huggingface.co/ibm-granite/granite-4.0-h-micro config.json",
)

SMOKE = ModelConfig(
    arch_id="granite-4.0-h-micro-smoke", family="hybrid",
    n_layers=3, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
    vocab_size=512, tie_embeddings=True,
    ssm_state=16, ssm_expand=2, ssm_head_dim=32, ssm_conv=4,
    ssm_chunk=8, ssm_groups=1, ssm_conv_bias=True,
    layer_types=("mamba", "attention", "mamba"), embedding_multiplier=12.0,
    residual_multiplier=0.22, attn_scale=0.0625, logits_scaling=8.0,
    rope=False,
    source="reduced granite-4.0-h family",
)
