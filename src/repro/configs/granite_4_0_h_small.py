"""granite-4.0-h-small — 32B-A9B hybrid: 40 layers, 36 Mamba-2 (SSD) and 4
GQA attention layers with no positional embedding (indices 5, 15, 25, 35),
d_model=4096; Mamba-2 with 128 heads of 64, d_state 128, one group, conv
width 4 with bias, chunk 256; attention 32 query / 8 KV heads of 128,
softmax scale ``attention_multiplier`` = 1/128; after every mixer a MoE
of 72 experts (top-10, width 768) plus one shared SwiGLU expert of width
1536; embedding x12, each branch x0.22 before its residual add, logits
/16; RMSNorm eps 1e-5; vocab 100352, tied.
[hf:ibm-granite/granite-4.0-h-small config.json]
"""
from .base import ArchConfig
from .registry import register

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = register(ArchConfig(
    name="granite-4.0-h-small",
    family="ssm_moe",
    num_layers=40,
    layer_types=_PERIOD * 4,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,                 # per-expert hidden
    shared_ff=1536,
    vocab_size=100352,
    rope_theta=0.0,           # NoPE
    tie_embeddings=True,
    norm_eps=1e-5,
    num_experts=72,
    top_k=10,
    ssm_state=128,
    ssm_headdim=64,
    ssm_ngroups=1,
    ssm_conv=4,
    ssm_expand=2,
    ssm_chunk=256,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attention_multiplier=0.0078125,
    attn_chunk=512,
    remat=False,
    source="hf:ibm-granite/granite-4.0-h-small; hf",
))
