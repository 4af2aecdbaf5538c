"""Kimi-Linear-48B-A3B-Instruct — 27 layers, d_model 2304, three KDA
(Kimi Delta Attention) layers to one MLA layer: KDA at 1-indexed layers
1-3, 5-7, ..., 25, 26, MLA at 4, 8, ..., 24 and 27; KDA with 32 heads of
128 and a short conv of 4; MLA (NoPE) with 32 heads, qk 128 + 64 (the
64 shared by every head), v 128, kv latent 512, no q latent; layer 1's
feed-forward a dense SwiGLU of width 9216, every other layer 256 experts
of width 1024 under a sigmoid router (top-8, renormalised, x2.446, one
group) plus one shared expert; RMSNorm eps 1e-5; vocab 163840, untied.
[hf:moonshotai/Kimi-Linear-48B-A3B-Instruct config.json;
arXiv:2510.26692]
"""
from .base import ArchConfig
from .registry import register

_KDA_LAYERS = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
               23, 25, 26)

CONFIG = register(ArchConfig(
    name="kimi-linear-48b-a3b-instruct",
    family="kda_mla_moe",
    num_layers=27,
    layer_types=tuple("kda" if i in _KDA_LAYERS else "mla"
                      for i in range(1, 28)),
    d_model=2304,
    num_heads=32,
    num_kv_heads=32,
    head_dim=128,             # KDA head size
    kda_heads=32,
    kda_gate_rank=128,        # assumed (not in config.json)
    ssm_conv=4,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    d_ff=1024,                # per-expert hidden
    shared_ff=1024,
    dense_ff=9216,
    first_dense_layers=1,
    vocab_size=163840,
    rope_theta=0.0,           # mla_use_nope
    tie_embeddings=False,
    norm_eps=1e-5,
    num_experts=256,
    top_k=8,
    router="sigmoid",
    routed_scaling=2.446,
    attn_chunk=512,
    remat=False,
    source="hf:moonshotai/Kimi-Linear-48B-A3B-Instruct; hf",
))
