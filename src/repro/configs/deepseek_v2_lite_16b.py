"""deepseek-v2-lite-16b [moe] — MLA with YaRN, shared + routed MoE top-6.

DeepSeek-V2-Lite as published
(https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json;
arXiv:2405.04434): 27 layers, d_model 2048, 16 heads, vocab 102400,
untied head, RMSNorm eps 1e-6, SiLU.  MLA without q compression:
kv_lora_rank 512, qk_nope 128, qk_rope 64, v 128, its rope key scaled by
YaRN (factor 40 over 4096 positions, beta_fast 32, beta_slow 1, mscale =
mscale_all_dim = 0.707, theta 10000).  The first layer has a dense MLP
of width 10944 (first_k_dense_replace 1), the other 26 hold 64 routed
experts of width 1408 plus 2 shared ones; the router takes float32
softmax scores, greedy top-6, no renormalisation, routed_scaling_factor 1.
Expressed as prefix (the dense layer) + period (one MoE layer).
"""
from .base import LayerSpec, MLACfg, ModelConfig, MoECfg, YarnCfg

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=10944,                      # dense first-layer MLP
    vocab=102400,
    prefix=(LayerSpec(mixer="mla", ffn="mlp"),),
    pattern=(LayerSpec(mixer="mla", ffn="moe"),),
    rope_theta=10000.0,
    rope_yarn=YarnCfg(factor=40.0, original_max_pos=4096, beta_fast=32.0,
                      beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    mla=MLACfg(q_lora_rank=0, kv_lora_rank=512, nope_dim=128, rope_dim=64,
               v_dim=128),
    moe=MoECfg(n_experts=64, top_k=6, d_ff=1408, n_shared=2,
               norm_topk_prob=False, routed_scale=1.0, router_f32=True),
    activation="silu",
)

REDUCED = CONFIG.replace(
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=256,
    vocab=512,
    mla=MLACfg(q_lora_rank=0, kv_lora_rank=32, nope_dim=16, rope_dim=8,
               v_dim=16),
    moe=MoECfg(n_experts=8, top_k=2, d_ff=32, n_shared=1,
               norm_topk_prob=False, router_f32=True))
