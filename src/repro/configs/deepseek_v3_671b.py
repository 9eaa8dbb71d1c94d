"""deepseek-v3-671b [moe]: 61L, d_model=7168, 128H (kv=128 via MLA), MoE 256e
top-8 (+1 shared), moe_d_ff=2048, vocab=129280, MLA latent attention, MTP.

Widths, routing and rotary scaling as the model's published config.json
(https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json;
arXiv:2412.19437): sigmoid ``noaux_tc`` routing over 8 groups keeping 4,
normalised top-8 weights times 2.5; YaRN factor 40 over 4096 original
positions (beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 1); RMSNorm
eps 1e-6; one MTP layer."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    d_ff=18432,                 # dense FFN for the first_dense_layers
    vocab_size=129280,
    attn_type="mla",
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    mlp_type="swiglu",
    n_experts=256,
    n_experts_active=8,
    n_shared_experts=1,
    moe_d_ff=2048,
    first_dense_layers=3,
    moe_scoring="sigmoid",
    moe_router_bias=True,
    moe_n_group=8,
    moe_topk_group=4,
    moe_norm_topk=True,
    moe_routed_scaling=2.5,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale_all_dim=1.0,
    norm_eps=1e-6,
    mtp=True,
    notes="MLA compressed-latent KV cache; 1 shared + 256 routed top-8",
)
