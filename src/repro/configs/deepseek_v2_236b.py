"""deepseek-v2-236b [moe+MLA]: 60L d_model=5120 128H MLA kv_lora=512
expert d_ff=1536 vocab=102400, 160 routed top-6 + 2 shared
[arXiv:2405.04434].  Routing and RoPE as the published config.json:
group_limited_greedy over 8 groups (3 per token), gates not
renormalized, x16; yarn RoPE (factor 40 over 4096 positions, theta 1e4,
mscale and mscale_all_dim 0.707)."""
from repro.core import ModelSpec, MoESpec, MLASpec
from repro.models.common import RuntimeCfg

SPEC = ModelSpec(name="deepseek-v2-236b", n_layers=60, d_model=5120,
                 n_heads=128, n_kv_heads=128, d_ff=12288, vocab=102400,
                 d_head=128, block="mla",
                 mla=MLASpec(kv_lora=512, q_lora=1536, rope_dim=64,
                             nope_dim=128, v_dim=128, rope_theta=10000.0,
                             rope_factor=40.0, rope_original_max=4096,
                             beta_fast=32.0, beta_slow=1.0,
                             mscale_all_dim=0.707),
                 moe=MoESpec(n_experts=160, top_k=6, n_shared=2,
                             d_expert=1536, first_dense=True, n_group=8,
                             topk_group=3, norm_topk=False,
                             routed_scale=16.0))
SMOKE = ModelSpec(name="dsv2-smoke", n_layers=3, d_model=128, n_heads=8,
                  n_kv_heads=8, d_ff=256, vocab=512, d_head=16, block="mla",
                  mla=MLASpec(kv_lora=32, q_lora=48, rope_dim=8, nope_dim=16,
                              v_dim=16, rope_factor=40.0,
                              mscale_all_dim=0.707),
                  moe=MoESpec(n_experts=8, top_k=2, n_shared=2, d_expert=64,
                              first_dense=True, n_group=4, topk_group=2,
                              norm_topk=False, routed_scale=16.0))
RUNTIME = RuntimeCfg()
SKIP = {}
