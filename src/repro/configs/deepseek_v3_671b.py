"""deepseek-v3-671b [moe] — MLA attention, 1 shared + 256 routed top-8 experts.
[arXiv:2412.19437; hf deepseek-ai/DeepSeek-V3 config.json]

Router as published (``topk_method`` noaux_tc): sigmoid scores, a
correction bias used only to choose, 8 groups of which a token routes into
the top 4 (group score: the sum of its top-2 biased scores), top-8, weights
from the unbiased scores normalised and scaled by 2.5. Rope on the
decoupled 64 dims under YaRN (factor 40 over 4096 positions), which also
scales the softmax by mscale(40, 1)^2. The MTP (multi-token prediction)
head drafts tokens for speculative decoding and is not modeled. The
assigned d_ff=2048 is the per-expert hidden dim; the first 3 layers use a
dense FFN of 18432 per the released config.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, RopeScaling

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    family="lm",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    d_head=128,            # v head dim
    d_ff=2048,             # per-expert hidden (assigned)
    vocab_size=129280,
    act="silu",
    mlp_kind="glu",
    mla=MLAConfig(
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_dim=128,
        qk_rope_dim=64,
        v_dim=128,
    ),
    moe=MoEConfig(num_experts=256, top_k=8, d_expert=2048, num_shared=1,
                  scoring="sigmoid", n_group=8, topk_group=4,
                  routed_scale=2.5, norm_topk=True),
    moe_every=1,
    first_k_dense=3,
    dense_d_ff=18432,
    rope_theta=1e4,
    rope_scaling=RopeScaling(factor=40, original_max_position_embeddings=4096,
                             beta_fast=32, beta_slow=1, mscale=1,
                             mscale_all_dim=1),
    norm_eps=1e-6,
)
