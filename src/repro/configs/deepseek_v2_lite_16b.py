"""deepseek-v2-lite-16b [moe] — arXiv:2405.04434; hf-verified against
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json.

27L d_model=2048 16H vocab=102400.  MLA with kv_lora_rank 512, no q LoRA,
nope/rope/v head dims 128/64/128, DeepSeek's interleaved rope with YaRN
(factor 40 over 4096 original positions, beta 32/1, mscale 0.707 both ways,
so the softmax scale is 192^-0.5 * (0.1 * 0.707 * ln 40 + 1)^2).  The first
layer is dense (``first_k_dense_replace=1``, d_ff=10944); the other 26 have
64 routed experts of width 1408, top-6 from a softmax router with greedy
selection and no renormalisation (``norm_topk_prob`` false,
``routed_scaling_factor`` 1), plus 2 shared experts.  RMSNorm eps 1e-6.
"""

from repro.configs.base import ModelConfig, register


@register
def deepseek_v2_lite_16b() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-16b",
        family="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        d_ff=10944,                    # dense first layer
        vocab_size=102400,
        prefix_pattern=(("mla", "mlp"),),
        unit_pattern=(("mla", "moe"),),
        rope_theta=10000.0,
        rope_interleaved=True,
        yarn_factor=40.0,
        yarn_original_max_pos=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        kv_lora_rank=512,
        q_lora_rank=0,                 # lite: direct q projection
        qk_nope_dim=128,
        qk_rope_dim=64,
        v_head_dim=128,
        num_experts=64,
        num_shared_experts=2,
        top_k=6,
        d_ff_expert=1408,
        norm_topk_prob=False,
        routed_scaling_factor=1.0,
        norm_eps=1e-6,
    )
