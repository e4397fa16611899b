"""deepseek-v2-lite [moe]: 27L d_model=2048 16H vocab=102400 -- MLA
kv_lora=512 without q LoRA, 64 routed experts (width 1408) top-6 with raw
softmax gates + 2 shared, first layer dense (SwiGLU 10944), YaRN rope.
[hf:deepseek-ai/DeepSeek-V2-Lite, config.json]

The published ``config.json`` keys this follows: ``num_hidden_layers``
27, ``hidden_size`` 2048, ``num_attention_heads`` 16 (``num_key_value_heads``
16), ``kv_lora_rank`` 512, ``q_lora_rank`` null, ``qk_nope_head_dim`` 128,
``qk_rope_head_dim`` 64, ``v_head_dim`` 128, ``first_k_dense_replace`` 1
with ``intermediate_size`` 10944, ``n_routed_experts`` 64 of
``moe_intermediate_size`` 1408, ``num_experts_per_tok`` 6, ``scoring_func``
softmax, ``topk_method`` greedy, ``norm_topk_prob`` false,
``routed_scaling_factor`` 1, ``n_shared_experts`` 2 (one SwiGLU of width
2 x 1408), ``rms_norm_eps`` 1e-6, ``rope_theta`` 10000 with
``rope_scaling`` yarn (factor 40, original 4096, beta_fast 32, beta_slow
1, mscale 0.707, mscale_all_dim 0.707), ``vocab_size`` 102400,
``tie_word_embeddings`` false, ``attention_bias`` false.

Departure: the rope dims rotate as halves (i, i + 32), where the
published code pairs (2i, 2i + 1); the two agree when each head's 64 rope
columns of ``wq`` and the 64 of ``wkv_a`` are permuted by
``[0, 2, ..., 62, 1, 3, ..., 63]`` (tests/test_dsv2_block.py).
"""

from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=10944,             # dense (first) layer ffn
        vocab_size=102400,
        mla=True,
        kv_lora_rank=512,
        q_lora_rank=0,
        rope_head_dim=64,
        nope_head_dim=128,
        v_head_dim=128,
        n_experts=64,
        experts_per_token=6,
        n_shared_experts=2,
        moe_d_ff=1408,
        first_dense_layers=1,
        norm_topk_prob=False,
        routed_scaling=1.0,
        router_aux_weight=0.001,   # aux_loss_alpha (not in config.json)
        rope_theta=1e4,
        yarn_factor=40.0,
        yarn_original_max_pos=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        norm_eps=1e-6,
        dtype="bfloat16",
    )
