"""stablelm-1.6b [dense]: 24L d_model=2048 32H (kv=32) d_ff=5632
vocab=100352 -- LayerNorm, 25% partial rotary.  [hf:stabilityai/stablelm-2-1_6b]

The published ``config.json`` keys this follows: ``num_hidden_layers`` 24,
``hidden_size`` 2048, ``intermediate_size`` 5632 (SwiGLU, ``hidden_act``
silu), ``num_attention_heads`` 32, ``num_key_value_heads`` 32 (head dim
64), ``vocab_size`` 100352, ``tie_word_embeddings`` false, pre-norm
``LayerNorm`` with a learned scale and shift (``layer_norm_eps`` 1e-5),
``use_qkv_bias`` true (biases on q, k and v only), ``partial_rotary_factor``
0.25 (the first 16 of 64 dims, rotate-half), ``rope_theta`` 10000,
``max_position_embeddings`` 4096, sequential residual, no qk-norm,
``torch_dtype`` bfloat16.
"""

from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="stablelm-1.6b",
        family="dense",
        n_layers=24,
        d_model=2048,
        n_heads=32,
        n_kv_heads=32,
        d_ff=5632,
        vocab_size=100352,
        qkv_bias=True,
        rope_theta=10_000.0,
        rope_fraction=0.25,
        norm_type="layer",
        norm_eps=1e-5,
        dtype="bfloat16",
    )
