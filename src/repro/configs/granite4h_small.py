"""granite-4.0-h-small [hybrid]: 40L d_model=4096; 36 Mamba-2 layers (128
heads of 64, d_state 128, 1 group, conv 4 with bias, chunk 256) and 4 NoPE
GQA attention layers (32 q / 8 kv heads of 128, score scale 1/128) at 5, 15,
25 and 35; after every mixer a MoE of 72 SwiGLU experts of width 768, top-10
with a softmax over the 10 chosen logits, plus a shared SwiGLU of width
1,536; embedding x12, residual x0.22, logits /16; RMSNorm eps 1e-5; tied
embeddings, vocab 100,352.
Source: https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json
(Granite 4.0-H Small, 32B-A9B); equations as ``transformers``'
``modeling_granitemoehybrid.py``."""

from repro.models import ModelConfig

LAYER_TYPES = tuple(
    "attention" if i in (5, 15, 25, 35) else "mamba" for i in range(40)
)

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    vocab_size=100352,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,
    activation="swiglu",
    num_experts=72,
    top_k=10,
    shared_d_ff=1536,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_chunk=256,
    ssm_conv_width=4,
    layer_types=LAYER_TYPES,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attention_multiplier=0.0078125,
    norm_eps=1e-5,
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
    fed_mode="vmap",
    fed_clients=16,
)
