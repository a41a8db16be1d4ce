"""granite-4.0-h-small — [hybrid] Mamba2 and NoPE-attention layers, each
followed by a dropless mixture of experts.  40L (36 Mamba2, attention at
5, 15, 25, 35), d_model=4096; Mamba2 128 heads x 64, d_state 128, one
group, conv 4 with bias, expand 2, chunk 256; attention 32H kv=8 x 128
with no position embedding and score scale 1/128; MoE 72 experts top-10
(softmax over the chosen 10) of width 768 plus one shared expert of
1536; embeddings x12, residual branches x0.22, logits /16; vocab 100352,
tied.  [hf ibm-granite/granite-4.0-h-small config.json; hf]"""
from repro.configs.base import ModelConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    arch_id="granite-4.0-h-small", family="hybrid",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=768, vocab_size=100352,
    n_experts=72, top_k=10, n_shared_experts=1, shared_expert_ff=1536,
    router="topk_softmax",
    ssm_state=128, ssm_expand=2, ssm_headdim=64, ssm_conv=4, ssm_chunk=256,
    layer_types=_PERIOD * 4,
    position_embedding="nope", attention_multiplier=0.0078125,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=16.0,
    rope_theta=10000.0, norm_eps=1e-5, act="silu", glu=True,
    tie_embeddings=True,
    source="[hf ibm-granite/granite-4.0-h-small config.json; hf]",
)
