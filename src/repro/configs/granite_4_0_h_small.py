"""granite-4.0-h-small [mamba_hybrid] — Mamba2 + NoPE GQA mixers, a 72-expert
top-10 MoE with a shared expert in every layer (32B total, 9B active).
[hf:ibm-granite/granite-4.0-h-small, config.json, model_type granitemoehybrid;
https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json]
40L d_model=4096, attention at layers 5/15/25/35 (32H, kv=8, hd=128),
Mamba2 128 heads of 64, d_state 128, expert d_ff=768 (read from
intermediate_size), shared d_ff=1536, vocab=100352 tied."""

from repro.models.config import ModelConfig, MoEConfig, SSMConfig

LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba" for i in range(40))


def config() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-small",
        family="mamba_hybrid",
        num_layers=40,
        d_model=4096,
        num_heads=32,
        num_kv_heads=8,
        d_ff=0,  # no dense MLP: every layer's feed-forward is the MoE
        vocab_size=100_352,
        head_dim=128,
        tied_embeddings=True,
        moe=MoEConfig(num_experts=72, top_k=10, d_ff_expert=768, d_ff_shared=1536),
        ssm=SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64, chunk=256, n_groups=1, conv_bias=True),
        layer_types=LAYER_TYPES,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        attention_multiplier=0.0078125,
        norm_eps=1e-5,
    )
