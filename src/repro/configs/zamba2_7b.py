"""zamba2-7b — hybrid: 81 Mamba2 layers with two B/C groups; before the
Mamba of 13 of them, one of two shared transformer blocks runs in turn on
concat(h, embedding), with a LoRA adapter on its MLP and a d x d linear
of the call's own [arXiv:2411.15242; hf Zyphra/Zamba2-7B-Instruct]."""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="zamba2-7b", family="hybrid",
    num_layers=81, d_model=3584, num_heads=32, num_kv_heads=32,
    head_dim=224,                     # 2 * d_model / num_heads
    d_ff=14336, vocab_size=32000,
    ssm_state=64, ssm_headdim=64, ssm_expand=2, ssm_conv_width=4,
    ssm_chunk=256, ssm_ngroups=2,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2, adapter_rank=128,
    rope_theta=10_000.0, gated_mlp=True, act="gelu", norm="rmsnorm",
    norm_eps=1e-5, tie_embeddings=True,
    source="arXiv:2411.15242; hf Zyphra/Zamba2-7B-Instruct",
)
