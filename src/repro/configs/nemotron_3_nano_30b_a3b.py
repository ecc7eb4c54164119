"""NVIDIA Nemotron-3-Nano-30B-A3B (model_type nemotron_h), from its
published config.json.  52 single-mixer layers in the published pattern:
23 Mamba-2 (M; 64 heads of 64, d_state 128, 8 groups, conv 4, chunk 128),
23 routed-expert FFNs (E; 128 experts, top-6, width 1856, relu^2 not
gated, one shared expert of width 3712, a sigmoid router with a
selection-only correction bias, top-k renormalised, routed scaling 2.5)
and 6 GQA attention layers (*; 32 query heads, 2 KV heads of 128, no
rotary embedding).  d_model 2688, vocabulary 131,072, untied head."""
from repro.configs.base import ArchConfig, FedConfig

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

CONFIG = ArchConfig(
    name="nemotron-3-nano-30b-a3b",
    family="hybrid",
    n_layers=len(PATTERN),
    layer_pattern=PATTERN,
    d_model=2688,
    vocab=131072,
    n_heads=32,
    n_kv_heads=2,
    head_dim=128,
    pos_emb="none",
    activation="sq_relu",
    gated_mlp=False,
    norm="rmsnorm",
    norm_eps=1e-5,
    tie_embeddings=False,
    n_experts=128,
    n_shared_experts=1,
    top_k=6,
    moe_d_ff=1856,
    moe_shared_d_ff=3712,
    router_score="sigmoid",
    router_aux_coef=0.0,
    moe_routed_scale=2.5,
    ssm_d_state=128,
    ssm_d_inner=4096,
    ssm_head_dim=64,
    ssm_n_groups=8,
    ssm_norm_groups=8,
    ssm_d_conv=4,
    ssm_chunk=128,
    fed=FedConfig(mode="client_sequential"),
    source="https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16",
)
