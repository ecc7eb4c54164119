"""The system under test for Nemotron-H configurations: the program's
``LMTask`` over its own nemotron-3-nano-30b-a3b ArchConfig with the
configuration's sizes, layer pattern and held experts
(``repro.models``), driven through ``StreamScheduler`` and
``RoundEngine``."""
from __future__ import annotations

import dataclasses


def program_kwargs(cfg) -> dict:
    from repro.configs import get_config
    from repro.fed import LMTask

    m = cfg["model"]
    pattern = cfg["hybrid_override_pattern"]
    arch = dataclasses.replace(
        get_config("nemotron-3-nano-30b-a3b"),
        n_layers=len(pattern), layer_pattern=pattern,
        d_model=cfg["hidden_size"], vocab=m["vocab_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_experts=m["router_experts"], top_k=cfg["num_experts_per_tok"],
        moe_experts_held=cfg["n_routed_experts"],
        moe_expert_offset=m["expert_offset"],
        moe_d_ff=cfg["moe_intermediate_size"],
        moe_shared_d_ff=cfg["moe_shared_expert_intermediate_size"],
        moe_routed_scale=cfg["routed_scaling_factor"],
        ssm_d_state=cfg["ssm_state_size"],
        ssm_d_inner=cfg["mamba_num_heads"] * cfg["mamba_head_dim"],
        ssm_head_dim=cfg["mamba_head_dim"], ssm_n_groups=cfg["n_groups"],
        ssm_norm_groups=cfg["n_groups"], ssm_d_conv=cfg["conv_kernel"],
        ssm_chunk=cfg["chunk_size"], norm_eps=cfg["layer_norm_epsilon"],
        dtype=m["dtype"])
    if arch.vocab_padded != arch.vocab:
        raise ValueError(f"program pads the vocabulary {arch.vocab} to "
                         f"{arch.vocab_padded}; the reference has no "
                         f"padded rows")
    return {"task": LMTask(arch, seq_len=cfg["training"]["seq"],
                           fsdp=False)}


def work(cfg) -> dict:
    from bench.lib.hybrid_flops import flops_per_token

    t = cfg["training"]
    tokens = t["batch"] * t["seq"]
    return {"flops": tokens * flops_per_token(cfg, t["seq"]),
            "tokens": tokens}
