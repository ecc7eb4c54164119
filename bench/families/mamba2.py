"""The system under test for Mamba-2 configurations: the program's
``LMTask`` over its own mamba2 ArchConfig with the configuration's sizes
(``repro.models``), driven through ``StreamScheduler`` and
``RoundEngine``."""
from __future__ import annotations

import dataclasses


def program_kwargs(cfg) -> dict:
    from repro.configs import get_config
    from repro.fed import LMTask

    m = cfg["model"]
    arch = dataclasses.replace(
        get_config("mamba2-130m"), n_layers=m["n_layer"],
        d_model=m["d_model"], vocab=m["vocab_size"],
        ssm_d_state=m["d_state"], ssm_expand=m["expand"],
        ssm_head_dim=m["headdim"], ssm_n_groups=m["ngroups"],
        ssm_d_conv=m["d_conv"], ssm_chunk=m["chunk_size"],
        norm_eps=m["rms_norm_eps"], dtype=m["dtype"],
        tie_embeddings=m["tie_embeddings"])
    if arch.vocab_padded != m["vocab_padded"]:
        raise ValueError(f"program pads the vocabulary to "
                         f"{arch.vocab_padded}, config says "
                         f"{m['vocab_padded']}")
    return {"task": LMTask(arch, seq_len=cfg["training"]["seq"],
                           fsdp=False)}


def work(cfg) -> dict:
    from bench.lib.flops import mamba2_flops_per_token

    t = cfg["training"]
    tokens = t["batch"] * t["seq"]
    return {"flops": tokens * mamba2_flops_per_token(cfg["model"]),
            "tokens": tokens}
