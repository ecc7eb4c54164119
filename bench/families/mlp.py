"""The system under test for the paper-model configurations: the
program's own MLP loss (``repro.models.small``) over feature/label
clients, driven through ``StreamScheduler`` and ``RoundEngine``."""
from __future__ import annotations


def program_kwargs(cfg) -> dict:
    """What StreamScheduler takes for this family's model."""
    from repro.configs.paper import PaperModelConfig
    from repro.models.small import make_loss_fn

    m = cfg["model"]
    if len(set(m["hidden"])) != 1 or len(m["hidden"]) != 2:
        raise ValueError("the program's MLP has two equal hidden layers")
    pcfg = PaperModelConfig(name=cfg["name"], kind="mlp",
                            input_shape=tuple(m["input_shape"]),
                            n_classes=m["n_classes"], hidden=m["hidden"][0])
    return {"loss_fn": make_loss_fn(pcfg)}


def work(cfg) -> dict:
    """FLOPs and tokens of one applied local step of one client."""
    import numpy as np

    from bench.lib.flops import mlp_flops_per_sample

    m = cfg["model"]
    sizes = [int(np.prod(m["input_shape"]))] + list(m["hidden"]) + \
        [m["n_classes"]]
    B = cfg["training"]["batch"]
    return {"flops": B * mlp_flops_per_sample(sizes), "tokens": 0}
