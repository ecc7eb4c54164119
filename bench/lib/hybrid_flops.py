"""Operations and bytes of a Nemotron-H hybrid (Mamba-2, routed-expert
and attention layers in ``hybrid_override_pattern``) from its
configuration's shapes alone: what a run is charged for, whatever
implements it.

As in ``bench/lib/flops.py``: the algorithm as published, never what a
compiler emitted; padding, relayouts and remat recompute are not work.
The routed experts count at the share a chip's held experts take on
average: each token sends top_k x held / router_experts of its slots to
them (the routing of a given batch may send more or fewer).
"""
from __future__ import annotations

BF16 = 2


def _layers(cfg: dict) -> dict:
    p = cfg["hybrid_override_pattern"]
    return {"M": p.count("M"), "E": p.count("E"), "*": p.count("*")}


def expert_share(cfg: dict) -> float:
    """Expected (token, expert) slots a token sends to the held experts of
    one expert layer."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["model"]["router_experts"])


def matmul_params_per_token(cfg: dict) -> float:
    """Weights a token multiplies, per the kinds of layer: the Mamba-2
    in-projection (z, x, B, C, dt) and out-projection; the router, the
    held experts' up and down projections at their expected share and
    the shared expert; q, k, v and o; the untied head over the
    vocabulary.  The embedding gather is not a matmul."""
    d = cfg["hidden_size"]
    d_in = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    mamba = d * (2 * d_in + 2 * gn + cfg["mamba_num_heads"]) + d_in * d
    expert = (d * cfg["model"]["router_experts"]
              + expert_share(cfg) * 2 * d * cfg["moe_intermediate_size"]
              + 2 * d * cfg["moe_shared_expert_intermediate_size"])
    hd = cfg["head_dim"]
    attn = (d * (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])
            * hd + cfg["num_attention_heads"] * hd * d)
    n = _layers(cfg)
    return (n["M"] * mamba + n["E"] * expert + n["*"] * attn
            + d * cfg["model"]["vocab_size"])


def ssd_flops_per_token(cfg: dict) -> float:
    """Forward FLOPs per token of one Mamba-2 layer's chunked SSD (chunk
    Q): C.B^T within the chunk (2QN a group), the masked mixing of the
    chunk's inputs (2QP a head), the chunk-state build and the
    inter-chunk output (2NP a head each)."""
    Q, N = cfg["chunk_size"], cfg["ssm_state_size"]
    H, P, G = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"]
    return 2.0 * Q * N * G + H * (2.0 * Q * P + 4.0 * N * P)


def attn_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward FLOPs per token of one causal attention layer's scores
    and mixing: 2 x 2 x heads x head_dim per key, over (seq + 1) / 2
    keys on average."""
    return (4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
            * (seq + 1) / 2.0)


def flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward (3 x forward) FLOPs per trained token: 6 per
    matmul weight, and the SSD and attention terms of every layer."""
    n = _layers(cfg)
    return (6.0 * matmul_params_per_token(cfg)
            + 3.0 * n["M"] * ssd_flops_per_token(cfg)
            + 3.0 * n["*"] * attn_flops_per_token(cfg, seq))


def moe_gmm_cost(cfg: dict, tokens: int):
    """(flops, bytes) of the held experts' grouped matmuls in one local
    step over ``tokens`` tokens, forward and backward, every expert
    layer: for each projection (d -> f up, f -> d down) over the
    expected rows r, the forward, the input gradient and the weight
    gradient each 2 r a b FLOPs, and each reads or writes the held
    weights once and r rows in and out, all in bfloat16."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    r = tokens * expert_share(cfg)
    w = cfg["n_routed_experts"] * d * f * BF16
    flops = 2 * 3 * 2.0 * r * d * f
    nbytes = 2 * 3 * (w + r * (d + f) * BF16)
    n = _layers(cfg)["E"]
    return n * flops, n * nbytes


def ssd_scan_cost(cfg: dict, tokens: int):
    """(flops, bytes) of the SSD in one local step over ``tokens``
    tokens, forward and backward (3 x forward FLOPs), every Mamba-2
    layer.  Bytes are what the algorithm must move: forward, x (bf16),
    dt and the decay cumsum (f32), B and C (bf16) in and y (bf16) out;
    backward, those inputs and dy in, and the gradient of each input
    out (dx bf16, the rest f32).  The running state stays on chip."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    hp = H * P
    fwd = hp * BF16 + 2 * H * 4 + 2 * gn * BF16 + hp * BF16
    bwd = (2 * hp * BF16 + 2 * H * 4 + 2 * gn * BF16
           + hp * BF16 + 2 * H * 4 + 2 * gn * 4)
    n = _layers(cfg)["M"]
    return (n * 3.0 * ssd_flops_per_token(cfg) * tokens,
            n * float(fwd + bwd) * tokens)
