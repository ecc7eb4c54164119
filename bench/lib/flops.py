"""Operations and bytes the algorithms need, from the configuration's
shapes alone: what a run is charged for, whatever implements it.

Counts are of the algorithm as published, never of what a compiler
emitted: padding, relayouts and recomputation (remat) are not work.
"""
from __future__ import annotations


def mlp_params(sizes) -> int:
    """Parameters of a dense MLP with biases, e.g. [784, 200, 200, 10]."""
    return sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))


def mlp_flops_per_sample(sizes) -> float:
    """Forward (2 per weight) plus backward (4 per weight) of one sample:
    6 x parameters (biases counted as weights, as the 6N rule does)."""
    return 6.0 * mlp_params(sizes)


def mamba2_matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul per token: every layer's input
    projection (z, x, B, C, dt) and output projection, and the tied
    logits head over the logical vocabulary.  The embedding gather is
    not a matmul."""
    d = cfg["d_model"]
    d_in = cfg["expand"] * d
    G, N = cfg["ngroups"], cfg["d_state"]
    H = d_in // cfg["headdim"]
    in_proj = d * (2 * d_in + 2 * G * N + H)
    out_proj = d_in * d
    return cfg["n_layer"] * (in_proj + out_proj) + d * cfg["vocab_size"]


def mamba2_ssd_flops_per_token(cfg: dict) -> float:
    """Forward FLOPs per token of one layer's chunked SSD (chunk Q):
    C.B^T within the chunk (2QN per group), the masked mixing of the
    chunk's inputs (2QP per head), the chunk-state build and the
    inter-chunk output (2NP per head each)."""
    d_in = cfg["expand"] * cfg["d_model"]
    P, N, Q = cfg["headdim"], cfg["d_state"], cfg["chunk_size"]
    H, G = d_in // P, cfg["ngroups"]
    return 2.0 * Q * N * G + H * (2.0 * Q * P + 4.0 * N * P)


def mamba2_flops_per_token(cfg: dict) -> float:
    """Forward + backward (3 x forward) FLOPs per trained token: the
    matmul weights (6 per weight) and the SSD terms of every layer.
    Remat recompute is excluded."""
    return (6.0 * mamba2_matmul_params(cfg)
            + 3.0 * cfg["n_layer"] * mamba2_ssd_flops_per_token(cfg))


def weighted_agg_cost(K: int, D: int, wire_bytes: int = 4):
    """(flops, bytes) of one weighted aggregation out[d] = sum_k c_k x_kd
    over the unpadded shapes: one multiply-add per delta element; the
    (K, D) deltas at the wire's width, the K coefficients and the (D,)
    f32 result."""
    return 2.0 * K * D, float(K * D * wire_bytes + K * 4 + D * 4)
