"""Parameter initialization.  Per-layer params are stacked with a leading
(n_layers,) dim for lax.scan; statistically equivalent per-layer normal init.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.common import norm_init


def _split(key, n):
    return list(jax.random.split(key, n))


def _dense(key, shape, dtype, scale=0.02):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def _norm(cfg, d=None):
    return norm_init(d or cfg.d_model, jnp.float32,
                     cfg.norm == "layernorm")


def _attn_params(key, cfg, dtype, L=None):
    """GQA or MLA attention params; leading (L,) stack dim if L given.

    Head-structured projections are stored FLATTENED ((d, H*hd) etc.):
    every assigned arch's H*hd product divides the 16-way model axis, while
    raw head counts (56, 25, 24, 5, 2, ...) do not — this keeps argument
    shardings divisible and exact (no padded heads).  Forward code reshapes.
    """
    s = (L,) if L else ()
    ks = _split(key, 8)
    d, hd = cfg.d_model, cfg.head_dim
    if cfg.use_mla:
        p = {}
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        if cfg.q_lora_rank:
            p["w_dq"] = _dense(ks[0], s + (d, cfg.q_lora_rank), dtype)
            p["q_ln"] = {"scale": jnp.zeros(s + (cfg.q_lora_rank,), jnp.float32)}
            p["w_uq"] = _dense(ks[1], s + (cfg.q_lora_rank, cfg.n_heads * qk), dtype)
        else:
            p["wq"] = _dense(ks[1], s + (d, cfg.n_heads * qk), dtype)
        p["w_dkv"] = _dense(ks[2], s + (d, cfg.kv_lora_rank + cfg.qk_rope_dim), dtype)
        p["kv_ln"] = {"scale": jnp.zeros(s + (cfg.kv_lora_rank,), jnp.float32)}
        p["w_uk"] = _dense(ks[3], s + (cfg.kv_lora_rank, cfg.n_heads * cfg.qk_nope_dim), dtype)
        p["w_uv"] = _dense(ks[4], s + (cfg.kv_lora_rank, cfg.n_heads * cfg.v_head_dim), dtype)
        p["wo"] = _dense(ks[5], s + (cfg.n_heads * cfg.v_head_dim, d), dtype)
        return p
    p = {
        "wq": _dense(ks[0], s + (d, cfg.n_heads * hd), dtype),
        "wk": _dense(ks[1], s + (d, cfg.n_kv_heads * hd), dtype),
        "wv": _dense(ks[2], s + (d, cfg.n_kv_heads * hd), dtype),
        "wo": _dense(ks[3], s + (cfg.n_heads * hd, d), dtype),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros(s + (cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros(s + (cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros(s + (cfg.n_kv_heads * hd,), dtype)
        p["bo"] = jnp.zeros(s + (d,), dtype)
    return p


def _mlp_params(key, cfg, dtype, d_ff, L=None):
    s = (L,) if L else ()
    ks = _split(key, 3)
    d = cfg.d_model
    p = {}
    if cfg.gated_mlp:
        p["w_gate"] = _dense(ks[0], s + (d, d_ff), dtype)
    p["w_up"] = _dense(ks[1], s + (d, d_ff), dtype)
    p["w_down"] = _dense(ks[2], s + (d_ff, d), dtype)
    if cfg.use_bias:
        p["b_up"] = jnp.zeros(s + (d_ff,), dtype)
        p["b_down"] = jnp.zeros(s + (d,), dtype)
    return p


def _norm_params(cfg, L=None, d=None):
    s = (L,) if L else ()
    d = d or cfg.d_model
    p = {"scale": jnp.zeros(s + (d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros(s + (d,), jnp.float32)
    return p


def _ssm_params(key, cfg, dtype, L=None):
    s = (L,) if L else ()
    ks = _split(key, 8)
    d = cfg.d_model
    d_in = cfg.d_inner
    H = cfg.ssm_n_heads
    G, N, K = cfg.ssm_n_groups, cfg.ssm_d_state, cfg.ssm_d_conv
    conv_ch = d_in + 2 * G * N
    rng = np.random.default_rng(0)
    a_init = jnp.log(jnp.asarray(
        rng.uniform(1.0, 16.0, size=(H,)), jnp.float32))
    dt_init = jnp.log(jnp.expm1(jnp.asarray(
        np.clip(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=(H,))),
                1e-4, None), jnp.float32)))
    bc = lambda a: jnp.broadcast_to(a, s + a.shape) if L else a
    return {
        "in_z": _dense(ks[0], s + (d, d_in), dtype),
        "in_x": _dense(ks[1], s + (d, d_in), dtype),
        "in_B": _dense(ks[2], s + (d, G * N), dtype),
        "in_C": _dense(ks[3], s + (d, G * N), dtype),
        "in_dt": _dense(ks[4], s + (d, H), dtype),
        "conv_w": _dense(ks[5], s + (K, conv_ch), dtype, scale=0.1),
        "conv_b": jnp.zeros(s + (conv_ch,), dtype),
        "A_log": bc(a_init),
        "D": jnp.ones(s + (H,), jnp.float32),
        "dt_bias": bc(dt_init),
        "ssm_norm": jnp.zeros(s + (d_in,), jnp.float32),
        "out_proj": _dense(ks[6], s + (d_in, d), dtype),
    }


def _moe_params(key, cfg, dtype, L=None):
    """The router scores all n_experts; only the held experts' weights
    are here (cfg.moe_experts_held, all of them by default)."""
    s = (L,) if L else ()
    ks = _split(key, 6)
    d, f = cfg.d_model, cfg.moe_d_ff
    E = cfg.n_experts
    held = cfg.moe_experts_held or E
    p = {
        "router": _dense(ks[0], s + (d, E), jnp.float32, scale=0.006),
        "experts": {
            "w_up": _dense(ks[2], s + (held, d, f), dtype),
            "w_down": _dense(ks[3], s + (held, f, d), dtype),
        },
    }
    if cfg.gated_mlp:
        p["experts"]["w_gate"] = _dense(ks[1], s + (held, d, f), dtype)
    if cfg.router_score == "sigmoid":
        p["router_bias"] = jnp.zeros(s + (E,), jnp.float32)
    if cfg.n_shared_experts:
        fs = cfg.moe_shared_d_ff or cfg.moe_d_ff * cfg.n_shared_experts
        p["shared"] = {
            "w_up": _dense(ks[5], s + (d, fs), dtype),
            "w_down": _dense(ks[4], s + (fs, d), dtype),
        }
        if cfg.gated_mlp:
            p["shared"]["w_gate"] = _dense(ks[4], s + (d, fs), dtype)
    return p


def _block_params(key, cfg, kind, dtype, L):
    ks = _split(key, 6)
    p = {"ln1": _norm_params(cfg, L)}
    if kind == "ssm":
        p["ssm"] = _ssm_params(ks[0], cfg, dtype, L)
    elif kind == "expert":
        p["moe"] = _moe_params(ks[1], cfg, dtype, L)
    elif kind == "attention":
        p["attn"] = _attn_params(ks[0], cfg, dtype, L)
    elif kind == "hybrid":
        p["attn"] = _attn_params(ks[0], cfg, dtype, L)
        p["ssm"] = _ssm_params(ks[1], cfg, dtype, L)
        p["ln_a"] = _norm_params(cfg, L)
        p["ln_s"] = _norm_params(cfg, L)
        p["ln2"] = _norm_params(cfg, L)
        p["mlp"] = _mlp_params(ks[2], cfg, dtype, cfg.d_ff, L)
    elif kind == "moe":
        p["attn"] = _attn_params(ks[0], cfg, dtype, L)
        p["ln2"] = _norm_params(cfg, L)
        p["moe"] = _moe_params(ks[1], cfg, dtype, L)
    else:  # dense
        p["attn"] = _attn_params(ks[0], cfg, dtype, L)
        p["mlp"] = _mlp_params(ks[2], cfg, dtype, cfg.d_ff, L)
        if not cfg.parallel_residual:
            p["ln2"] = _norm_params(cfg, L)
    return p


# layer_pattern characters -> (params_key, block kind): every layer of a
# kind is one row of that kind's stack, in the order the pattern has them
PATTERN_KINDS = {"M": ("ssm_blocks", "ssm"),
                 "E": ("expert_blocks", "expert"),
                 "*": ("attn_blocks", "attention")}


def block_kinds(cfg: ArchConfig):
    """Returns [(params_key, kind, n_layers), ...] stack layout."""
    if cfg.layer_pattern:
        if len(cfg.layer_pattern) != cfg.n_layers:
            raise ValueError(f"layer_pattern has {len(cfg.layer_pattern)} "
                             f"layers, n_layers is {cfg.n_layers}")
        return [PATTERN_KINDS[ch] + (cfg.layer_pattern.count(ch),)
                for ch in dict.fromkeys(cfg.layer_pattern)]
    if cfg.family == "ssm":
        return [("blocks", "ssm", cfg.n_layers)]
    if cfg.family == "hybrid":
        return [("blocks", "hybrid", cfg.n_layers)]
    if cfg.family == "moe":
        out = []
        if cfg.first_k_dense:
            out.append(("dense_blocks", "dense", cfg.first_k_dense))
        out.append(("moe_blocks", "moe", cfg.n_layers - cfg.first_k_dense))
        return out
    return [("blocks", "dense", cfg.n_layers)]


def init_params(key, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.dtype)
    ks = _split(key, 8)
    params = {}
    V = cfg.vocab_padded
    if cfg.n_codebooks:
        params["embed"] = _dense(ks[0], (cfg.n_codebooks, V,
                                         cfg.d_model), dtype)
    else:
        params["embed"] = _dense(ks[0], (V, cfg.d_model), dtype)
    for i, (name, kind, L) in enumerate(block_kinds(cfg)):
        params[name] = _block_params(ks[1 + i], cfg, kind, dtype, L)
    params["final_norm"] = _norm_params(cfg)
    if not cfg.tie_embeddings:
        if cfg.n_codebooks:
            params["lm_head"] = _dense(ks[4], (cfg.n_codebooks, cfg.d_model,
                                               V), dtype)
        else:
            params["lm_head"] = _dense(ks[4], (cfg.d_model, V), dtype)
    if cfg.mtp_depth:
        params["mtp"] = {
            "mtp_proj": _dense(ks[5], (2 * cfg.d_model, cfg.d_model), dtype),
            "block": _block_params(ks[6], cfg, "dense", dtype, None),
            "norm": _norm_params(cfg),
        }
    return params


def param_count(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
