"""Per-layer blocks: dense (GQA/MLA + MLP), MoE, SSM (Mamba2), hybrid
(parallel attention + SSM heads, Hymba-style), and the single-mixer
layers of a layer pattern (Nemotron-H): ssm, expert (routed-expert FFN
alone) and attention (attention alone)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import gqa_attention
from repro.models.common import apply_norm, mlp_apply, rmsnorm
from repro.models.mla import mla_attention
from repro.models.moe import moe_ffn
from repro.models.ssd import mamba_mixer


def _attn(p, x, cfg, positions, cache, decode):
    """Attention under the device scope ``lm.attn``."""
    with jax.named_scope("lm.attn"):
        if cfg.use_mla:
            return mla_attention(p, x, cfg, positions, cache=cache,
                                 decode=decode)
        return gqa_attention(p, x, cfg, positions, cache=cache,
                             decode=decode)


def block_apply(p, x, cfg, kind, positions, cache=None, decode=False):
    """Returns (x_out, aux_loss, new_cache)."""
    aux = jnp.float32(0.0)
    new_cache = {}
    cache = cache or {}

    if kind == "ssm":
        h = apply_norm(x, p["ln1"], cfg)
        y, c = mamba_mixer(p["ssm"], h, cfg, cache=cache.get("ssm"),
                           decode=decode)
        if c is not None:
            new_cache["ssm"] = c
        x = x + y

    elif kind == "expert":
        h = apply_norm(x, p["ln1"], cfg)
        y, aux_moe = moe_ffn(p["moe"], h, cfg)
        aux = aux + aux_moe
        x = x + y

    elif kind == "attention":
        h = apply_norm(x, p["ln1"], cfg)
        a, ca = _attn(p["attn"], h, cfg, positions, cache.get("attn"), decode)
        if ca is not None:
            new_cache["attn"] = ca
        x = x + a

    elif kind == "hybrid":
        h = apply_norm(x, p["ln1"], cfg)
        a, ca = _attn(p["attn"], h, cfg, positions, cache.get("attn"), decode)
        s, cs = mamba_mixer(p["ssm"], h, cfg, cache=cache.get("ssm"),
                            decode=decode)
        if ca is not None:
            new_cache["attn"] = ca
        if cs is not None:
            new_cache["ssm"] = cs
        # Hymba: per-branch norm, mean combine
        y = 0.5 * (rmsnorm(a, p["ln_a"]["scale"], cfg.norm_eps)
                   + rmsnorm(s, p["ln_s"]["scale"], cfg.norm_eps))
        x = x + y
        h2 = apply_norm(x, p["ln2"], cfg)
        x = x + mlp_apply(p["mlp"], h2, cfg)

    elif kind == "moe":
        h = apply_norm(x, p["ln1"], cfg)
        a, ca = _attn(p["attn"], h, cfg, positions, cache.get("attn"), decode)
        if ca is not None:
            new_cache["attn"] = ca
        x = x + a
        h2 = apply_norm(x, p["ln2"], cfg)
        # expert-parallel dispatch is safe whenever we are NOT under the
        # client vmap: serving paths (decode / prefill-with-cache) and
        # client_sequential training
        ep = decode or bool(cache) or cfg.fed.mode == "client_sequential"
        y, aux_moe = moe_ffn(p["moe"], h2, cfg, ep=ep)
        aux = aux + aux_moe
        x = x + y

    else:  # dense
        if cfg.parallel_residual:
            h = apply_norm(x, p["ln1"], cfg)
            a, ca = _attn(p["attn"], h, cfg, positions, cache.get("attn"),
                          decode)
            if ca is not None:
                new_cache["attn"] = ca
            x = x + a + mlp_apply(p["mlp"], h, cfg)
        else:
            h = apply_norm(x, p["ln1"], cfg)
            a, ca = _attn(p["attn"], h, cfg, positions, cache.get("attn"),
                          decode)
            if ca is not None:
                new_cache["attn"] = ca
            x = x + a
            h2 = apply_norm(x, p["ln2"], cfg)
            x = x + mlp_apply(p["mlp"], h2, cfg)

    return x, aux, (new_cache or None)
