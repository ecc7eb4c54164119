"""GQA attention: chunked-causal train/prefill path + cached decode path.

Sliding-window archs use a ring-buffer cache of `window` slots so the
long_500k decode shape carries O(window), not O(seq), state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.rotary import apply_rope
from repro.models.sharding import BATCH, constrain

NEG_INF = -1e30


def _mask_bias(valid):
    return jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Core: chunked causal attention (train / prefill)
# ---------------------------------------------------------------------------


def causal_attention(q, k, v, *, window: int = 0, q_offset=0,
                     chunk: int = 512, remat_chunks: bool = False):
    """q: (B,S,H,hd)  k,v: (B,S,KV,hd)  ->  (B,S,H,hd).

    Scans over query chunks; each chunk attends to the full key range under
    a causal (+ optional sliding-window) mask.  FLOPs are ~2x the causal
    optimum (future blocks are masked, not skipped) — the Pallas
    flash_attention kernel is the optimized TPU path.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    c = min(chunk, S)
    while S % c:
        c //= 2
    nc = S // c
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    qr = q.reshape(B, nc, c, KV, g, hd)
    qr = jnp.moveaxis(qr, 1, 0)  # (nc, B, c, KV, g, hd)
    kpos = jnp.arange(S)

    def body(carry, inp):
        i, q_chunk = inp
        qpos = q_offset + i * c + jnp.arange(c)
        s = jnp.einsum("bckgd,bskd->bkgcs", q_chunk, k,
                       preferred_element_type=jnp.float32) * scale
        valid = kpos[None, :] <= qpos[:, None]
        if window:
            valid &= kpos[None, :] > qpos[:, None] - window
        s = s + _mask_bias(valid)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bkgcs,bskd->bckgd", w, v)
        return carry, o

    if remat_chunks:
        # §Perf: do not save per-chunk (c, S) softmax probs for backward —
        # recompute them.  Cuts the dominant HBM-traffic term of the train
        # shapes at ~+30% attention FLOPs.
        body = jax.checkpoint(body)
    _, outs = jax.lax.scan(body, None, (jnp.arange(nc), qr))
    # note: v head dim may differ from q/k head dim (MLA)
    outs = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, v.shape[-1])
    return outs


# ---------------------------------------------------------------------------
# Core: single-token decode against a (ring-buffer) cache
# ---------------------------------------------------------------------------


def decode_attention(q, k_cache, v_cache, pos_map, pos, *, window: int = 0):
    """q: (B,1,H,hd); caches: (B,Slots,KV,hd); pos_map: (Slots,) absolute
    position held by each slot (-1 = empty)."""
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    g = H // KV
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    s = jnp.einsum("bckgd,bskd->bkgcs",
                   q.reshape(B, 1, KV, g, hd), k_cache,
                   preferred_element_type=jnp.float32) * scale
    valid = (pos_map >= 0) & (pos_map <= pos)
    if window:
        valid &= pos_map > pos - window
    s = s + _mask_bias(valid)[None, None, None, None, :]
    w = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bkgcs,bskd->bckgd", w, v_cache)
    return o.reshape(B, 1, H, hd)


# ---------------------------------------------------------------------------
# Projected GQA layer
# ---------------------------------------------------------------------------


def gqa_project_qkv(p, x, cfg, positions):
    """Projections are stored flattened (d, H*hd); reshape to heads here."""
    B, S = x.shape[0], x.shape[1]
    hd = cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, P(BATCH, None, "model", None))
    k = constrain(k, P(BATCH, None, "model", None))
    v = constrain(v, P(BATCH, None, "model", None))
    return q, k, v


def _expand_kv(k, v, cfg):
    """§Perf: repeat kv heads to H so q/k/v/probs all shard on one head
    axis (no grouped-dim resharding per chunk).  Mathematically identical
    to grouped attention; AD sums replica grads onto the kv projections."""
    rep = cfg.n_heads // cfg.n_kv_heads
    k = constrain(jnp.repeat(k, rep, axis=2), P(BATCH, None, "model", None))
    v = constrain(jnp.repeat(v, rep, axis=2), P(BATCH, None, "model", None))
    return k, v


def gqa_attention(p, x, cfg, positions, cache=None, decode=False):
    """Full GQA block.  Returns (out, updated_cache_or_None).

    positions: (S,) int32 absolute positions of the rows of x (decode: (1,)).
    cache (per layer): {"k": (B,Slots,KV,hd), "v": ..., "pos_map": (Slots,)}.
    """
    q, k, v = gqa_project_qkv(p, x, cfg, positions)
    B = q.shape[0]
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    # caches store the kv dim flattened (KV*hd) so argument shardings stay
    # divisible by the 16-way model axis even for small kv-head counts
    unflat = lambda c: c.reshape(B, c.shape[1], KV, hd)
    if decode:
        assert cache is not None
        slots = cache["k"].shape[1]
        pos = positions[0]
        slot = (pos % slots).astype(jnp.int32)
        k_cache = cache["k"].at[:, slot].set(k[:, 0].reshape(B, KV * hd))
        v_cache = cache["v"].at[:, slot].set(v[:, 0].reshape(B, KV * hd))
        pos_map = cache["pos_map"].at[slot].set(pos.astype(jnp.int32))
        o = decode_attention(q, unflat(k_cache), unflat(v_cache), pos_map,
                             pos, window=cfg.sliding_window)
        new_cache = {"k": k_cache, "v": v_cache, "pos_map": pos_map}
    else:
        ka, va = (k, v)
        if cfg.expand_gqa and cfg.n_kv_heads < cfg.n_heads:
            ka, va = _expand_kv(k, v, cfg)
        if cfg.attn_impl == "flash" and not cfg.sliding_window:
            # Pallas flash kernel (forward-only: serving prefill path)
            from repro.kernels.flash_attention import flash_attention
            if ka.shape[2] < q.shape[2]:
                ka, va = _expand_kv(k, v, cfg)
            o = flash_attention(q.swapaxes(1, 2), ka.swapaxes(1, 2),
                                va.swapaxes(1, 2)).swapaxes(1, 2)
        else:
            o = causal_attention(q, ka, va, window=cfg.sliding_window,
                                 q_offset=positions[0],
                                 remat_chunks=cfg.remat_attention)
        new_cache = None
        if cache is not None:  # prefill: populate the (ring-buffer) cache
            slots = cache["k"].shape[1]
            S = k.shape[1]
            keep = max(0, S - slots)  # ring buffer keeps the last `slots`
            write_slots = (positions[keep:] % slots).astype(jnp.int32)
            kf = k[:, keep:].reshape(B, S - keep, KV * hd)
            vf = v[:, keep:].reshape(B, S - keep, KV * hd)
            k_cache = cache["k"].at[:, write_slots].set(kf)
            v_cache = cache["v"].at[:, write_slots].set(vf)
            pm = cache["pos_map"].at[write_slots].set(
                positions[keep:].astype(jnp.int32))
            new_cache = {"k": k_cache, "v": v_cache, "pos_map": pm}
    out = o.reshape(B, o.shape[1], -1) @ p["wo"]
    if "bo" in p:
        out = out + p["bo"]
    return out, new_cache
