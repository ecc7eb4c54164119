"""Top-level model: embedding, scanned layer stacks, loss, prefill/decode.

All ten assigned architectures run through `model_forward`; family
differences are config- and param-structure-driven (see params.block_kinds).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.blocks import block_apply
from repro.models.common import apply_norm
from repro.models.params import PATTERN_KINDS, block_kinds
from repro.models.rotary import sinusoidal
from repro.models.sharding import BATCH, constrain

LOSS_CHUNK = 512


# ---------------------------------------------------------------------------
# Embedding / heads
# ---------------------------------------------------------------------------


def embed_tokens(params, cfg: ArchConfig, tokens, positions):
    with jax.named_scope("lm.embed"):
        if cfg.n_codebooks:
            # tokens: (B,S,K) — summed codebook embeddings
            parts = [jnp.take(params["embed"][k], tokens[..., k], axis=0)
                     for k in range(cfg.n_codebooks)]
            x = sum(parts)
        else:
            x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.embed_scale:
            x = x * jnp.sqrt(float(cfg.d_model)).astype(x.dtype)
        if cfg.pos_emb == "sinusoidal":
            x = x + sinusoidal(positions, cfg.d_model).astype(x.dtype)
        return constrain(x, P(BATCH, None, None))


def logits_fn(params, cfg: ArchConfig, h):
    """h: (..., d) -> logits (..., V) (audio: (..., K, V)); float32."""
    if cfg.n_codebooks:
        head = params.get("lm_head")
        if head is None:
            head = jnp.swapaxes(params["embed"], 1, 2)
        lg = jnp.einsum("...d,kdv->...kv", h, head,
                        preferred_element_type=jnp.float32)
    else:
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        lg = jnp.einsum("...d,dv->...v", h, head,
                        preferred_element_type=jnp.float32)
    return constrain(lg, P(*([None] * (lg.ndim - 1)), "model"))


# ---------------------------------------------------------------------------
# Layer stacks
# ---------------------------------------------------------------------------


def _run_stack(stack_p, x, cfg, kind, positions, cache=None, decode=False):
    """lax.scan over stacked layer params (+ per-layer cache).

    §Perf (sequence parallelism): in train/prefill the residual stream
    carried between layers is sharded over the *model* axis along the
    sequence dim — the saved-for-backward layer inputs shrink 16x and
    GSPMD turns each block's output psum into reduce-scatter + the next
    block's input all-gather (Megatron SP).  Decode (S=1) is exempt.
    """
    seq_shard = (cfg.seq_parallel and not decode and x.shape[1] > 1)

    def reshard(t):
        if seq_shard:
            return constrain(t, P(BATCH, "model", None))
        return t

    x = reshard(x)

    def body(carry, xs):
        x = carry
        p_layer, cache_layer = xs
        x, aux, new_cache = block_apply(p_layer, x, cfg, kind, positions,
                                        cache=cache_layer, decode=decode)
        return reshard(x), (aux, new_cache)

    if cfg.remat:
        body = jax.checkpoint(body)

    xs = (stack_p, cache)
    if cache is None:
        n_layers = jax.tree.leaves(stack_p)[0].shape[0]
        xs = (stack_p, jnp.zeros((n_layers,), jnp.int32))

        def body_nc(carry, xs):  # cache-free wrapper keeps pytrees static
            p_layer, _ = xs
            x, aux, _ = block_apply(p_layer, carry, cfg, kind, positions,
                                    cache=None, decode=False)
            return reshard(x), (aux, 0)

        body_fn = jax.checkpoint(body_nc) if cfg.remat else body_nc
        x, (auxs, _) = jax.lax.scan(body_fn, x, xs)
        return x, jnp.sum(auxs), None

    x, (auxs, new_cache) = jax.lax.scan(body, x, xs)
    return x, jnp.sum(auxs), new_cache


def _run_pattern(params, x, cfg, positions):
    """The layers of ``cfg.layer_pattern`` in order, the j-th layer of a
    kind being row j of its kind's stack (params.PATTERN_KINDS); each
    layer rematerialised as ``cfg.remat`` says.  Returns (x, aux)."""
    fns, seen = {}, {}
    aux = jnp.float32(0.0)
    for ch in cfg.layer_pattern:
        name, kind = PATTERN_KINDS[ch]
        j = seen[ch] = seen.get(ch, -1) + 1
        if kind not in fns:
            def layer(p, x, positions, kind=kind):
                y, a, _ = block_apply(p, x, cfg, kind, positions)
                return y, a
            fns[kind] = jax.checkpoint(layer) if cfg.remat else layer
        p = jax.tree.map(lambda a: a[j], params[name])
        x, a = fns[kind](p, x, positions)
        aux = aux + a
    return x, aux


def model_forward(params, cfg: ArchConfig, tokens, *, patch_emb=None,
                  positions=None, cache=None, decode=False):
    """Returns (hidden (B,S,d), aux_loss, new_cache_or_None).

    tokens: (B,S[,K]); decode: S == 1, positions: (1,) current position.
    patch_emb: (B,P,d) VLM patch embeddings, prepended (train/prefill only).
    """
    B = tokens.shape[0]
    S = tokens.shape[1]
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    if patch_emb is not None and not decode:
        Pn = patch_emb.shape[1]
        positions = jnp.arange(Pn + S, dtype=jnp.int32)
        x_text = embed_tokens(params, cfg, tokens, positions[Pn:])
        x = jnp.concatenate([patch_emb.astype(x_text.dtype), x_text], axis=1)
    else:
        x = embed_tokens(params, cfg, tokens, positions)

    total_aux = jnp.float32(0.0)
    new_cache = {} if cache is not None else None
    if cfg.layer_pattern:
        if cache is not None:
            raise NotImplementedError("a layer pattern runs without a cache")
        x, total_aux = _run_pattern(params, x, cfg, positions)
    else:
        for name, kind, _L in block_kinds(cfg):
            stack_cache = cache.get(name) if cache is not None else None
            x, aux, nc = _run_stack(params[name], x, cfg, kind, positions,
                                    cache=stack_cache, decode=decode)
            total_aux = total_aux + aux
            if new_cache is not None:
                new_cache[name] = nc
    if cfg.seq_parallel and not decode and x.shape[1] > 1:
        # leave the sequence-sharded domain before the (token-chunked,
        # vocab-sharded) loss — avoids GSPMD resharding thrash there
        x = constrain(x, P(BATCH, None, None))
    with jax.named_scope("lm.head"):
        x = apply_norm(x, params["final_norm"], cfg)
    return x, total_aux, new_cache


# ---------------------------------------------------------------------------
# Training loss (chunked cross-entropy over the token axis)
# ---------------------------------------------------------------------------


def _xent_chunk(params, cfg, h_chunk, labels_chunk):
    lg = logits_fn(params, cfg, h_chunk)  # (c[,K],Vp) f32
    if lg.shape[-1] != cfg.vocab:  # mask padded vocab entries
        vmask = jnp.arange(lg.shape[-1]) < cfg.vocab
        lg = jnp.where(vmask, lg, -1e30)
    logz = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels_chunk[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    valid = (labels_chunk >= 0)
    per = (logz - ll) * valid
    return jnp.sum(per), jnp.sum(valid)


def chunked_xent(params, cfg, hidden2d, labels1d, chunk=LOSS_CHUNK):
    """hidden2d: (T,d); labels1d: (T[,K]).  -1 labels are masked.
    Its ops (logits and cross-entropy) carry the device scope
    ``lm.head``, as does the final norm in ``model_forward``.

    §Perf: T is PADDED up to a chunk multiple (masked labels) rather than
    shrinking the chunk — an off-by-one T (e.g. the MTP head's S-1 tokens)
    previously degenerated to 64-token chunks and a 4095-trip loss scan.
    """
    with jax.named_scope("lm.head"):
        T = hidden2d.shape[0]
        c = min(chunk, T)
        pad = (-T) % c
        if pad:
            hidden2d = jnp.pad(
                hidden2d, ((0, pad),) + ((0, 0),) * (hidden2d.ndim - 1))
            labels1d = jnp.pad(
                labels1d, ((0, pad),) + ((0, 0),) * (labels1d.ndim - 1),
                constant_values=-1)
            T += pad
        nc = T // c
        hs = hidden2d.reshape(nc, c, -1)
        ls = labels1d.reshape(nc, c, *labels1d.shape[1:])

        def body(carry, xs):
            s, n = carry
            h, l = xs
            ds, dn = _xent_chunk(params, cfg, h, l)
            return (s + ds, n + dn), None

        body = jax.checkpoint(body)
        (s, n), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)),
                                 (hs, ls))
        return s / jnp.maximum(n, 1.0)


def train_loss(params, cfg: ArchConfig, batch):
    """batch: {"tokens": (B,S[,K]), "labels": (B,S[,K])
               [, "patch_emb": (B,P,d)]}.  Returns scalar loss."""
    tokens = batch["tokens"]
    labels = batch["labels"]
    patch_emb = batch.get("patch_emb")
    h, aux, _ = model_forward(params, cfg, tokens, patch_emb=patch_emb)
    if patch_emb is not None:
        h = h[:, patch_emb.shape[1]:]  # loss on text positions only
    B, S = labels.shape[0], labels.shape[1]
    loss = chunked_xent(params, cfg, h.reshape(B * S, -1),
                        labels.reshape(B * S, *labels.shape[2:]))
    if cfg.mtp_depth and "mtp" in params:
        loss = loss + 0.1 * _mtp_loss(params, cfg, h, tokens, labels)
    return loss + aux


def _mtp_loss(params, cfg, h, tokens, labels):
    """DeepSeek-V3 multi-token prediction (depth 1): predict t+2 from
    [norm(h_t); embed(token_{t+1})] through one extra block."""
    mtp = params["mtp"]
    emb_next = embed_tokens(params, cfg, tokens[:, 1:],
                            jnp.arange(1, tokens.shape[1], dtype=jnp.int32))
    h_in = jnp.concatenate([apply_norm(h[:, :-1], mtp["norm"], cfg),
                            emb_next], axis=-1)
    x = h_in @ mtp["mtp_proj"]
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x, _aux, _ = block_apply(mtp["block"], x, cfg, "dense", positions)
    x = apply_norm(x, params["final_norm"], cfg)
    labels2 = labels[:, 1:]
    B, S2 = labels2.shape[0], labels2.shape[1]
    return chunked_xent(params, cfg, x.reshape(B * S2, -1),
                        labels2.reshape(B * S2, *labels2.shape[2:]))


# ---------------------------------------------------------------------------
# Serving: cache init, prefill, decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int):
    """Stacked per-layer caches; ring buffer of `sliding_window` slots for
    SWA archs."""
    dtype = jnp.dtype(cfg.dtype)
    slots = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    cache = {}
    for name, kind, L in block_kinds(cfg):
        c = {}
        if kind in ("dense", "moe", "hybrid") and cfg.n_heads:
            if cfg.use_mla:
                c["attn"] = {
                    "ckv": jnp.zeros((L, batch, max_len, cfg.kv_lora_rank),
                                     dtype),
                    "krope": jnp.zeros((L, batch, max_len, cfg.qk_rope_dim),
                                       dtype),
                    "pos_map": jnp.full((L, max_len), -1, jnp.int32),
                }
            else:
                # kv dim flattened (KV*hd): divisible by the model axis
                c["attn"] = {
                    "k": jnp.zeros((L, batch, slots,
                                    cfg.n_kv_heads * cfg.head_dim), dtype),
                    "v": jnp.zeros((L, batch, slots,
                                    cfg.n_kv_heads * cfg.head_dim), dtype),
                    "pos_map": jnp.full((L, slots), -1, jnp.int32),
                }
        if kind in ("ssm", "hybrid"):
            G, N = cfg.ssm_n_groups, cfg.ssm_d_state
            hg = cfg.ssm_n_heads // G
            conv_ch = cfg.d_inner + 2 * G * N
            c["ssm"] = {
                "conv": jnp.zeros((L, batch, cfg.ssm_d_conv - 1, conv_ch),
                                  dtype),
                "state": jnp.zeros((L, batch, G, hg, cfg.ssm_head_dim, N),
                                   jnp.float32),
            }
        cache[name] = c
    return cache


def prefill(params, cfg: ArchConfig, tokens, cache, *, patch_emb=None):
    """Run the prompt, fill the cache; returns (last-position logits, cache)."""
    h, _aux, new_cache = model_forward(params, cfg, tokens,
                                       patch_emb=patch_emb, cache=cache,
                                       decode=False)
    lg = logits_fn(params, cfg, h[:, -1:])[..., : cfg.vocab]
    return lg, new_cache


def decode_step(params, cfg: ArchConfig, cache, token, pos):
    """One decode step.  token: (B,1[,K]); pos: scalar int32 absolute
    position.  Returns (logits (B,1[,K],V), new_cache)."""
    positions = jnp.reshape(pos, (1,)).astype(jnp.int32)
    h, _aux, new_cache = model_forward(params, cfg, token,
                                       positions=positions, cache=cache,
                                       decode=True)
    lg = logits_fn(params, cfg, h)[..., : cfg.vocab]
    return lg, new_cache
