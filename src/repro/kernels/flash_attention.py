"""Pallas TPU kernel: causal flash attention (forward).

Online-softmax blockwise attention: grid (B*H, nq, nk) with the kv axis
innermost; running max/denominator/accumulator live in VMEM scratch across
kv steps.  Block sizes are MXU-aligned (128).  This is the serving-path
hot spot (32k prefill); the pure-jnp chunked path in models/attention.py
is the baseline it replaces on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.weighted_agg import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  blk_q: int, blk_k: int, n_k: int, seq_len: int,
                  causal: bool, scale: float):
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # (blk_q, hd)
    k = k_ref[0]                                   # (blk_k, hd)
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    qpos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32,
                                                 (blk_q, blk_k), 0)
    kpos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32,
                                                 (blk_q, blk_k), 1)
    valid = kpos < seq_len
    if causal:
        valid &= kpos <= qpos
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                            # (blk_q, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _fin():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "blk_q", "blk_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, blk_q: int = 128,
                    blk_k: int = 128, interpret: bool | None = None):
    """q: (B,H,S,hd); k,v: (B,KV,S,hd) — kv heads repeated to H if GQA."""
    interpret = resolve_interpret(interpret)
    B, H, S, hd = q.shape
    if k.shape[1] != H:
        rep = H // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    blk_q = min(blk_q, S)
    blk_k = min(blk_k, S)
    pad = (-S) % max(blk_q, blk_k)
    if pad:
        padw = ((0, 0), (0, 0), (0, pad), (0, 0))
        q, k, v = (jnp.pad(x, padw) for x in (q, k, v))
    Sp = S + pad
    n_q, n_k = Sp // blk_q, Sp // blk_k
    scale = 1.0 / float(hd) ** 0.5
    qf = q.reshape(B * H, Sp, hd)
    kf = k.reshape(B * H, Sp, hd)
    vf = v.reshape(B * H, Sp, hd)
    kern = functools.partial(_flash_kernel, blk_q=blk_q, blk_k=blk_k,
                             n_k=n_k, seq_len=S, causal=causal, scale=scale)
    out = pl.pallas_call(
        kern,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :S].reshape(B, H, S, hd)
