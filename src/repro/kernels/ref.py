"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def weighted_agg_ref(coeffs, deltas, scales=None, *, chunk=None):
    """(K,), (K, *shape) -> shape f32.  With scales (K, D/chunk) f32,
    deltas are (K, D) int8 codes: dequantize, then reduce."""
    deltas = deltas.astype(jnp.float32)
    if scales is not None:
        K, D = deltas.shape
        deltas = (deltas.reshape(K, D // chunk, chunk)
                  * scales[..., None]).reshape(K, D)
    return jnp.einsum("k,k...->...", coeffs.astype(jnp.float32), deltas)


def ssd_intra_chunk_ref(cum, C, B, xdt):
    """(G,Q), (G,Q,N), (G,Q,N), (G,Q,P) -> (G,Q,P) f32."""
    cum = cum.astype(jnp.float32)
    Q = cum.shape[-1]
    diff = cum[:, :, None] - cum[:, None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(mask, jnp.exp(diff), 0.0)
    s = jnp.einsum("gqn,gsn->gqs", C.astype(jnp.float32),
                   B.astype(jnp.float32)) * L
    return jnp.einsum("gqs,gsp->gqp", s, xdt.astype(jnp.float32))


def flash_attention_ref(q, k, v, *, causal: bool = True):
    """q,k,v: (B,H,S,hd) -> (B,H,S,hd); plain softmax attention."""
    S = q.shape[2]
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)
