"""Shared model building blocks: norms, activations, initializers."""
from __future__ import annotations

import jax
import jax.numpy as jnp


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm(x, scale, eps=1e-5):
    """RMSNorm with a hand-written VJP (§Perf): the automatic backward
    materialises several full-residual float32 intermediates per layer
    (the dominant HBM-traffic term of the train shapes); this VJP keeps
    the saved residuals and the returned cotangent in the model dtype,
    doing float32 math only inside the fused reductions."""
    return _rmsnorm_fwd(x, scale, eps)[0]


def _rmsnorm_fwd(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x32 * rstd * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)
    return y, (x, scale, rstd)


def _rmsnorm_bwd(eps, res, dy):
    x, scale, rstd = res
    x32 = x.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    xhat = x32 * rstd
    g = dy32 * (1.0 + scale.astype(jnp.float32))
    dscale = jnp.sum(dy32 * xhat,
                     axis=tuple(range(dy.ndim - scale.ndim))
                     ).astype(scale.dtype)
    dx = rstd * (g - xhat * jnp.mean(g * xhat, axis=-1, keepdims=True))
    return dx.astype(x.dtype), dscale


rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def group_rmsnorm(x, scale, groups: int, eps=1e-5):
    """RMSNorm over each of ``groups`` equal slices of the last axis, each
    with its slice of ``scale`` (Nemotron-H's gated norm: 8 groups of
    512); one group is ``rmsnorm`` itself."""
    if groups == 1:
        return rmsnorm(x, scale, eps)
    d = x.shape[-1]
    y = rmsnorm(x.reshape(*x.shape[:-1], groups, d // groups),
                scale.reshape(groups, d // groups), eps)
    return y.reshape(x.shape)


def layernorm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = y * (1.0 + scale.astype(jnp.float32))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def apply_norm(x, p, cfg):
    """p is {"scale": ...} or {"scale": ..., "bias": ...}."""
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["scale"], cfg.norm_eps)
    return layernorm(x, p["scale"], p.get("bias"), cfg.norm_eps)


def activation_fn(name):
    if name == "silu":
        return jax.nn.silu
    if name in ("gelu", "geglu"):
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "sq_relu":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name}")


def mlp_apply(p, x, cfg):
    """Dense FFN: gated (SwiGLU/GeGLU) or plain 2-matmul."""
    act = activation_fn(cfg.activation)
    if cfg.gated_mlp:
        g = x @ p["w_gate"]
        u = x @ p["w_up"]
        h = act(g) * u
    else:
        h = x @ p["w_up"]
        if "b_up" in p:
            h = h + p["b_up"]
        h = act(h)
    y = h @ p["w_down"]
    if "b_down" in p:
        y = y + p["b_down"]
    return y


def safe_concat(parts, axis: int):
    """Concatenate via dynamic-update-slices instead of a concatenate op.

    The GSPMD partitioner on this jax/XLA version miscompiles
    ``concatenate`` when the operands carry different shardings and the
    concatenated dim's shard boundary does not align with the piece
    boundaries (observed: a 'model'-sharded (…, 512) next to replicated
    (…, 16) pieces returns wrong *values*, max abs err ~4.5 on unit-scale
    inputs).  Writing each piece into a zeros buffer with
    dynamic_update_slice partitions correctly, and XLA fuses it back into
    a copy — same cost, correct data movement."""
    axis = axis % parts[0].ndim
    total = sum(p.shape[axis] for p in parts)
    shape = list(parts[0].shape)
    shape[axis] = total
    out = jnp.zeros(shape, parts[0].dtype)
    off = 0
    for p in parts:
        out = jax.lax.dynamic_update_slice_in_dim(
            out, p.astype(out.dtype), off, axis)
        off += p.shape[axis]
    return out


# ---------------------------------------------------------------------------
# Initialization helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, scale=0.02, fan_in_axis=None):
    if fan_in_axis is not None:
        fan_in = shape[fan_in_axis]
        scale = 1.0 / jnp.sqrt(fan_in)
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def norm_init(shape_d, dtype, with_bias):
    p = {"scale": jnp.zeros((shape_d,), dtype)}
    if with_bias:
        p["bias"] = jnp.zeros((shape_d,), dtype)
    return p
