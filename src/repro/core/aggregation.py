"""Aggregation schemes (paper §4.1) and the generalized FedAvg update.

Eq. (2):  w <- w + sum_k p_tau^k (w_k - w),  with round-varying p_tau^k.

Scheme A: only complete devices (s=E), p_tau^k = N p^k / K_tau (round
          dropped if K_tau = 0).
Scheme B: accept partial work, fixed p_tau^k = p^k.
Scheme C: debiased, p_tau^k = (E / s_tau^k) p^k (0 when inactive) — the
          paper's contribution; the only scheme converging to the global
          optimum under heterogeneous participation (Thm 3.1 / Table 1).

Coefficients are plain device arrays, so one compiled round step serves
every scheme and every participation pattern.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def scheme_coefficients(scheme: str, p: jnp.ndarray, s: jnp.ndarray,
                        E: int) -> jnp.ndarray:
    """p: (C,) static data weights p^k; s: (C,) completed epochs.
    Returns p_tau: (C,) aggregation coefficients."""
    p = jnp.asarray(p, jnp.float32)
    s = jnp.asarray(s, jnp.float32)
    if scheme == "A":
        complete = (s >= E).astype(jnp.float32)
        K = jnp.sum(complete)
        # N is the number of devices in the objective (p > 0), not the
        # buffer length: capacity-slotted engines carry empty columns
        # with p = 0 that must not inflate the coefficients
        N = jnp.sum((p > 0).astype(jnp.float32))
        return jnp.where(K > 0, N * p * complete / jnp.maximum(K, 1.0), 0.0)
    if scheme == "B":
        return p * (s > 0)
    if scheme == "C":
        return jnp.where(s > 0, E * p / jnp.maximum(s, 1.0), 0.0)
    raise ValueError(f"unknown scheme {scheme}")


def theta_bound(scheme: str, n_clients: int, E: int) -> float:
    """Assumption 3.5 upper bound p_tau^k / p^k <= theta."""
    return {"A": float(n_clients), "B": 1.0, "C": float(E)}[scheme]


def aggregate_deltas(params, deltas, coeffs):
    """w + sum_k c_k delta_k over a stacked client axis.

    deltas: pytree with leading client dim (C, ...); coeffs: (C,).
    This is the jnp reference path; aggregate_deltas_flat is the Pallas
    path (one weighted_agg launch per leaf) used by the device-resident
    round engine.
    """
    def upd(p, d):
        c = coeffs.astype(jnp.float32).reshape((-1,) + (1,) * (d.ndim - 1))
        return (p.astype(jnp.float32)
                + jnp.sum(c * d.astype(jnp.float32), axis=0)).astype(p.dtype)

    with jax.named_scope("fed.aggregate"):
        return jax.tree.map(upd, params, deltas)


def _flatten_leaf(leaf):
    """(C, ...) -> (C, size) f32.  A leaf whose per-client size fills
    whole 128-lane rows goes through (C, size/128, 128) behind an
    optimization barrier: XLA's TPU compiler spends minutes on some
    one-step relayouts, e.g. ~90 s for (100, 784, 200) -> (100, 156800)
    against ~2 s for the two steps."""
    C, size = leaf.shape[0], leaf[0].size
    leaf = leaf.astype(jnp.float32)
    if leaf.ndim > 2 and size % 128 == 0:
        leaf = jax.lax.optimization_barrier(leaf.reshape(C, size // 128, 128))
    return leaf.reshape(C, size)


def flatten_client_deltas(deltas):
    """Stacked-client pytree (leaves (C, ...)) -> one (C, D_total) f32
    buffer, leaves concatenated in jax.tree.leaves order."""
    with jax.named_scope("fed.flatten"):
        return jnp.concatenate(
            [_flatten_leaf(l) for l in jax.tree.leaves(deltas)], axis=1)


def _apply_flat(params, agg):
    """w + agg over the (D_total,) aggregate, unflattened leaf by leaf in
    jax.tree.leaves order."""
    p_leaves, treedef = jax.tree.flatten(params)
    outs, off = [], 0
    for p in p_leaves:
        seg = agg[off:off + p.size].reshape(p.shape)
        outs.append((p.astype(jnp.float32) + seg).astype(p.dtype))
        off += p.size
    return jax.tree.unflatten(treedef, outs)


def agg_launches(params, compression=None) -> int:
    """weighted_agg launches aggregate_deltas_flat makes a round: one per
    parameter leaf on the f32 and bf16 wires, one over the flattened
    codes on the int8 wires."""
    from repro.core.compression import resolve_compression
    if resolve_compression(compression).quantized:
        return 1
    return len(jax.tree.leaves(params))


def aggregate_deltas_flat(params, deltas, coeffs, *, sharding=None,
                          compression=None):
    """Same contract as aggregate_deltas, reduced by the weighted_agg
    Pallas kernel instead of one scaled-add tree per leaf.

    On the f32 and bf16 wires each stacked delta leaf (C, ...) is reduced
    where it lies, one launch per leaf (``agg_launches``): no (C, D_total)
    buffer and no padded copy exists, and w + agg is a plain add per
    leaf.  bf16 is a cast of each leaf first (the kernel reduces any
    float dtype in f32).

    The int8 kinds flatten the leaves into one (C, D_total) buffer,
    quantize it on-device on the flattened-leaf chunk grid that the
    sequential mode and aggregate_deltas_compressed_ref share, and reduce
    the codes with ONE launch that fuses dequantization.

    sharding: an optional fed.sharding.FedSharding whose mesh shards the
    client axis — each device then reduces its own clients' leaves (or
    codes and scales: only compressed bytes shard over the federation
    axes) locally and one psum epilogue replicates the result (the
    cross-device path of the sharded round engine)."""
    from repro.core.compression import compress_flat, resolve_compression
    # kernels never import core: no cycle
    from repro.kernels.weighted_agg import (weighted_agg,
                                            weighted_agg_sharded)

    spec = resolve_compression(compression)
    if spec.quantized:
        flat = flatten_client_deltas(deltas)
        with jax.named_scope("fed.flatten"):
            codes, scales = compress_flat(flat, spec)
        with jax.named_scope("fed.aggregate"):
            if sharding is None:
                agg = weighted_agg(coeffs, codes, scales, chunk=spec.chunk)
            else:
                # each shard owns its own clients' compressed bytes
                agg = weighted_agg_sharded(
                    coeffs, sharding.constrain_client(codes),
                    sharding.constrain_client(scales), chunk=spec.chunk,
                    mesh=sharding.mesh, axis=sharding.axis)
            return _apply_flat(params, agg[:flat.shape[1]])
    leaves, treedef = jax.tree.flatten(deltas)
    if spec.kind == "bf16":
        with jax.named_scope("fed.flatten"):
            leaves = [x.astype(jnp.bfloat16) for x in leaves]
    with jax.named_scope("fed.aggregate"):
        if sharding is None:
            aggs = [weighted_agg(coeffs, x) for x in leaves]
        else:
            aggs = weighted_agg_sharded(
                coeffs, [sharding.constrain_client(x) for x in leaves],
                mesh=sharding.mesh, axis=sharding.axis)
        return jax.tree.map(
            lambda p, a: (p.astype(jnp.float32) + a).astype(p.dtype),
            params, jax.tree.unflatten(treedef, aggs))


def aggregate_deltas_compressed_ref(params, deltas, coeffs, compression):
    """Pure-jnp reference for the compressed flat reduction: quantize ->
    dequantize -> einsum on the same flat layout and chunk grid as the
    fused kernel.  This is the off-TPU path (interpret-mode Pallas is an
    emulator, far slower than XLA's einsum on CPU) — same lattice, only
    the f32 reduction order differs."""
    from repro.core.compression import resolve_compression, round_trip

    spec = resolve_compression(compression)
    flat = flatten_client_deltas(deltas)
    with jax.named_scope("fed.flatten"):
        flat = round_trip(flat, spec)
    with jax.named_scope("fed.aggregate"):
        agg = jnp.einsum("k,kd->d", coeffs.astype(jnp.float32), flat)
        return _apply_flat(params, agg)


def accumulate_delta(acc, delta, coeff):
    """Streaming form for the client-sequential mode: acc += c * delta.
    coeff may be a plain python float or a jax scalar."""
    with jax.named_scope("fed.aggregate"):
        c = jnp.asarray(coeff, jnp.float32)
        return jax.tree.map(
            lambda a, d: a + c * d.astype(jnp.float32), acc, delta)


def apply_accumulator(params, acc):
    with jax.named_scope("fed.aggregate"):
        return jax.tree.map(
            lambda p, a: (p.astype(jnp.float32) + a).astype(p.dtype),
            params, acc)


def expected_coeff_stats(scheme: str, p: np.ndarray, trace_samples,
                         E: int, n_rounds: int = 2000, seed: int = 0):
    """Monte-Carlo estimates of E[p_tau^k s_tau^k] etc. used by the theory
    module (learning-rate scale, z_tau detection).  trace_samples(rng) must
    return s: (C,) for one round."""
    rng = np.random.default_rng(seed)
    C = len(p)
    ps_sum = np.zeros(C)
    for _ in range(n_rounds):
        s = trace_samples(rng)
        c = np.asarray(scheme_coefficients(scheme, jnp.asarray(p),
                                           jnp.asarray(s), E))
        ps_sum += c * s
    Eps = ps_sum / n_rounds
    ratio = Eps / np.maximum(p, 1e-12)
    z = float(np.std(ratio) > 1e-6 * max(1.0, np.mean(np.abs(ratio))))
    return {"E_ps": Eps, "ratio": ratio, "z": z,
            "E_sum_ps": float(np.sum(Eps))}
