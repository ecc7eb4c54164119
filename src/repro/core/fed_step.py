"""The jit'd federated round (Eq. 1–2) in the equivalent view (App. A.1.1).

Two execution layouts with identical arithmetic:

  client_parallel   — vmap over the client axis (sharded over the mesh
                      'data'/'pod' axes).  Paper-faithful breadth; per-client
                      parameter copies are live simultaneously.
  client_sequential — lax.scan over clients; each client's *batch* is
                      data-parallel over the mesh and params are fully
                      sharded (FSDP x TP).  Used for >=30B architectures.

Local updates are vanilla SGD (the paper's optimizer) with the staircase
learning rate supplied per round; each of the E steps is masked by
alpha[c, e] in {0,1}, so s_tau^k = sum_e alpha[c, e].
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.aggregation import (accumulate_delta, aggregate_deltas,
                                    aggregate_deltas_compressed_ref,
                                    aggregate_deltas_flat, apply_accumulator,
                                    scheme_coefficients)
from repro.core.compression import resolve_compression, round_trip_tree


def local_sgd(loss_fn: Callable, params, client_batches, alpha_e, eta):
    """E masked SGD steps on one client.

    client_batches: pytree with leading (E, ...) dim (one batch per local
    epoch); alpha_e: (E,) masks; returns the client delta w_E - w_0.
    """

    def step(w, xs):
        batch, a = xs
        _, g = jax.value_and_grad(loss_fn)(w, batch)
        w = jax.tree.map(
            lambda p, gg: (p.astype(jnp.float32)
                           - eta * a * gg.astype(jnp.float32)).astype(p.dtype),
            w, g)
        return w, None

    with jax.named_scope("fed.local_sgd"):
        w_end, _ = jax.lax.scan(step, params, (client_batches, alpha_e))
        return jax.tree.map(
            lambda e, s: e.astype(jnp.float32) - s.astype(jnp.float32),
            w_end, params)


def _constrain_client_deltas(sharding, deltas, param_specs):
    """Constrain stacked client deltas (leaves (C, ...)): the client dim
    over the federation axes, the trailing dims per the model's param
    spec (or unsharded for replicated small-model params)."""
    if param_specs is None:
        return sharding.constrain_client_tree(deltas)
    entry = sharding._entry()
    return jax.tree.map(
        lambda d, s: jax.lax.with_sharding_constraint(
            d, sharding.param_sharding(
                jax.sharding.PartitionSpec(entry, *s))),
        deltas, param_specs)


def _constrain_batch(sharding, batches, axis_dim: int):
    """Shard the batch dim of a batch pytree over the federation axes
    (the client-sequential data-parallel layout).

    Ragged batch dims used to fall back silently to GSPMD's choice (in
    practice: replication of the whole batch, wasting every federation
    device but one).  Policy decided here: **pad to divisible** — the
    batch dim is extended to the next multiple of the shard count by
    wrapping around to the leading samples, then sharded.  The gradient
    becomes a weighted batch mean in which the first ``pad`` samples
    count twice — statistically benign for SGD, bit-identical whenever
    the batch already divides (pad == 0, the config every production run
    should use), and logged once per shape at trace time so a ragged
    deployment shows up in the logs rather than in the profile."""
    n = sharding.n_shards

    def con(l):
        if l.ndim <= axis_dim:
            return l
        b = l.shape[axis_dim]
        pad = -b % n
        if pad:
            _log_batch_padding(b, n, pad)
            wrap = jnp.arange(b + pad) % b
            l = jnp.take(l, wrap, axis=axis_dim)
        return sharding.constrain_client(l, axis_dim)

    return jax.tree.map(con, batches)


@functools.lru_cache(maxsize=None)
def _log_batch_padding(b: int, n_shards: int, pad: int) -> None:
    """Once per (batch, shards) shape — tracing re-runs this, real
    dispatch never does."""
    import logging
    logging.getLogger(__name__).warning(
        "fed_round_sequential: batch dim %d is ragged over %d federation "
        "shards; padding to %d by wrapping %d leading samples "
        "(padding fraction %.3f — the first %d samples weigh double in "
        "the batch mean)", b, n_shards, b + pad, pad, pad / (b + pad), pad)


def fed_round_parallel(loss_fn, params, batches, alpha, coeffs, eta, *,
                       agg: str = "tree", with_metrics: bool = True,
                       sharding=None, param_specs=None, compression=None):
    """batches: pytree (C, E, ...); alpha: (C, E); coeffs: (C,).
    Returns (new_params, metrics).

    agg selects the aggregation layout: "tree" is the per-leaf jnp
    reference; "flat" reduces each delta leaf where it lies with the
    weighted_agg Pallas kernel (aggregate_deltas_flat).
    with_metrics=False skips the delta-norm reduction (hot-loop mode).

    sharding: optional fed.sharding.FedSharding — the client axis of
    batches/alpha/deltas is constrained to the mesh's federation axis
    (or composite axes, e.g. ('pod', 'data')) so local epochs run
    device-parallel, and the delta reduction psums over exactly the
    federation axes.  param_specs (a PartitionSpec pytree matching
    params, see models.sharding.tree_param_specs) keeps params sharded
    over the mesh's model/FSDP axes through the round — without it the
    aggregated params come back replicated (small-model path).

    compression: optional CompressionSpec/str — client deltas are
    quantized right after the masked-SGD epochs, before aggregation.
    On the flat layout the fused dequant-and-reduce kernel consumes the
    compressed payload directly; on the tree layout the pure-jnp
    reference round-trips the same quantization lattice.  Both paths use
    the flattened-leaf chunk grid, so layouts (and the sequential mode)
    stay parity-comparable."""
    spec = resolve_compression(compression)
    deltas = jax.vmap(lambda b, a: local_sgd(loss_fn, params, b, a, eta))(
        batches, alpha)
    if sharding is not None:
        deltas = _constrain_client_deltas(sharding, deltas, param_specs)
    if agg == "flat":
        new_params = aggregate_deltas_flat(params, deltas, coeffs,
                                           sharding=sharding,
                                           compression=spec)
    elif spec.active:
        new_params = aggregate_deltas_compressed_ref(params, deltas,
                                                     coeffs, spec)
    else:
        new_params = aggregate_deltas(params, deltas, coeffs)
    if sharding is not None:
        new_params = sharding.constrain_params(new_params, param_specs)
    if not with_metrics:
        return new_params, {"delta_norm": jnp.float32(0)}
    dn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                      for x in jax.tree.leaves(deltas)))
    return new_params, {"delta_norm": dn}


def fed_round_sequential(loss_fn, params, batches, alpha, coeffs, eta, *,
                         with_metrics: bool = True, sharding=None,
                         param_specs=None, compression=None):
    """Same contract as fed_round_parallel; clients scanned to bound
    memory: only the global params, the streaming aggregation accumulator
    and ONE live client delta exist at a time — never a (C, D_total) or
    per-client parameter stack.  This is the >=30B path.

    Under ``sharding`` each client's *batch* dim is data-parallel over
    the federation axes (GSPMD psums the gradient over exactly those
    axes) while params and the accumulator stay sharded per
    ``param_specs`` (FSDP x TP over the mesh's model axes) — the
    federated round never materializes a replicated copy of the model.

    compression round-trips each client's delta through the wire format
    (core.compression.round_trip_tree) before it enters the accumulator
    — the flattened-leaf chunk grid matches the parallel layout, so the
    two modes quantize identically and differ only in f32 reduction
    order."""
    spec = resolve_compression(compression)
    if sharding is not None:
        params = sharding.constrain_params(params, param_specs)

    def con_acc(acc):
        if sharding is not None:
            return sharding.constrain_params(acc, param_specs)
        return acc

    acc0 = con_acc(jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params))

    def one_client(carry, xs):
        acc, dn2 = carry
        b_c, a_c, c_c = xs
        if sharding is not None:
            # (E, B, ...): batch dim 1 shards over the federation axes
            b_c = _constrain_batch(sharding, b_c, axis_dim=1)
        delta = local_sgd(loss_fn, params, b_c, a_c, eta)
        if spec.active:
            with jax.named_scope("fed.flatten"):
                delta = round_trip_tree(delta, spec)
        acc = con_acc(accumulate_delta(acc, delta, c_c))
        if with_metrics:
            dn2 = dn2 + sum(jnp.sum(jnp.square(x))
                            for x in jax.tree.leaves(delta))
        return (acc, dn2), None

    # the client loop is local SGD; the accumulate inside it names itself
    # fed.aggregate, which a profile reads as the innermost scope
    with jax.named_scope("fed.local_sgd"):
        (acc, dn2), _ = jax.lax.scan(one_client, (acc0, jnp.float32(0)),
                                     (batches, alpha, coeffs))
    new_params = apply_accumulator(params, acc)
    if sharding is not None:
        new_params = sharding.constrain_params(new_params, param_specs)
    dn = jnp.sqrt(dn2) if with_metrics else jnp.float32(0)
    return new_params, {"delta_norm": dn}


def make_fed_round(loss_fn, mode: str = "client_parallel",
                   agg: str = "tree", compression=None):
    """Returns fed_round(params, batches, alpha, coeffs, eta)."""
    if mode == "client_parallel":
        return functools.partial(fed_round_parallel, loss_fn, agg=agg,
                                 compression=compression)
    if mode != "client_sequential":
        raise ValueError(f"mode must be client_parallel|client_sequential, "
                         f"got {mode!r}")
    return functools.partial(fed_round_sequential, loss_fn,
                             compression=compression)


def fed_train_step(loss_fn, cfg, params, batches, alpha, p_weights, eta,
                   scheme: str = None, mode: str = None):
    """Convenience one-call round: compute scheme coefficients from the
    realized s = alpha.sum(-1), then run the round."""
    scheme = scheme or cfg.fed.scheme
    mode = mode or cfg.fed.mode
    s = jnp.sum(alpha, axis=-1)
    coeffs = scheme_coefficients(scheme, p_weights, s, cfg.fed.local_epochs)
    return make_fed_round(loss_fn, mode)(params, batches, alpha, coeffs, eta)
