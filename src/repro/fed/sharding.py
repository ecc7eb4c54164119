"""Sharding specs for the federation (client) axis.

The paper's aggregation schemes only become interesting at scale — the
non-IID effects of inactivity and incomplete updates assume federations of
hundreds to thousands of devices — so the engine's capacity-slotted client
buffers (``data buffers (C, Nmax, …)``, ``n``, ``s_cdf``) carry a
federation-sharded leading axis: each mesh device owns ``C / n_shards``
client slots, per-client local epochs run fully in parallel across
devices, and the per-round delta reduction ends in a cross-device
all-reduce over the federation axes.

This module is the single place the slot-buffer layout is decided:

  * :class:`FedSharding` — an immutable spec (mesh + federation axis
    name(s)) with helpers to place (``put_client`` / ``put_replicated``)
    and constrain (``constrain_client`` / ``constrain_replicated``)
    arrays;
  * :func:`make_fed_sharding` — build a spec over a 1-D ``'data'`` mesh of
    local devices (``launch/mesh.make_data_mesh``), or over any existing
    mesh that has the federation axes (e.g. the production
    ``launch/mesh.make_production_mesh``).

Composite federation axes: ``axis`` may be a single mesh-axis name
(``'data'``) or a tuple (``('pod', 'data')``) for multi-pod federations —
the client axis then shards over the *product* of those axes
(``P(('pod', 'data'))``) and every cross-device reduction psums over
exactly that set.  Axes of the mesh **not** named (e.g. ``'model'``) are
left alone: params may stay sharded over them per the model's partition
specs (FSDP x TP, ``models/sharding.py``), which is how one mesh carries
both the federation and the large-model layout — see docs/scaling.md.

Slot ownership invariant: capacity is always padded to a multiple of the
shard count (``pad_capacity``), so every shard owns the same number of
whole slots and a slot mutation (``RoundEngine.admit/evict/set_trace``)
stays one replicated-row ``device_put`` plus a dynamic-update-slice that
XLA lowers to a masked, shard-local write — membership churn never moves
data between shards and never recompiles the span scans.

Usage::

    from repro.fed.sharding import make_fed_sharding
    fs = make_fed_sharding()            # 1-D 'data' mesh over all devices
    eng = RoundEngine(..., sharding=fs) # client axis sharded over the mesh

    # multi-pod federation: clients shard over pod x data
    fs = make_fed_sharding(mesh=pod_mesh, axis=("pod", "data"))
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class FedSharding:
    """Where the federation's client axis lives on the mesh.

    mesh: any jax Mesh with the axis (or axes) named by ``axis`` (default
    ``'data'``; a tuple such as ``('pod', 'data')`` declares a composite
    federation axis).  The client/slot axis of every engine buffer is
    sharded over the named axes; scalars and small-model params are
    replicated, while large-model params may stay sharded over the mesh's
    remaining (e.g. ``'model'``) axes via per-leaf PartitionSpecs.
    """
    mesh: Mesh
    axis: Union[str, Tuple[str, ...]] = "data"

    def __post_init__(self):
        for a in self.axes:
            if a not in self.mesh.axis_names:
                raise ValueError(
                    f"mesh has no {a!r} axis (axes: "
                    f"{self.mesh.axis_names}); every federation axis must "
                    f"name an existing mesh axis")

    # -- geometry -------------------------------------------------------------
    @property
    def axes(self) -> Tuple[str, ...]:
        """The federation axis names as a tuple (composite-safe form)."""
        return (self.axis,) if isinstance(self.axis, str) else \
            tuple(self.axis)

    @property
    def n_shards(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.axes]))

    def pad_capacity(self, capacity: int) -> int:
        """Round capacity up so every shard owns the same number of whole
        slots (padded slots behave exactly like empty capacity slots)."""
        n = self.n_shards
        return -(-capacity // n) * n

    # -- specs ----------------------------------------------------------------
    def _entry(self):
        """The PartitionSpec entry for the client dim: the bare name for a
        single axis, the tuple for a composite one."""
        return self.axis if isinstance(self.axis, str) else tuple(self.axis)

    def client_spec(self, ndim: int, axis_dim: int = 0) -> P:
        """PartitionSpec sharding dimension ``axis_dim`` over the
        federation axis/axes (the leading slot axis of engine buffers;
        plan arrays carry the client axis at dim 1)."""
        spec = [None] * ndim
        spec[axis_dim] = self._entry()
        return P(*spec)

    def client(self, ndim: int, axis_dim: int = 0) -> NamedSharding:
        return NamedSharding(self.mesh, self.client_spec(ndim, axis_dim))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def param_sharding(self, spec: Optional[P]) -> NamedSharding:
        """NamedSharding for a parameter leaf: ``spec=None`` replicates
        (the small-model path); a PartitionSpec from the model's rule
        table (``models.sharding.tree_param_specs``) keeps the leaf
        sharded over the mesh's model/FSDP axes.  Axis names absent from
        the mesh are dropped, so one spec serves every mesh shape."""
        if spec is None:
            return self.replicated()
        from repro.models.sharding import _filter_spec
        return NamedSharding(self.mesh, _filter_spec(spec, self.mesh))

    # -- placement (host -> device, commits the layout) -----------------------
    def put_client(self, x, axis_dim: int = 0):
        return jax.device_put(x, self.client(np.ndim(x), axis_dim))

    def put_replicated(self, tree):
        repl = self.replicated()
        return jax.tree.map(lambda l: jax.device_put(l, repl), tree)

    def put_params(self, tree, specs=None):
        """Place a parameter pytree: replicated when ``specs`` is None,
        else per-leaf model-spec shardings (the large-model path)."""
        if specs is None:
            return self.put_replicated(tree)
        return jax.tree.map(
            lambda l, s: jax.device_put(l, self.param_sharding(s)),
            tree, specs)

    # -- constraints (inside jit, steer GSPMD) --------------------------------
    def constrain_client(self, x, axis_dim: int = 0):
        return jax.lax.with_sharding_constraint(
            x, self.client(x.ndim, axis_dim))

    def constrain_client_tree(self, tree, axis_dim: int = 0):
        return jax.tree.map(
            lambda l: self.constrain_client(l, axis_dim), tree)

    def constrain_replicated(self, tree):
        repl = self.replicated()
        return jax.tree.map(
            lambda l: jax.lax.with_sharding_constraint(l, repl), tree)

    def constrain_params(self, tree, specs=None):
        """Constrain a parameter pytree to its model specs (or replicated
        when ``specs`` is None) — the in-jit counterpart of put_params."""
        if specs is None:
            return self.constrain_replicated(tree)
        return jax.tree.map(
            lambda l, s: jax.lax.with_sharding_constraint(
                l, self.param_sharding(s)), tree, specs)


def make_fed_sharding(n_devices: Optional[int] = None, *,
                      mesh: Optional[Mesh] = None,
                      axis: Union[str, Tuple[str, ...]] = "data"
                      ) -> FedSharding:
    """FedSharding over a fresh 1-D ``'data'`` mesh of local devices
    (n_devices=None uses all of them), or over an existing ``mesh`` that
    already has the federation axis/axes (pass ``axis=('pod', 'data')``
    for a composite multi-pod federation)."""
    if mesh is None:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(n_devices)
    return FedSharding(mesh=mesh, axis=axis)
