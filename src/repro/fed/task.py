"""ClientTask: the model/step layer behind the federation engine.

The paper's flexible-participation schemes (incomplete updates, arrivals,
departures) are model-agnostic, but the engine used to hard-wire the
logreg workload: ``(C, Nmax, d)`` feature / ``(C, Nmax)`` label buffers
and an ``{"x", "y"}`` batch dict.  This module factors everything
model-specific behind one small protocol, so the *same* RoundEngine /
StreamScheduler / FederatedTrainer machinery federates anything from the
paper's logistic regression to the >=30B architectures in ``models/``:

  * which per-sample arrays a client contributes (``buffers``),
  * how a gathered batch is presented to the loss (``make_batch``),
  * the loss itself (``loss_fn``),
  * parameter init (``init_params``) and — for sharded large models —
    per-leaf PartitionSpecs (``param_specs``: ``None`` replicates, the
    small-model path; a spec tree keeps params sharded FSDP x TP over the
    mesh's model axes while the federation axes carry clients/batches).

Two implementations ship:

  * :class:`ArrayTask` — feature/label pairs for the paper models
    (``models/small.py``); the engine builds one automatically from a
    bare ``loss_fn=`` for backward compatibility.
  * :class:`LMTask` — next-token prediction for any assigned
    ``ArchConfig`` (``models/transformer.py``): clients hold token
    streams ``(n, S+1)``, batches slice tokens/labels on the fly, and
    params carry ``models.sharding.tree_param_specs`` so a federated
    round composes with the model-parallel mesh axes.

Usage::

    task = LMTask(get_config("mamba2-130m").reduced(), seq_len=64)
    clients = [Client(x=task.token_stream(rng, n=40, domain=d),
                      trace=TRACES[d]) for d in range(4)]
    eng = RoundEngine(task=task, clients=clients, local_epochs=2,
                      batch_size=2, mode="client_sequential")
"""
from __future__ import annotations

import copy
import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

__all__ = ["BufferSpec", "ClientTask", "ArrayTask", "LMTask"]


@dataclass(frozen=True)
class BufferSpec:
    """One per-sample device-resident buffer: the engine stores it as a
    ``(capacity, Nmax) + row_shape`` stack of the given dtype."""
    shape: Tuple[int, ...]
    dtype: Any = np.float32

    @property
    def row_shape(self) -> Tuple[int, ...]:
        """A sample's shape in the engine's device stack.  A sample of
        rank >= 2 (an image, an audio token block) is one row of
        ``prod(shape)`` elements, so the round's batch gather moves whole
        lane-dense rows and the loss takes the shape back per step;
        rank <= 1 samples are rows already."""
        if len(self.shape) < 2:
            return self.shape
        return (math.prod(self.shape),)


class ClientTask:
    """Protocol (duck-typed base) between the federation engine and a
    model family.  Subclasses define:

    buffers          — dict name -> BufferSpec of per-sample arrays.
    loss_fn(p, b)    — scalar training loss on one batch.
    client_arrays(c) — dict name -> (n, *spec.shape) arrays for a Client.
    make_batch(g)    — map one local step's gathered buffers (each
                       (..., B) + spec.shape) to the loss_fn batch pytree;
                       the engine calls it inside the loss, per step.
    init_params(key) — fresh parameter pytree.
    param_specs(p)   — pytree of PartitionSpec matching params, or None
                       to replicate (small models).  Specs may name mesh
                       axes that don't exist on a given mesh; they are
                       filtered per-mesh at placement time.
    tokens_per_sample — tokens one sample trains (0 for non-LM tasks).
    ssd_fused_layers — SSD layers that run as the fused Pallas scan (0 for
                       tasks without SSD layers).
    moe_experts_held — routed experts each expert layer holds (0 for tasks
                       without them).
    moe_slots_per_token — expected (token, expert) slots a token sends to
                       an expert layer's held experts: top_k x held /
                       experts (0 without routed experts).
    placed(n)        — the task as a round placed on n devices runs it
                       (itself, unless the placement picks its kernels).
    """

    buffers: Dict[str, BufferSpec] = {}
    tokens_per_sample: int = 0
    ssd_fused_layers: int = 0
    moe_experts_held: int = 0
    moe_slots_per_token: float = 0.0

    def placed(self, devices: int) -> "ClientTask":
        return self

    def loss_fn(self, params, batch):
        raise NotImplementedError

    def client_arrays(self, client) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    def make_batch(self, gathered: Dict[str, Any]):
        return gathered

    def init_params(self, key):
        raise NotImplementedError

    def param_specs(self, params):
        return None


class ArrayTask(ClientTask):
    """Feature/label clients for the paper's small models — the layout the
    engine used before the ClientTask refactor, now one implementation of
    it.  ``loss_fn(params, {"x": ..., "y": ...})``; params replicated."""

    def __init__(self, loss_fn, feature_shape: Tuple[int, ...], *,
                 init_fn=None, label_dtype=np.int32):
        self._loss_fn = loss_fn
        self._init_fn = init_fn
        self.buffers = {"x": BufferSpec(tuple(feature_shape), np.float32),
                        "y": BufferSpec((), label_dtype)}

    def loss_fn(self, params, batch):
        return self._loss_fn(params, batch)

    def client_arrays(self, client):
        return {"x": np.asarray(client.x, np.float32),
                "y": np.asarray(client.y,
                                self.buffers["y"].dtype)}

    def init_params(self, key):
        if self._init_fn is None:
            raise NotImplementedError("ArrayTask built without init_fn")
        return self._init_fn(key)


class LMTask(ClientTask):
    """Next-token prediction over an assigned architecture: the large-
    model federation path.

    Clients hold raw token streams shaped ``(n, seq_len + 1)`` (append
    ``(K,)`` codebooks for audio archs) in ``Client.x``; a training batch
    slices ``tokens = t[..., :-1]`` / ``labels = t[..., 1:]`` on device,
    so one int32 buffer per client serves both sides of the shift.
    ``param_specs`` comes from the model's partition-rule table
    (``tree_param_specs``), so under a composite mesh the federated round
    leaves params sharded FSDP x TP (never replicated) while the
    federation axes carry the client/batch dims — see docs/scaling.md.
    """

    def __init__(self, cfg, *, seq_len: int = 128, fsdp: bool = True):
        self.cfg = cfg
        self.seq_len = int(seq_len)
        self.tokens_per_sample = self.seq_len
        self.fsdp = fsdp
        tail: Tuple[int, ...] = (self.seq_len + 1,)
        if cfg.n_codebooks:
            tail = tail + (cfg.n_codebooks,)
        self.buffers = {"tokens": BufferSpec(tail, np.int32)}

    # -- engine protocol ------------------------------------------------------
    def _kernel_paths(self, cfg) -> tuple:
        from repro.models.moe import gmm_kernel_fits
        from repro.models.ssd import scan_fused
        rows = self.seq_len * cfg.top_k
        return (scan_fused(cfg, self.seq_len),
                bool(cfg.moe_experts_held) and gmm_kernel_fits(cfg, rows))

    def placed(self, devices: int) -> "LMTask":
        """On one device the SSD runs as the fused Pallas scan where its
        shapes fit (``ssm_kernel``) and the held experts' grouped matmul
        as the Pallas kernel (``moe_kernel``); a round sharded over more
        keeps the jnp paths, since GSPMD cannot partition a Mosaic
        call."""
        cfg = dataclasses.replace(self.cfg, ssm_kernel=devices == 1,
                                  moe_kernel=devices == 1)
        if self._kernel_paths(cfg) == self._kernel_paths(self.cfg):
            return self                  # the placement changes no path
        task = copy.copy(self)
        task.cfg = cfg
        return task

    @property
    def ssd_fused_layers(self) -> int:
        from repro.models.params import block_kinds
        from repro.models.ssd import scan_fused
        if not scan_fused(self.cfg, self.seq_len):
            return 0
        return sum(n for _, kind, n in block_kinds(self.cfg)
                   if kind in ("ssm", "hybrid"))

    @property
    def moe_experts_held(self) -> int:
        if not self.cfg.n_experts:
            return 0
        return self.cfg.moe_experts_held or self.cfg.n_experts

    @property
    def moe_slots_per_token(self) -> float:
        if not self.cfg.n_experts:
            return 0.0
        return self.cfg.top_k * self.moe_experts_held / self.cfg.n_experts

    def loss_fn(self, params, batch):
        from repro.models import transformer
        return transformer.train_loss(params, self.cfg, batch)

    def client_arrays(self, client):
        t = np.asarray(client.x, np.int32)
        want = self.buffers["tokens"].shape
        if t.shape[1:] != want:
            raise ValueError(f"client token stream shaped {t.shape[1:]}, "
                             f"task expects {want} (seq_len+1[, K])")
        return {"tokens": t}

    def make_batch(self, gathered):
        t = gathered["tokens"]
        # the seq axis sits before the codebook axis for audio archs
        ax = t.ndim - 2 if self.cfg.n_codebooks else t.ndim - 1
        sl = [slice(None)] * t.ndim
        sl[ax] = slice(None, -1)
        tokens = t[tuple(sl)]
        sl[ax] = slice(1, None)
        labels = t[tuple(sl)]
        return {"tokens": tokens, "labels": labels}

    def init_params(self, key):
        from repro.models.params import init_params
        return init_params(key, self.cfg)

    def param_specs(self, params):
        from repro.models.sharding import tree_param_specs
        return tree_param_specs(params, fsdp=self.fsdp)

    # -- client construction helpers ------------------------------------------
    def token_stream(self, rng: np.random.Generator, *, n: int,
                    domain: int = 0, zipf_a: float = 1.2) -> np.ndarray:
        """A client's dataset: ``n`` sequences of ``seq_len + 1`` tokens
        from the synthetic non-IID Zipf stream (``data/tokens.py``) —
        clients sharing a ``domain`` share token statistics."""
        from repro.data.tokens import client_token_stream
        K = max(1, self.cfg.n_codebooks)
        flat = client_token_stream(rng, self.cfg.vocab, domain,
                                   n * (self.seq_len + 1) * K,
                                   zipf_a=zipf_a)
        return flat.reshape((n,) + self.buffers["tokens"].shape)
