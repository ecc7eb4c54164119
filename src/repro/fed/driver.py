"""Host-level federated round driver.

Implements the full paper protocol around the jitted round step:
  * per-round participation sampling from device traces (alpha masks),
  * Scheme A/B/C aggregation coefficients,
  * arrivals with objective shift + fast-reboot (coefficient boost + LR
    restart, §4.2),
  * departures with include/exclude applicability decision (§4.3),
  * membership is handled by masking (alpha=0, coeff=0), so the compiled
    round step never recompiles as devices come and go.

Execution delegates to the device-resident RoundEngine (fed/engine.py):
client data lives on device once and R rounds run per host dispatch via a
chunked, donated lax.scan.  Three modes:

  engine="plan"   (default) participation/batch indices are sampled with
                  the host numpy RNG in the seed order (sample-for-sample
                  identical to the legacy loop) but every round runs on
                  device; spans break at events and eval rounds.
  engine="device" fully fused on-device jax.random sampling — the fast
                  path; statistically equivalent to "plan".
  engine="host"   the seed per-round host loop (reference for parity
                  tests and benchmarks).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import scheme_coefficients
from repro.core.arrivals import RebootState, staircase_lr
from repro.core.departures import BoundTerms, should_exclude
from repro.core.fed_step import make_fed_round
from repro.core.participation import Trace
from repro.fed.engine import RoundEngine


@dataclass
class Client:
    """One federated device: per-sample arrays + availability trace.

    ``x`` holds whatever the active ClientTask stores per sample — feature
    rows for the paper models, token sequences ``(n, S+1)`` for the LM
    path (``y``/test arrays stay None there)."""
    x: np.ndarray
    y: Optional[np.ndarray] = None
    trace: Trace = None
    x_test: Optional[np.ndarray] = None
    y_test: Optional[np.ndarray] = None
    # membership
    active_from: int = 0          # round the device joins (0 = founding)
    departs_at: Optional[int] = None
    departure_policy: str = "exclude"   # exclude | include | auto
    gamma_l: float = 1.0          # non-IID estimate used by policy "auto"

    @property
    def n(self) -> int:
        return len(self.y) if self.y is not None else len(self.x)


@dataclass
class RoundRecord:
    tau: int
    loss: float     # NaN on rounds where no eval ran (honest records)
    acc: float      # NaN on rounds where no eval ran
    eta: float
    n_active: int
    s: np.ndarray
    event: str = ""


class FederatedTrainer:
    def __init__(self, *, loss_fn: Optional[Callable] = None,
                 eval_fn: Optional[Callable] = None,
                 init_params, clients: List[Client], local_epochs: int = 5,
                 batch_size: int = 10, scheme: str = "C", eta0: float = 0.01,
                 reboot_boost: float = 3.0, fast_reboot: bool = True,
                 horizon: Optional[int] = None,
                 bound_terms: Optional[BoundTerms] = None,
                 seed: int = 0, engine: Optional[str] = "plan",
                 chunk_size: int = 16, agg: str = "auto",
                 donate: Optional[bool] = None,
                 compression=None, with_metrics: bool = False,
                 sharding=None, task=None,
                 mode: str = "client_parallel"):
        self.task = task
        self.mode = mode
        if loss_fn is None:
            if task is None:
                raise ValueError("pass loss_fn= (or a task= that carries "
                                 "one)")
            loss_fn = task.loss_fn
        self.loss_fn = loss_fn
        self.eval_fn = eval_fn  # eval_fn(params, x, y) -> (loss, acc)
        self.params = init_params
        self.clients = clients
        self.E = local_epochs
        self.B = batch_size
        self.scheme = scheme
        self.eta0 = eta0
        self.reboot_boost = reboot_boost
        self.fast_reboot = fast_reboot
        # Corollary 4.0.3 inputs for departure_policy == "auto": the
        # training deadline T and the fitted Theorem-3.1 bound terms
        self.horizon = horizon
        self.bound_terms = bound_terms or BoundTerms(
            D=5.0, V=20.0, gamma=10.0, E=local_epochs)
        self.rng = np.random.default_rng(seed)
        from repro.core.compression import resolve_compression
        self.compression = resolve_compression(compression)
        self.round_fn = jax.jit(make_fed_round(
            loss_fn, "client_parallel", compression=self.compression))
        self.engine_mode = engine if engine not in (None, "off") else "host"
        if self.engine_mode not in ("host", "plan", "device"):
            raise ValueError(f"engine must be one of host|plan|device|off, "
                             f"got {engine!r}")
        self.chunk_size = chunk_size
        self.agg = agg
        self.donate = donate
        self.with_metrics = with_metrics
        self.sharding = sharding
        self._engine: Optional[RoundEngine] = None
        self._scheduler = None
        self._key = jax.random.PRNGKey(seed)
        # membership bookkeeping
        self.objective: set = {i for i, c in enumerate(clients)
                               if c.active_from == 0}
        self.reboots: List[RebootState] = []
        self.lr_shift_tau = 0
        self.history: List[RoundRecord] = []
        self._next_tau = 0

    @property
    def engine(self) -> RoundEngine:
        if self._engine is None:
            self._engine = RoundEngine(
                loss_fn=None if self.task is not None else self.loss_fn,
                task=self.task, clients=self.clients,
                local_epochs=self.E, batch_size=self.B, scheme=self.scheme,
                eta0=self.eta0, chunk_size=self.chunk_size, agg=self.agg,
                donate=self.donate,
                compression=self.compression,
                with_metrics=self.with_metrics, sharding=self.sharding,
                mode=self.mode)
        return self._engine

    # -- weights over the current objective set -----------------------------
    def data_weights(self) -> np.ndarray:
        p = np.zeros(len(self.clients))
        total = sum(self.clients[i].n for i in self.objective)
        for i in self.objective:
            p[i] = self.clients[i].n / total
        return p

    def _participating(self, i: int, tau: int) -> bool:
        cl = self.clients[i]
        return (i in self.objective and tau >= cl.active_from
                and (cl.departs_at is None or tau < cl.departs_at))

    def _sample_plan(self, tau: int):
        """One round of host-RNG sampling: alpha (C, E) and batch indices
        idx (C, E, B).  Draw order matches the seed loop exactly, so a
        given numpy seed yields the identical sample stream."""
        C = len(self.clients)
        alpha = np.zeros((C, self.E), np.float32)
        idx = np.zeros((C, self.E, self.B), np.int64)
        for i, cl in enumerate(self.clients):
            if not self._participating(i, tau):
                continue
            alpha[i] = (np.arange(self.E)
                        < cl.trace.sample_s(self.rng, self.E)
                        ).astype(np.float32)
            idx[i] = self.rng.integers(0, cl.n, size=(self.E, self.B))
        return alpha, idx

    def _sample_round(self, tau: int):
        alpha, idx = self._sample_plan(tau)
        C = len(self.clients)
        xdim = self.clients[0].x.shape[1:]
        bx = np.zeros((C, self.E, self.B, *xdim), np.float32)
        by = np.zeros((C, self.E, self.B), np.int32)
        for i, cl in enumerate(self.clients):
            if self._participating(i, tau):
                bx[i] = cl.x[idx[i]]
                by[i] = cl.y[idx[i]]
        return alpha, {"x": bx, "y": by}

    # -- events --------------------------------------------------------------
    def _handle_events(self, tau: int) -> str:
        ev = ""
        for i, cl in enumerate(self.clients):
            if cl.active_from == tau and i not in self.objective:
                # arrival: mandatory objective shift (+ optional fast-reboot)
                self.objective.add(i)
                self.lr_shift_tau = tau
                if self.fast_reboot:
                    self.reboots.append(RebootState(tau, i,
                                                    self.reboot_boost))
                ev += f"arrival:{i};"
            if cl.departs_at == tau and i in self.objective:
                policy = cl.departure_policy
                if policy == "auto":
                    # Corollary 4.0.3: exclude iff enough training remains
                    T = self.horizon if self.horizon is not None \
                        else tau + 100
                    policy = "exclude" if should_exclude(
                        T, tau, self.bound_terms, cl.gamma_l) else "include"
                if policy == "exclude":
                    self.objective.discard(i)
                    self.lr_shift_tau = tau
                    ev += f"departure-exclude:{i};"
                else:
                    ev += f"departure-include:{i};"
        return ev

    # -- main loop ------------------------------------------------------------
    def run(self, n_rounds: int, eval_every: int = 1):
        if self.engine_mode == "host":
            return self._run_host(n_rounds, eval_every)
        return self._run_engine(n_rounds, eval_every)

    def _run_host(self, n_rounds: int, eval_every: int = 1):
        """The seed per-round host loop (reference path)."""
        start = self._next_tau
        for tau in range(start, start + n_rounds):
            ev = self._handle_events(tau)
            p = self.data_weights()
            alpha, batches = self._sample_round(tau)
            s = alpha.sum(axis=1)
            coeffs = np.array(scheme_coefficients(
                self.scheme, jnp.asarray(p), jnp.asarray(s), self.E))
            for rb in self.reboots:
                coeffs[rb.client_idx] *= rb.coeff_multiplier(tau)
            eta = staircase_lr(self.eta0, tau + 1, self.lr_shift_tau)
            self.params, _m = self.round_fn(
                self.params,
                {"x": jnp.asarray(batches["x"]),
                 "y": jnp.asarray(batches["y"])},
                jnp.asarray(alpha), jnp.asarray(coeffs),
                jnp.float32(eta))
            loss = acc = float("nan")
            if tau % eval_every == 0 or ev:
                loss, acc = self.evaluate()
            self.history.append(RoundRecord(tau, float(loss), float(acc),
                                            eta, int((s > 0).sum()), s, ev))
        self._next_tau = start + n_rounds
        return self.history

    def _stream_scheduler(self):
        """The engine path delegates to the streaming subsystem
        (fed/stream.py): the precomputed Client.active_from/departs_at
        schedule is translated into an event stream once, and the
        StreamScheduler owns span splitting, weights/reboot/LR
        recomputation and history.  The trainer is a thin adapter: it
        shares its clients/engine/RNG/history with the scheduler and
        mirrors membership state back after each run.  (Don't mix
        engine-mode and host-mode run() calls on one trainer — the
        scheduler tracks its own round clock.)"""
        if self._scheduler is None:
            from repro.fed.stream import (Arrival, Departure,
                                          StreamScheduler)
            events = []
            for i, cl in enumerate(self.clients):
                if cl.active_from > 0:
                    events.append(Arrival(cl.active_from, client_id=i))
                if cl.departs_at is not None:
                    events.append(Departure(cl.departs_at, client_id=i))

            def eval_cb(params):
                self.params = params
                return self.evaluate()

            self._scheduler = StreamScheduler(
                clients=self.clients, init_params=self.params,
                engine=self.engine, mode=self.engine_mode,
                reboot_boost=self.reboot_boost,
                fast_reboot=self.fast_reboot, horizon=self.horizon,
                bound_terms=self.bound_terms, rng=self.rng,
                key=self._key, evaluate=eval_cb, history=self.history,
                reboots=self.reboots, objective=self.objective,
                events=events)
        return self._scheduler

    def _run_engine(self, n_rounds: int, eval_every: int = 1):
        sch = self._stream_scheduler()
        sch.params = self.params
        sch.run(n_rounds, eval_every)
        # mirror scheduler state onto the legacy public attributes
        # (objective/reboots/history are shared objects already)
        self.params = sch.params
        self.lr_shift_tau = sch.lr_shift_tau
        self._next_tau = sch._next_tau
        return self.history

    def evaluate(self, include_idx: Optional[set] = None):
        idx = include_idx if include_idx is not None else self.objective
        xs = [self.clients[i].x_test for i in idx
              if self.clients[i].x_test is not None]
        ys = [self.clients[i].y_test for i in idx
              if self.clients[i].y_test is not None]
        if self.eval_fn is None or not xs:
            # task-only construction (e.g. LM clients without held-out
            # arrays): honest-NaN records, same as the scheduler's path
            return float("nan"), float("nan")
        return self.eval_fn(self.params, jnp.asarray(np.concatenate(xs)),
                            jnp.asarray(np.concatenate(ys)))
