"""Streaming participation: an event-sourced control plane driving spans.

The paper's core claim is that devices may "depart or arrive in the middle
of training" — yet FederatedTrainer required every arrival/departure to be
declared at construction time (Client.active_from / departs_at).  This
module makes participation an external *stream* (cf. Gu et al. 2021 on
arbitrary device unavailability; Wang & Ji 2022 on arbitrary client
participation), split into two layers:

  * FedState (fed/state.py) — the pure, serializable control plane: slot
    registry, objective/joined/departed/mask membership, reboot arrays,
    LR-shift round, the pending event queue and the RNG/key state, with
    event application as plain-data state transitions that *return* the
    implied engine actions;
  * StreamScheduler (here) — the thin span-execution loop: it pops due
    events at span boundaries, executes the returned slot actions against
    the capacity-slotted RoundEngine (arrival runs coalesce into one
    fused admit_many burst), and drives RoundEngine.run_span over the
    event-free gaps.  Between events, R rounds run per host dispatch on
    device-resident data; events cost one slot write each, never an
    engine rebuild or a scan recompile.

Because FedState round-trips through to_dict()/from_dict() and per-round
randomness is derived by folding the round index into a never-split base
key (fed/engine.py), ``save()``/``restore()`` give exact mid-stream
checkpoint/resume: a killed run restored from disk replays the remaining
rounds bit-for-bit against an uninterrupted one
(tests/test_checkpoint_resume.py).  fed/service.py layers a thread-safe
ingestion service on top; FederatedTrainer (fed/driver.py) remains a thin
adapter translating its precomputed schedule into events.

Event application semantics: events are applied at the first span boundary
with tau >= event.tau (spans always break at queued event taus, so an
event pushed before run() fires on its exact round; an event pushed with a
tau already in the past fires at the next boundary — the honest streaming
behavior for late-arriving news).

Usage::

    sch = StreamScheduler(clients=clients, init_params=params,
                          loss_fn=loss_fn, capacity=16,
                          events=[Arrival(tau=5, client=new_client)])
    sch.run(n_rounds=20, eval_every=5)   # push() more events, run() again
    sch.save("ckpt/")                    # ... crash ...
    sch = StreamScheduler.restore("ckpt/", loss_fn=loss_fn)
    sch.run(n_rounds=20, eval_every=5)   # resumes round-for-round
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arrivals import RebootState
from repro.core.departures import BoundTerms
from repro.fed.bank import ClientBank, CohortStager
from repro.fed.driver import Client, RoundRecord
from repro.fed.engine import RoundEngine, trace_cdf_row
# event types re-exported for compatibility (they lived here pre-PR-5)
from repro.fed.events import (Arrival, Departure,  # noqa: F401
                              InactivityBurst, ParticipationEvent,
                              TraceShift)
from repro.fed.state import FedState
from repro.obs.fedmetrics import FedObserver
from repro.obs.telemetry import resolve as resolve_telemetry


class StreamScheduler:
    """Consumes a stream of ParticipationEvents while driving
    RoundEngine.run_span over the event-free gaps.

    Scheduling loop: at each span start, pop every queued event with
    tau <= now, apply it to the FedState (slot bookkeeping, objective
    shift, reboot boost, LR restart, burst masking) and execute the
    returned engine actions (admit/evict/set_trace — consecutive admits
    coalesce into one fused admit_many burst); then run rounds until the
    next event tau / burst expiry / eval round, whichever is first.
    Membership-derived span arguments (weights p, active mask, reboot
    arrays) are recomputed only when an event dirtied them.

    mode="device": fully fused on-device sampling (the fast path).
    mode="plan":   host numpy-RNG sampling in the seed draw order —
                   sample-for-sample identical to the legacy host loop,
                   used by the trainer-parity tests.
    """

    def __init__(self, *, clients: Sequence[Client] = (), init_params,
                 engine: Optional[RoundEngine] = None,
                 loss_fn: Optional[Callable] = None,
                 task=None, engine_mode: str = "client_parallel",
                 eval_fn: Optional[Callable] = None,
                 capacity: Optional[int] = None,
                 max_samples: Optional[int] = None,
                 sharding=None,
                 local_epochs: int = 5, batch_size: int = 10,
                 scheme: str = "C", eta0: float = 0.01,
                 chunk_size: int = 16, agg: str = "auto",
                 donate: Optional[bool] = None,
                 compression=None, with_metrics: bool = False,
                 reboot_boost: float = 3.0, fast_reboot: bool = True,
                 horizon: Optional[int] = None,
                 bound_terms: Optional[BoundTerms] = None,
                 seed: int = 0, mode: str = "device",
                 rng: Optional[np.random.Generator] = None,
                 key=None, evaluate: Optional[Callable] = None,
                 history: Optional[List[RoundRecord]] = None,
                 reboots: Optional[List[RebootState]] = None,
                 objective: Optional[set] = None,
                 state: Optional[FedState] = None,
                 events: Sequence[ParticipationEvent] = (),
                 injector=None, log_spans: bool = False,
                 telemetry=None, bank=None, prefetch: bool = False):
        if mode not in ("device", "plan"):
            raise ValueError(f"mode must be device|plan, got {mode!r}")
        self.mode = mode
        # telemetry (repro.obs): null default — a reused engine keeps its
        # own telemetry; a freshly built one inherits the scheduler's
        self.telemetry = resolve_telemetry(telemetry)
        self.observer = FedObserver(self.telemetry)
        self._m_cache_hit = self.telemetry.counter(
            "sched_eval_cache_hits_total",
            "eval-array cache hits (objective unchanged)")
        self._m_cache_miss = self.telemetry.counter(
            "sched_eval_cache_miss_total",
            "eval-array cache rebuilds (objective membership changed)")
        # fault-injection hook (fed/faults.py): fires site "sched_span"
        # at every span iteration so chaos tests can crash mid-run
        self.injector = injector
        # optional per-span argument log: (tau, p, active, lr_shift_tau)
        # appended whenever membership-derived span args are recomputed —
        # the fuzzer's weight/LR invariants forward-fill from it
        self.span_log: Optional[List[tuple]] = [] if log_spans else None
        clients = list(clients) if state is None else state.clients
        if engine is None:
            engine = RoundEngine(
                loss_fn=loss_fn, task=task, clients=clients,
                local_epochs=local_epochs, batch_size=batch_size,
                scheme=scheme, eta0=eta0, chunk_size=chunk_size, agg=agg,
                donate=donate, compression=compression,
                with_metrics=with_metrics, capacity=capacity,
                max_samples=max_samples, sharding=sharding,
                mode=engine_mode, telemetry=telemetry)
        self.engine = engine
        self.E = engine.E
        self.B = engine.B
        self.eta0 = engine.eta0
        self.params = init_params
        self.eval_fn = eval_fn
        self._evaluate = evaluate          # optional external eval callback
        if state is None:
            state = FedState(
                clients=clients, capacity=engine.capacity,
                reboot_boost=reboot_boost, fast_reboot=fast_reboot,
                horizon=horizon, bound_terms=bound_terms,
                local_epochs=engine.E, seed=seed, rng=rng, key=key,
                objective=objective, reboots=reboots)
        self.state = state
        self.history: List[RoundRecord] = (history if history is not None
                                           else [])
        # tiered client store (fed/bank.py): the fleet's host-side home —
        # bank=True builds one from the engine geometry, or pass a
        # configured ClientBank (spill_dir / ram budget); prefetch=True
        # additionally overlaps arrival staging with span compute on a
        # background thread (implies a bank)
        if prefetch and bank is None:
            bank = True
        if bank:
            self.bank = (bank if isinstance(bank, ClientBank)
                         else ClientBank(engine.task, engine.nmax))
            for i, c in enumerate(self.state.clients):
                self.bank.put(i, c)
        else:
            self.bank = None
        self._stager = (CohortStager(engine, self.bank)
                        if prefetch else None)
        self._prefetch_sig = None
        self._staged = None          # retained cohort (spans boundaries)
        self.prefetch_hits = 0
        self.prefetch_misses = 0
        self._m_prefetch_hits = self.telemetry.counter(
            "sched_prefetch_hits_total",
            "admits served from a prefetched cohort")
        self._m_prefetch_miss = self.telemetry.counter(
            "sched_prefetch_misses_total",
            "admits that fell back to the synchronous staging path")
        self._span_args = None
        self._dirty = True
        self._eval_cache = None            # (objective_version, x, y)
        self.push(*events)

    # -- control-plane views (the public surface pre-refactor) ----------------
    @property
    def clients(self) -> List[Client]:
        return self.state.clients

    @property
    def objective(self) -> set:
        return self.state.objective

    @property
    def departed(self) -> set:
        return self.state.departed

    @property
    def slot_of(self):
        return self.state.slot_of

    @property
    def client_at(self):
        return self.state.client_at

    @property
    def free_slots(self):
        return self.state.free_slots

    @property
    def reboots(self) -> List[RebootState]:
        return self.state.reboots

    @property
    def lr_shift_tau(self) -> int:
        return self.state.lr_shift_tau

    @property
    def events_applied(self) -> int:
        return self.state.events_applied

    @property
    def rng(self) -> np.random.Generator:
        return self.state.rng

    @property
    def _next_tau(self) -> int:
        return self.state.next_tau

    @property
    def _queue(self):
        return self.state.queue

    def data_weights(self) -> np.ndarray:
        return self.state.data_weights()

    # -- queue ---------------------------------------------------------------
    def push(self, *events: ParticipationEvent) -> None:
        """Enqueue participation events (any order; any time — including
        between run() calls, which is the streaming use case).  With the
        tiered bank on, staging starts here — at ingestion — not at the
        next span start: the boundary is the deadline, so the staging
        thread should get the full span of lead time, not the sliver
        between span dispatch and the boundary."""
        self.state.push(*events)
        if self._stager is not None:
            self._maybe_prefetch()

    @property
    def pending(self) -> int:
        return self.state.pending

    # -- event application (executes FedState transitions on the engine) -----
    def _apply_events(self, tau: int) -> str:
        st = self.state
        if not st.due(tau):
            # fast path: nothing queued for this boundary — skip the
            # span/observer machinery entirely (most boundaries)
            if st.expire(tau):
                self._dirty = True
            return ""
        with self.telemetry.span("sched.apply_events", tau=tau):
            return self._apply_due_events(tau)

    def _apply_due_events(self, tau: int) -> str:
        st = self.state
        ev = ""
        # an arrival burst coalesces into one fused admit burst: slot
        # writes are deferred while consecutive admit actions accumulate,
        # and flushed before any action that may read or free a slot
        admits: List = []

        def flush():
            if admits:
                try:
                    self._flush_admits(admits)
                finally:
                    admits.clear()

        try:
            while st.due(tau):
                e = st.pop_event()
                s, actions = st.apply(e, tau)
                self.observer.observe_event(e, tau)
                for act in actions:
                    if act[0] == "admit":
                        admits.append((act[1], act[2]))
                    elif act[0] == "evict":
                        flush()
                        self.engine.evict(act[1])
                    else:                       # ("set_trace", slot, trace)
                        flush()
                        self.engine.set_trace(act[1], act[2])
                ev += s
                st.events_applied += 1
        finally:
            # a raising event must not strand staged admissions: slot
            # bookkeeping already recorded them, so the engine writes
            # have to land even on the error path
            flush()
        if st.expire(tau):
            self._dirty = True                  # masked cohort resumes
        if ev:
            self._dirty = True
        return ev

    def _flush_admits(self, admits: List[tuple]) -> None:
        """Land a coalesced admit burst: admits is (slot, client_id)
        pairs.  With a prefetched cohort covering some of the clients,
        those slots commit from the already-on-device stack (one fused
        gather+scatter); the rest take the synchronous admit_many path.
        n and trace CDFs always come from the live Client at commit
        time, so prefetched rows can never publish a stale law."""
        st = self.state
        pairs = [(slot, i, st.clients[i]) for slot, i in admits]
        staged = self._staged
        if self._stager is not None:
            fresh = self._stager.collect()
            if fresh is not None:
                # retain: later boundaries commit their subset of the
                # same stack without re-staging (rows are immutable)
                staged = self._staged = fresh
        if self.bank is not None:
            # fresh arrivals enter the bank here (first time their
            # client_id exists); a staged client's host rows ride along
            # so the span loop's thread never re-pads them
            for _, i, c in pairs:
                j = staged.index.get(id(c)) if staged is not None else None
                self.bank.put(i, c,
                              rows=staged.rows[j] if j is not None
                              else None)
        hits, misses = [], []
        for slot, _, c in pairs:
            j = staged.index.get(id(c)) if staged is not None else None
            if j is not None:
                hits.append((slot, c, j))
            else:
                misses.append((slot, c))
        if hits:
            self.engine.commit_burst(
                staged.dev,
                slots=[slot for slot, _, _ in hits],
                ns=[c.n for _, c, _ in hits],
                cdfs=[trace_cdf_row(c.trace, self.engine.E)
                      for _, c, _ in hits],
                idx=[j for _, _, j in hits])
            self.prefetch_hits += len(hits)
            self._m_prefetch_hits.inc(len(hits))
        if misses:
            if self._stager is not None:
                self.prefetch_misses += len(misses)
                self._m_prefetch_miss.inc(len(misses))
            self.engine.admit_many(misses)

    # -- evaluation -----------------------------------------------------------
    def _eval_arrays(self):
        """Concatenated held-out arrays over the objective, cached on
        device and invalidated only when objective *membership* changes
        (FedState.objective_version) — evaluate() used to re-concatenate
        and re-transfer every eval round."""
        version = self.state.objective_version
        if self._eval_cache is not None and self._eval_cache[0] == version:
            self._m_cache_hit.inc()
            return self._eval_cache[1], self._eval_cache[2]
        self._m_cache_miss.inc()
        xs = [self.clients[i].x_test for i in sorted(self.objective)
              if self.clients[i].x_test is not None]
        ys = [self.clients[i].y_test for i in sorted(self.objective)
              if self.clients[i].y_test is not None]
        if not xs:
            x = y = None
        else:
            x = jnp.asarray(np.concatenate(xs))
            y = jnp.asarray(np.concatenate(ys))
        self._eval_cache = (version, x, y)
        return x, y

    def evaluate(self):
        if self._evaluate is not None:
            return self._evaluate(self.params)
        if self.eval_fn is None:
            return float("nan"), float("nan")
        x, y = self._eval_arrays()
        if x is None:
            return float("nan"), float("nan")
        return self.eval_fn(self.params, x, y)

    # -- main loop ------------------------------------------------------------
    def run(self, n_rounds: int, eval_every: int = 1):
        with self.telemetry.span("sched.run", rounds=n_rounds):
            return self._run(n_rounds, eval_every)

    def _run(self, n_rounds: int, eval_every: int):
        eng = self.engine
        st = self.state
        start = st.next_tau
        stop = start + n_rounds
        tau = start
        # spans dispatch asynchronously: per-span metrics stay
        # device-side (host_metrics=False) and materialize only after
        # the loop, so the host races ahead applying events / staging
        # cohorts / dispatching the next boundary while the device is
        # still crunching earlier spans.  An evaluate() (which reads
        # params) is the only in-loop sync point.
        pending = []      # (tau, end, ev_label, device metrics, eval)
        try:
            while tau < stop:
                if self.injector is not None:
                    self.injector.fire("sched_span", tau=tau)
                ev = self._apply_events(tau)
                end = st.span_end(tau, stop, ev, eval_every)
                R = end - tau
                if self._stager is not None:
                    # double buffer: while this span computes, the
                    # staging thread assembles + ships the next event
                    # boundary's arrival cohort from the bank
                    self._maybe_prefetch()
                if self._span_args is None or self._dirty:
                    a = st.span_args(tau)
                    if self.span_log is not None:
                        self.span_log.append((tau, a["p"].copy(),
                                              a["active"].copy(),
                                              a["lr_shift_tau"]))
                    # one batched transfer for the four membership
                    # arrays (separate puts are a host dispatch each,
                    # paid at every churn boundary)
                    p_d, act_d, rb0_d, rbb_d = jax.device_put((
                        np.asarray(a["p"], np.float32),
                        np.asarray(a["active"], np.float32),
                        np.asarray(a["reboot_tau0"], np.int32),
                        np.asarray(a["reboot_boost"], np.float32)))
                    self._span_args = dict(
                        p=p_d, active=act_d,
                        lr_shift_tau=a["lr_shift_tau"],
                        reboot_tau0=rb0_d, reboot_boost=rbb_d)
                    self._dirty = False
                kwargs = self._span_args
                with self.telemetry.span("sched.run_span", tau=tau,
                                         rounds=R):
                    if self.mode == "device":
                        # the base key is never split: per-round
                        # randomness folds the round index on device, so
                        # the sample stream is invariant to span/chunk
                        # structure (resume parity)
                        self.params, m = eng.run_span(
                            self.params, tau, R, key=st.key,
                            host_metrics=False, **kwargs)
                    else:
                        plans = [st.sample_plan(t, self.E, self.B)
                                 for t in range(tau, end)]
                        alphas = np.stack([pl[0] for pl in plans])
                        idxs = np.stack([pl[1] for pl in plans])
                        self.params, m = eng.run_span(
                            self.params, tau, R, plan=(alphas, idxs),
                            host_metrics=False, **kwargs)
                eval_last = (end - 1) % eval_every == 0 or (ev and R == 1)
                ev_result = self.evaluate() if eval_last else None
                pending.append((tau, end, ev, m, ev_result))
                tau = end
            st.next_tau = stop
        finally:
            # materialize whatever completed, even if a mid-run fault
            # unwound the loop — those spans did run
            with self.telemetry.span("sched.flush", spans=len(pending)):
                self._flush_spans(pending)
        return self.history

    def _flush_spans(self, pending) -> None:
        """Convert deferred device-side span metrics to host records —
        history rows, observer signals, and wire accounting, in span
        order."""
        eng = self.engine
        # one batched transfer for every span's device metrics — a
        # per-array np.asarray would pay a separate sync each (dozens of
        # tiny readbacks per churned span run)
        hosted = jax.device_get([m for _, _, _, m, _ in pending])
        for (tau, end, ev, _, ev_result), m in zip(pending, hosted):
            m = {k: np.concatenate(chunks) for k, chunks in m.items()}
            eng.account_uploads(m["s"])
            self.observer.observe_span(self.state, tau, m, eng.scheme,
                                       self.E)
            for j, t in enumerate(range(tau, end)):
                loss = acc = float("nan")
                if ev_result is not None and t == end - 1:
                    loss, acc = ev_result
                s = m["s"][j]
                self.history.append(RoundRecord(
                    t, float(loss), float(acc), float(m["eta"][j]),
                    int((s > 0).sum()), s, ev if t == tau else ""))

    def _maybe_prefetch(self) -> None:
        """Submit the queued-arrival horizon as ONE staged cohort (not
        one per boundary): every Arrival currently in the queue pads,
        stacks and ships together, and successive boundaries commit
        their own subset of the retained stack.  Safe because the staged
        stack carries data rows only — n and the trace CDF are read from
        the live Client at commit — so a row can't go stale between
        boundaries.  Idempotent: skips when the retained cohort already
        covers the horizon; a genuinely new arrival set supersedes the
        in-flight staging work."""
        st = self.state
        if not st.queue:
            self._staged = None                 # horizon drained
            return
        until = max(t for t, _, _ in st.queue)
        items = st.upcoming_arrivals(until)
        if not items:
            return
        staged = self._staged
        if staged is not None and all(id(c) in staged.index
                                      for _, c in items):
            return
        sig = tuple(sorted(id(c) for _, c in items))
        if sig == self._prefetch_sig:
            return
        self._prefetch_sig = sig
        self._stager.submit(items)

    def close(self) -> None:
        """Stop the prefetch staging thread (if any).  Idempotent; the
        scheduler itself stays usable — the next prefetch would simply
        restage.  FederationService calls this whenever it retires a
        scheduler (stop / supervised recovery)."""
        self._staged = None
        if self._stager is not None:
            self._stager.close()

    def prefetch_stats(self) -> dict:
        """Bank + stager counters for dashboards and benches (empty when
        the tiered store is off)."""
        out = {}
        if self.bank is not None:
            out["bank"] = self.bank.stats()
        if self._stager is not None:
            out["stager"] = self._stager.stats()
            out["hits"] = self.prefetch_hits
            out["misses"] = self.prefetch_misses
        return out

    # -- checkpoint / resume ---------------------------------------------------
    def engine_config(self) -> dict:
        """The geometry/hyperparameters needed to rebuild the engine on
        restore (the loss/task callables are the caller's to re-supply)."""
        eng = self.engine
        return {"local_epochs": eng.E, "batch_size": eng.B,
                "scheme": eng.scheme, "eta0": eng.eta0,
                "chunk_size": eng.chunk_size, "agg": eng.agg,
                "compression": eng.compression.name,
                "with_metrics": eng.with_metrics,
                "engine_mode": eng.mode, "capacity": eng.capacity,
                "max_samples": eng.nmax, "mode": self.mode,
                "bank": self.bank is not None,
                "prefetch": self._stager is not None}

    def save(self, path: str, extra: Optional[dict] = None,
             client_chunks: Optional[bool] = None) -> None:
        """Persist params + FedState + history + engine config so a killed
        run resumes round-for-round (checkpoint/io.save_fed_checkpoint).
        Bank-backed schedulers default to the chunked fleet format
        (fed-checkpoint-v2): one checksummed npz per client, streamed,
        so a host-RAM-scale fleet never materializes twice."""
        from repro.checkpoint.io import save_fed_checkpoint
        if client_chunks is None:
            client_chunks = self.bank is not None
        save_fed_checkpoint(
            path, self.params, self.state.to_dict(),
            history=history_to_dict(self.history),
            config=self.engine_config(), extra=extra,
            injector=self.injector, telemetry=self.telemetry,
            client_chunks=client_chunks)

    @classmethod
    def restore(cls, path: str, *, loss_fn: Optional[Callable] = None,
                task=None, eval_fn: Optional[Callable] = None,
                evaluate: Optional[Callable] = None, sharding=None,
                donate: Optional[bool] = None,
                engine: Optional[RoundEngine] = None, injector=None,
                log_spans: bool = False, telemetry=None,
                **overrides) -> "StreamScheduler":
        """Rebuild a scheduler from ``save()`` output: the engine is
        reconstructed from the persisted geometry, every occupied slot is
        re-admitted from the serialized client data, and the FedState
        (queue, membership, RNG/key) resumes exactly where it stopped.
        Only the non-serializable callables (loss_fn/task, eval hooks)
        must be re-supplied.

        ``engine``: reuse an existing engine of the same geometry instead
        of building (and recompiling) a fresh one — every slot is evicted
        and the checkpoint's occupancy re-staged.  Safe only when no
        other thread still drives that engine (the service supervisor
        reuses its warm engine only after joining the dead worker).

        Raises checkpoint.CorruptCheckpointError when the checkpoint
        fails its checksum — supervised services fall back to an older
        snapshot."""
        from repro.checkpoint.io import load_fed_checkpoint
        params, state_dict, history, config, _extra = \
            load_fed_checkpoint(path, telemetry=telemetry)
        state = FedState.from_dict(state_dict)
        cfg = dict(config)
        cfg.update(overrides)
        if engine is None:
            if task is None and loss_fn is not None and state.clients:
                from repro.fed.task import ArrayTask
                task = ArrayTask(loss_fn,
                                 np.asarray(state.clients[0].x).shape[1:])
            engine = RoundEngine(
                task=task, clients=[], local_epochs=cfg["local_epochs"],
                batch_size=cfg["batch_size"], scheme=cfg["scheme"],
                eta0=cfg["eta0"], chunk_size=cfg["chunk_size"],
                agg=cfg["agg"], with_metrics=cfg["with_metrics"],
                # pre-compression checkpoints carry no key: f32 wire
                compression=cfg.get("compression", "none"),
                capacity=cfg["capacity"], max_samples=cfg["max_samples"],
                sharding=sharding, donate=donate,
                mode=cfg["engine_mode"], telemetry=telemetry)
        else:
            if engine.capacity != cfg["capacity"]:
                raise ValueError(
                    f"reused engine capacity {engine.capacity} != "
                    f"checkpoint capacity {cfg['capacity']}")
            if engine.compression.name != cfg.get("compression", "none"):
                raise ValueError(
                    f"reused engine compression "
                    f"{engine.compression.name!r} != checkpoint "
                    f"compression {cfg.get('compression', 'none')!r}")
            for slot in range(engine.capacity):
                engine.evict(slot)
        # re-stage every occupied slot (one fused burst; trace CDFs ride
        # along with each admit)
        engine.admit_many(sorted(
            ((slot, state.clients[i])
             for i, slot in state.slot_of.items()),
            key=lambda sc: sc[0]))
        sch = cls(init_params=jax.tree.map(jnp.asarray, params),
                  engine=engine, state=state, mode=cfg["mode"],
                  eval_fn=eval_fn, evaluate=evaluate,
                  history=history_from_dict(history),
                  injector=injector, log_spans=log_spans,
                  telemetry=telemetry,
                  # the bank rebuilds from the restored clients (its
                  # contents are derivable state, never persisted raw)
                  bank=cfg.get("bank", False),
                  prefetch=cfg.get("prefetch", False))
        return sch


# -- history (de)serialization -------------------------------------------------

def history_to_dict(history: Sequence[RoundRecord]) -> dict:
    """Columnar plain-data form of a RoundRecord list (numpy arrays +
    JSON-able lists) — round-trips exactly through history_from_dict."""
    R = len(history)
    cap = len(history[0].s) if R else 0
    return {
        "tau": np.asarray([h.tau for h in history], np.int64),
        "loss": np.asarray([h.loss for h in history], np.float64),
        "acc": np.asarray([h.acc for h in history], np.float64),
        "eta": np.asarray([h.eta for h in history], np.float64),
        "n_active": np.asarray([h.n_active for h in history], np.int64),
        "s": (np.stack([np.asarray(h.s, np.float32) for h in history])
              if R else np.zeros((0, cap), np.float32)),
        "event": [h.event for h in history],
    }


def history_from_dict(d: Optional[dict]) -> List[RoundRecord]:
    if not d or len(d.get("tau", ())) == 0:
        return []
    return [RoundRecord(int(d["tau"][j]), float(d["loss"][j]),
                        float(d["acc"][j]), float(d["eta"][j]),
                        int(d["n_active"][j]), np.asarray(d["s"][j]),
                        str(d["event"][j]))
            for j in range(len(d["tau"]))]
