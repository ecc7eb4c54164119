"""Device-resident multi-round federated engine.

The seed host loop (FederatedTrainer.run) rebuilt a (C, E, B, ...) numpy
batch tensor, shipped it host->device, and computed scheme coefficients in
numpy — every round.  This engine moves the whole round inside one jitted,
chunked ``lax.scan``:

  * client datasets are padded to a common length and live on device once
    as (C, Nmax, ...) stacks, a sample of rank >= 2 flattened to one
    lane-dense row (BufferSpec.row_shape); per-round batch selection is an
    on-device gather of whole rows (vmapped ``jnp.take``), and the loss
    takes each sample's shape back per local step;
  * participation masks alpha can be sampled on device (inverse-CDF draw
    from an exact per-client table of the paper's Table-2 trace law, see
    trace_s_cdf) or supplied as a host-precomputed *plan* — the plan path
    consumes the trainer's numpy RNG in the seed order, so it is
    sample-for-sample identical to the legacy loop and is what the parity
    tests compare against; on-device draws fold the round index into the
    caller's base key per round (device_sample_round), so round tau's
    randomness never depends on span/chunk structure — the invariance
    mid-stream checkpoint/resume rests on (fed/state.py);
  * scheme A/B/C coefficients, fast-reboot boosts (per-client (tau0,
    boost) arrays evaluated at each in-chunk tau, so the O(dt^-2) decay is
    exact mid-chunk) and the staircase LR are computed inside the step;
  * R rounds run per host dispatch via ``lax.scan`` over power-of-two
    chunk sizes (bounded compile cache), with ``params`` donated to the
    chunk call on backends that support buffer donation;
  * aggregation uses the weighted_agg Pallas kernel (``agg="flat"``):
    one launch per delta leaf, each reduced where it lies (the int8
    wires: one launch over the flattened, quantized leaves), or the
    per-leaf jnp tree path (``agg="tree"``);
  * with ``sharding=FedSharding(...)`` the client/slot axis of every
    buffer is sharded over the mesh's federation axis: local epochs run
    device-parallel and the delta reduction ends in a cross-device
    all-reduce that leaves params replicated (see fed/sharding.py and
    docs/scaling.md).

The host loop above the engine (StreamScheduler in fed/stream.py — with
FederatedTrainer as a thin adapter over it) handles participation events,
span splitting and evaluation at span boundaries.

Usage::

    eng = RoundEngine(loss_fn=loss_fn, clients=clients, local_epochs=5,
                      batch_size=10, capacity=16)
    params, metrics = eng.run_span(params, tau_start=0, n_rounds=32,
                                   p=p, active=active, lr_shift_tau=0,
                                   reboot_tau0=rb0, reboot_boost=rbb,
                                   key=jax.random.PRNGKey(0))
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import agg_launches, scheme_coefficients
from repro.core.compression import resolve_compression, wire_bytes
from repro.core.fed_step import fed_round_parallel, fed_round_sequential
from repro.fed.bank import pad_rows
from repro.fed.task import ArrayTask
from repro.obs.telemetry import resolve as resolve_telemetry


def _pow2_chunks(n: int, cap: int):
    """Split n rounds into power-of-two chunk lengths <= cap (largest
    first), so at most log2(cap)+1 distinct scan lengths ever compile."""
    out = []
    while n > 0:
        r = min(1 << (n.bit_length() - 1), 1 << (cap.bit_length() - 1))
        out.append(r)
        n -= r
    return out


@functools.lru_cache(maxsize=1024)
def trace_cdf_row(trace, E: int) -> np.ndarray:
    """CDF table of completed epochs s for one trace: (E+1,) with
    cdf[k] = P(s <= k).  Cached per (trace, E) — traces are frozen
    dataclasses and the betainc evaluation dominates admit() otherwise;
    callers must not mutate the returned array.

    s = round(frac * E) for frac ~ Beta(a, b) mixed with an inactivity
    atom at 0, so the s-law is a discrete distribution over {0..E} whose
    CDF is exact regularized-incomplete-beta evaluations at the rounding
    boundaries (k + 1/2)/E — computed once at engine build / admit time,
    which removes the gamma rejection sampler from the hot path entirely
    while sampling the *identical* distribution as Trace.sample_s.
    """
    from jax.scipy.special import betainc

    ks = np.arange(E + 1)
    ab = trace._beta_params()
    if ab is None:
        # degenerate trace: frac == mean deterministically
        s0 = int(np.clip(np.round(trace.mean * E), 0, E))
        base = (ks >= s0).astype(np.float64)
    else:
        x = np.clip((ks + 0.5) / E, 0.0, 1.0)
        base = np.asarray(betainc(ab[0], ab[1], x), np.float64)
        base[-1] = 1.0
    q = trace.p_inactive
    if q > 0:
        # inactive rounds put an atom at s = 0
        row = q + (1.0 - q) * base
    else:
        # CPU-contention traces never produce zero epochs: the s=0
        # mass moves to s=1 (Trace.sample_s's maximum(s, 1))
        row = base.copy()
        row[0] = 0.0
    row[-1] = 1.0
    return row.astype(np.float32)


# an empty slot's s-law: all mass at s = 0, so the slot never trains even
# before the scheduler's active mask is applied
def empty_slot_cdf(E: int) -> np.ndarray:
    return np.ones(E + 1, np.float32)


def trace_s_cdf(clients, E: int) -> np.ndarray:
    """Per-client CDF table of completed epochs s: (C, E+1) with
    cdf[c, k] = P(s_c <= k).  See trace_cdf_row."""
    return np.stack([trace_cdf_row(cl.trace, E) for cl in clients]) \
        if clients else np.zeros((0, E + 1), np.float32)


def device_sample_round(key, active, n, s_cdf, E: int, B: int):
    """On-device sampling of participation + batch indices for ONE round.

    active: (C,) 0/1 mask of clients participating this span; n: (C,)
    dataset sizes; s_cdf: (C, E+1) per-client CDF of completed epochs
    (trace_s_cdf).  Returns alpha (C, E) f32, idx (C, E, B) i32.

    The engine calls this inside the scan body with a per-round key
    ``fold_in(base_key, tau)`` — round tau's draw is a pure function of
    (base_key, tau), never of how training was cut into run() calls,
    spans or chunks.  That invariance is what makes mid-stream
    checkpoint/resume bit-exact in device mode (fed/state.py).
    """
    with jax.named_scope("fed.sample"):
        ks, kb = jax.random.split(key)
        # inverse-CDF draw of s: s = #{k : cdf[k] < u}
        u = jax.random.uniform(ks, (n.shape[0],))
        s = jnp.sum(u[:, None] > s_cdf, axis=-1)
        s = s.astype(jnp.float32) * active
        alpha = (jnp.arange(E, dtype=jnp.float32)[None, :]
                 < s[:, None]).astype(jnp.float32)
        ub = jax.random.uniform(kb, (n.shape[0], E, B))
        nf = n.astype(jnp.float32)[:, None, None]
        idx = jnp.minimum((ub * nf).astype(jnp.int32),
                          n[:, None, None] - 1)
    return alpha, idx


def device_sample_span(key, R: int, active, n, s_cdf, E: int, B: int):
    """R rounds of device_sample_round under per-round folded keys:
    alphas (R, C, E) f32, idxs (R, C, E, B) i32.  Convenience/testing
    view of the sampling law the engine applies inside its scan."""
    keys = jax.vmap(lambda t: jax.random.fold_in(key, t))(jnp.arange(R))
    return jax.vmap(
        lambda k: device_sample_round(k, active, n, s_cdf, E, B))(keys)


def gather_rows(data, idx):
    """The round's batch gather: ``{name: (C, E, B, *row_shape)}`` with
    ``rows[c, e, b] = data[name][c, idx[c, e, b]]``.  Whole rows per
    index, vmapped over the slot axis so a sharded slot axis stays
    shard-local (merging (capacity, Nmax) into one axis would copy the
    stack whenever Nmax % 8 != 0).  Indices are in [0, n) by
    construction; ``mode="clip"`` states that, which drops the NaN-fill
    select jnp.take's default puts after the gather (a relayout pass
    of the whole batch on the TPU)."""
    take = jax.vmap(lambda d, i: jnp.take(d, i, axis=0, mode="clip"))
    return {name: take(buf, idx) for name, buf in data.items()}


def _slot_write(buf, row, slot):
    """dynamic-update-slice of one leading-axis row (jitted; one trace per
    buffer dtype/shape, reused for every admit/evict/set_trace)."""
    return jax.lax.dynamic_update_index_in_dim(buf, row, slot, axis=0)


_slot_write = jax.jit(_slot_write)


def _evict_write(n_buf, cdf_buf, cdf_row, slot):
    """Both evict writes (n -> 1, s-law -> empty-slot atom) in one
    dispatch — separate _slot_writes are a host dispatch each on the
    churn boundary path."""
    return (jax.lax.dynamic_update_index_in_dim(
                n_buf, jnp.int32(1), slot, axis=0),
            jax.lax.dynamic_update_index_in_dim(
                cdf_buf, cdf_row, slot, axis=0))


_evict_write = jax.jit(_evict_write)


@functools.lru_cache(maxsize=64)
def _slots_writer(sharding):
    """Jitted burst scatter (admit_many), pinned to the buffer's own
    sharding: without out_shardings the scatter result can come back
    replicated, silently changing the compiled span fns' input layout
    (one recompile per churn event — exactly what the slot machinery
    exists to avoid).  Cached per sharding object; rows: (k, ...)
    stacked, slots: (k,) int32, duplicate slots carry identical rows
    (pow2 padding repeats the last pair), so scatter order cannot
    matter."""
    return jax.jit(lambda buf, rows, slots: buf.at[slots].set(rows),
                   out_shardings=sharding)


def _slots_write(buf, rows, slots):
    return _slots_writer(buf.sharding)(buf, rows, slots)


def _pow2_pad(k: int) -> int:
    """Next power of two >= k: bursts of any size reuse at most
    log2(capacity)+1 compiled scatter shapes per buffer."""
    return 1 << (k - 1).bit_length() if k > 1 else 1


def _dev(x, dtype):
    """jnp.asarray(x, dtype) that short-circuits for device arrays
    already in dtype — the common span-args case."""
    if isinstance(x, jax.Array) and x.dtype == dtype:
        return x
    return jnp.asarray(x, dtype)


@functools.lru_cache(maxsize=64)
def _burst_writer(data_shardings, n_sharding, cdf_sharding):
    """ONE jitted dispatch updating every client buffer plus the n and
    s-CDF columns of an admit burst — the previous per-buffer scatters
    cost 3+ dispatches per burst and measured *slower* per row than
    single admits at small k.  Data rows go through a gather
    ``rows[idx]`` first, so a prefetched cohort stack can be committed
    partially / reordered (idx maps each written slot to its staged
    row); duplicate slots carry identical rows (pow2 padding repeats
    the last entry), so scatter order cannot matter.  Under mesh
    sharding, out_shardings pin each buffer's own sharding — without
    them the scatter result can come back replicated and silently
    re-layout the compiled span fns (one recompile per churn event).
    Single-device callers pass sharding None: out_shardings would mint
    *committed* outputs where the engine's buffers start uncommitted,
    and that committed-ness flip shows up as new C++ fastpath cache
    entries on every span fn (the churn contract pins those flat).
    Cached per sharding tuple; shape variants retrace under the same
    jit (bounded: pow2 burst x pow2 stack sizes)."""

    def write(data_bufs, data_rows, n_buf, n_rows, cdf_buf, cdf_rows,
              idx, slots):
        out = {name: buf.at[slots].set(data_rows[name][idx])
               for name, buf in data_bufs.items()}
        return out, n_buf.at[slots].set(n_rows), \
            cdf_buf.at[slots].set(cdf_rows)

    if n_sharding is None:
        return jax.jit(write)
    out_sh = (dict(data_shardings), n_sharding, cdf_sharding)
    return jax.jit(write, out_shardings=out_sh)


class RoundEngine:
    """Runs R federated rounds per host dispatch on device-resident data.

    The model/step layer is a ClientTask (fed/task.py): the task names
    the per-sample buffers, maps gathered samples to loss batches, and
    (for sharded large models) supplies per-leaf param PartitionSpecs.
    ``loss_fn=`` remains as the legacy constructor — it wraps into the
    equivalent ArrayTask.  Two execution modes share every other engine
    mechanism (sampling, slots, chunking, schemes):

      mode="client_parallel"   — vmap over the client axis (the small-
                                 model fast path; per-client param copies
                                 are live simultaneously);
      mode="client_sequential" — lax.scan over clients streaming each
                                 masked-SGD delta into one aggregation
                                 accumulator (global params + one live
                                 client delta; required >= 30B).

    Membership, data weights p, the LR-restart round and reboot state are
    constant within a span (the trainer splits spans at every event), so
    they enter the chunk as plain array arguments — values change between
    chunks without recompiling.

    Capacity slots: with ``capacity=C_max`` the engine preallocates C_max
    client slots (data/size/trace-CDF buffers have a C_max leading axis);
    slots beyond the founding clients start empty (n=1, s-law all mass at
    0).  ``admit(slot, client)`` / ``evict(slot)`` / ``set_trace(slot,
    trace)`` mutate one slot with a single host->device transfer plus a
    dynamic-update-slice each — buffer shapes never change, so the
    compiled span scans are reused across arbitrarily many membership
    events (no rebuild, no recompile).

    Sharding: with ``sharding=FedSharding(mesh)`` the slot axis of every
    client buffer is sharded over the mesh's federation ('data') axis
    (capacity is padded so each shard owns whole slots), local epochs run
    in parallel across devices and aggregation all-reduces to replicated
    params.  Slot writes stay one replicated-row device_put plus the same
    dynamic-update-slice, which XLA lowers to a masked shard-local write —
    so the zero-recompile membership-churn contract is preserved
    unchanged under sharding.
    """

    def __init__(self, *, clients, local_epochs: int,
                 batch_size: int, loss_fn=None, task=None,
                 scheme: str = "C", eta0: float = 0.01,
                 chunk_size: int = 16, agg: str = "auto",
                 donate: Optional[bool] = None,
                 with_metrics: bool = False,
                 capacity: Optional[int] = None,
                 max_samples: Optional[int] = None,
                 sharding=None, mode: str = "client_parallel",
                 telemetry=None, compression=None):
        if (task is None) == (loss_fn is None):
            raise ValueError("pass exactly one of task= or loss_fn=")
        if task is None:
            # legacy construction: a bare loss over {"x", "y"} batches —
            # wrap it in the equivalent ArrayTask (feature shape fixed by
            # the founding clients, exactly as before the refactor)
            if not clients:
                raise ValueError("RoundEngine needs at least one founding "
                                 "client (fixes the feature shape)")
            task = ArrayTask(loss_fn,
                             np.asarray(clients[0].x).shape[1:])
        # the task's kernels follow the round's placement
        self.task = task = task.placed(
            1 if sharding is None else sharding.mesh.size)
        if mode not in ("client_parallel", "client_sequential"):
            raise ValueError(f"mode must be client_parallel|"
                             f"client_sequential, got {mode!r}")
        self.mode = mode
        self.E = local_epochs
        self.B = batch_size
        self.scheme = scheme
        self.eta0 = eta0
        self.chunk_size = max(1, chunk_size)
        # delta wire format (core/compression): closed over by the jitted
        # chunk fns — a static spec, so changing it means a new engine
        self.compression = resolve_compression(compression)
        if agg == "auto":
            # the fused Pallas launch is the TPU path; its interpret-mode
            # emulation on CPU costs more than the per-leaf jnp tree —
            # EXCEPT for quantized wires, where the fused dequant-and-
            # reduce consumes the int8 payload directly and measures
            # faster than the quantize->dequantize->einsum reference
            # even under the interpreter
            agg = ("flat" if (jax.default_backend() == "tpu"
                              or self.compression.quantized) else "tree")
        self.agg = agg
        self.with_metrics = with_metrics
        if donate is None:  # CPU jit cannot reuse donated buffers
            donate = jax.default_backend() != "cpu"
        self.donate = donate

        self.sharding = sharding
        C = len(clients)
        if C == 0 and (capacity is None or max_samples is None):
            # a task fixes feature shapes, but an empty engine still needs
            # explicit geometry (the founding fleet normally supplies it)
            raise ValueError("RoundEngine without founding clients needs "
                             "explicit capacity= and max_samples=")
        if capacity is None:
            capacity = C
        if capacity < max(C, 1):
            raise ValueError(f"capacity {capacity} < {C} founding clients")
        if sharding is not None:
            # every shard owns the same number of whole slots; the extra
            # columns are ordinary empty capacity slots (p=0, never train)
            capacity = sharding.pad_capacity(capacity)
        self.capacity = capacity
        nmax = max((c.n for c in clients), default=1)
        if max_samples is not None:
            nmax = max(nmax, max_samples)
        self.nmax = nmax
        # per-sample buffers are the task's business: one (capacity, Nmax,
        # *spec.row_shape) stack per named buffer (logreg: x/y; MNIST: x
        # as 784-wide rows; LM: tokens), filled through its spec.shape view
        stacks = {
            name: np.zeros((capacity, nmax) + spec.shape, spec.dtype)
            for name, spec in task.buffers.items()}
        # empty slots keep n=1 so the batch-index draw idx = min(u*n, n-1)
        # stays a valid gather (their alpha/coeff are 0 regardless)
        n_arr = np.ones(capacity, np.int32)
        cdf = np.tile(empty_slot_cdf(self.E), (capacity, 1))
        for i, c in enumerate(clients):
            for name, row in pad_rows(task, nmax, c).items():
                stacks[name][i] = row
            n_arr[i] = c.n
        cdf[:C] = trace_s_cdf(clients, self.E)
        # datasets move host->device exactly once, here; under sharding
        # each device receives only the slot rows it owns, and single
        # rows written later (admit/set_trace) go up replicated
        if sharding is not None:
            self._put_slots = sharding.put_client
            self._put_row = lambda a: jax.device_put(
                a, sharding.replicated())
        else:
            self._put_slots = self._put_row = jax.device_put
        self.data = {name: self._put_slots(self._as_rows(name, buf))
                     for name, buf in stacks.items()}
        self.n = self._put_slots(n_arr)
        self.s_cdf = self._put_slots(cdf)
        self._fns = {}
        self._empty_cdf_row = None    # lazy device copy (see evict)
        self.trace_count = 0      # bumped at chunk trace time (see _get_fn)
        self._pspecs = None
        self._pspecs_built = False
        # telemetry (repro.obs): null by default — spans and counters on
        # this path are shared no-ops, so uninstrumented runs stay
        # bit-identical (pinned by tests/test_telemetry.py)
        self.telemetry = tel = resolve_telemetry(telemetry)
        self._m_traces = tel.counter(
            "engine_traces_total",
            "jitted chunk (re)traces — actual scan compiles")
        # analytic client->server traffic (core/compression.wire_bytes),
        # labeled by wire format — incremented per span from the realized
        # participation counts
        self._m_wire = tel.counter(
            "fed_wire_bytes_total",
            "client->server delta bytes (analytic, by wire format)",
            labelnames=("wire",))
        self._d_total: Optional[int] = None
        # completed local steps, summed over client-rounds as their s
        # reaches the host (account_uploads): tokens trained are this
        # times engine_tokens_per_step
        self._m_steps = tel.counter(
            "fed_local_steps_applied_total",
            "local SGD steps applied (sum of s over client-rounds)")
        # build-time gauges: buffers stored as flattened rows; tokens one
        # client's local step trains (B x seq_len, 0 for non-LM tasks);
        # SSD layers on the fused scan
        tel.gauge("engine_lane_dense_buffers",
                  "per-sample buffers stored as flattened rows").set(
            sum(spec.row_shape != spec.shape
                for spec in task.buffers.values()))
        tel.gauge("engine_tokens_per_step",
                  "tokens one client's local step trains").set(
            self.B * task.tokens_per_sample)
        tel.gauge("engine_ssd_fused_layers",
                  "SSD layers that run as the fused Pallas scan").set(
            task.ssd_fused_layers)
        # routed experts an expert layer holds, and the (token, expert)
        # slots a local step is expected to send them in each such layer
        tel.gauge("engine_moe_experts_held",
                  "routed experts each expert layer holds").set(
            task.moe_experts_held)
        tel.gauge("engine_moe_rows_per_step",
                  "expected held-expert token slots a step, per layer").set(
            self.B * task.tokens_per_sample * task.moe_slots_per_token)
        # set with _d_total, from the first span's params
        self._g_agg_launches = tel.gauge(
            "engine_agg_launches", "weighted_agg launches a round")

    def _param_specs(self, params):
        """The task's per-leaf PartitionSpecs (None => replicated),
        resolved once — only consulted under sharding.

        client_parallel vmaps a client axis over the federation axes, so
        a param spec may not also claim them (FSDP and client-parallelism
        would name the same mesh axis twice); the federation axes are
        stripped from every leaf spec, leaving pure TP ('model') sharding
        — the client_sequential mode keeps full FSDP x TP specs."""
        if not self._pspecs_built:
            specs = self.task.param_specs(params)
            if (specs is not None and self.sharding is not None
                    and self.mode == "client_parallel"):
                from jax.sharding import PartitionSpec as P
                fed = set(self.sharding.axes)

                def strip(entry):
                    if entry is None:
                        return None
                    if isinstance(entry, (tuple, list)):
                        kept = tuple(a for a in entry if a not in fed)
                        if not kept:
                            return None
                        # singleton tuples normalize to the bare name
                        # (tuple/bare spellings are cache-key-distinct)
                        return kept[0] if len(kept) == 1 else kept
                    return None if entry in fed else entry

                specs = jax.tree.map(
                    lambda s: P(*(strip(e) for e in s)), specs,
                    is_leaf=lambda x: isinstance(x, P))
            self._pspecs = specs
            self._pspecs_built = True
        return self._pspecs

    def _as_rows(self, name, stack):
        """A host (..., Nmax, *spec.shape) stack viewed in the device
        layout (..., Nmax, *spec.row_shape): a reshape of a contiguous
        array, so it copies nothing."""
        spec = self.task.buffers[name]
        lead = stack.ndim - len(spec.shape)
        return stack.reshape(stack.shape[:lead] + spec.row_shape)

    def _step_loss(self, params, rows):
        """The task's loss on one local step's gathered rows: each
        buffer takes its per-sample shape back and ``make_batch`` runs
        here, per step, so no relaid copy of the round's batch sits
        between the gather and the loss (for the MLP the reshape folds
        into its own flatten)."""
        batch = {}
        for name, r in rows.items():
            spec = self.task.buffers[name]
            lead = r.ndim - len(spec.row_shape)
            batch[name] = r.reshape(r.shape[:lead] + spec.shape)
        return self.task.loss_fn(params, self.task.make_batch(batch))

    # -- capacity-slot lifecycle ----------------------------------------------
    def admit(self, slot: int, client) -> None:
        """Stage a client's data/size/trace-CDF into an engine slot.  The
        client may be brand new (constructed after engine build) — shapes
        are static, so no compiled span scan is invalidated.  Lands via
        the same fused multi-buffer write as admit_many (a k=1 burst):
        one transfer per buffer, one device dispatch total."""
        if not 0 <= slot < self.capacity:
            raise IndexError(f"slot {slot} out of range [0, {self.capacity})")
        with self.telemetry.span("engine.admit", slot=slot):
            self._admit_many([(slot, client)])

    def admit_many(self, assignments) -> None:
        """Admit an arrival burst in ONE fused device dispatch.

        assignments: sequence of (slot, client) pairs.  Per-client row
        staging happens host-side as in admit(), then the whole burst —
        every data buffer plus the n and s-CDF columns — goes up as one
        stacked device_put per buffer and lands in a single jitted
        multi-buffer scatter (_burst_writer) instead of 3+ transfers and
        scatters; under sharding every transfer replicates the rows to
        all devices, so coalescing cuts the dominant cost by ~k.  Bursts
        are padded to a power-of-two length by repeating the last
        (slot, row) pair, so at most log2(capacity)+1 scatter shapes
        ever compile (the zero-recompile churn contract)."""
        assignments = list(assignments)
        if not assignments:
            return
        with self.telemetry.span("engine.admit_many", k=len(assignments)):
            self._admit_many(assignments)

    def _admit_many(self, assignments) -> None:
        for slot, _ in assignments:
            if not 0 <= slot < self.capacity:
                raise IndexError(
                    f"slot {slot} out of range [0, {self.capacity})")
        dup = [s for s, _ in assignments]
        if len(set(dup)) != len(dup):
            # duplicate-index scatter order is unspecified per buffer, so
            # one slot could mix two clients' rows across buffers
            raise ValueError(f"admit_many got duplicate slots: {dup}")
        staged = [pad_rows(self.task, self.nmax, c) for _, c in assignments]
        k = len(assignments)
        pad = _pow2_pad(k) - k
        stacks = {name: np.stack([st[name] for st in staged]
                                 + [staged[-1][name]] * pad)
                  for name in self.task.buffers}
        self.commit_burst(
            self.put_burst(stacks),
            slots=[s for s, _ in assignments],
            ns=[c.n for _, c in assignments],
            cdfs=[trace_cdf_row(c.trace, self.E) for _, c in assignments])

    # -- staged-cohort handoff (fed/bank.CohortStager) ------------------------
    def put_burst(self, stacks) -> dict:
        """Move pre-stacked (k, Nmax, *spec.shape) host buffers to device
        as (k, Nmax, *spec.row_shape) rows (replicated under sharding).
        Pure transfer, no engine mutation — safe to call from a staging
        thread while a span runs.  All buffers go up in ONE batched
        device_put — per-buffer puts cost a host dispatch each."""
        host = {name: self._as_rows(name, np.ascontiguousarray(a))
                for name, a in stacks.items()}
        if self.sharding is not None:
            return jax.device_put(host, self.sharding.replicated())
        return jax.device_put(host)

    def commit_burst(self, dev_rows, *, slots, ns, cdfs, idx=None) -> None:
        """Land a (possibly prefetched) burst: one fused jitted
        gather+scatter across every data buffer plus n and s_cdf.

        dev_rows: put_burst output — (K, Nmax, *spec.row_shape) device
        stacks; slots/ns/cdfs: per-written-slot values in slot order;
        idx: row index into dev_rows for each written slot (default
        identity), so a staged cohort can be committed as a subset or
        reordered.  n and the trace CDF always come from the *live*
        client at commit time (the caller's ns/cdfs), never from the
        staged stack — a TraceShift between staging and commit can't
        publish a stale law."""
        k = len(slots)
        if k == 0:
            return
        if idx is None:
            idx = list(range(k))
        pad = _pow2_pad(k) - k
        slots_h = np.asarray(list(slots) + [slots[-1]] * pad, np.int32)
        idx_h = np.asarray(list(idx) + [idx[-1]] * pad, np.int32)
        ns_h = np.asarray(list(ns) + [ns[-1]] * pad, np.int32)
        cdf_h = np.stack(list(cdfs) + [cdfs[-1]] * pad)
        if self.sharding is not None:
            slots_a, idx_a, ns_a = (jax.device_put(a)
                                    for a in (slots_h, idx_h, ns_h))
            cdf_rows = self._put_row(cdf_h)
        else:
            # one batched transfer — four small puts cost four host
            # dispatches on the boundary's critical path
            slots_a, idx_a, ns_a, cdf_rows = jax.device_put(
                (slots_h, idx_h, ns_h, cdf_h))
        if self.sharding is not None:
            writer = _burst_writer(
                tuple(sorted((name, buf.sharding)
                             for name, buf in self.data.items())),
                self.n.sharding, self.s_cdf.sharding)
        else:
            writer = _burst_writer((), None, None)
        self.data, self.n, self.s_cdf = writer(
            self.data, dev_rows, self.n, ns_a, self.s_cdf, cdf_rows,
            idx_a, slots_a)

    def evict(self, slot: int) -> None:
        """Free a slot: its s-law collapses to the empty-slot atom at 0
        and n drops to 1 (keeps gathers valid).  Stale data stays on
        device — it is unreachable (alpha=0, coeff=0) until the next
        admit overwrites it."""
        if not 0 <= slot < self.capacity:
            raise IndexError(f"slot {slot} out of range [0, {self.capacity})")
        with self.telemetry.span("engine.evict", slot=slot):
            if self._empty_cdf_row is None:
                # the empty-slot law is the same for every evict — put
                # it once
                self._empty_cdf_row = self._put_row(empty_slot_cdf(self.E))
            self.n, self.s_cdf = _evict_write(
                self.n, self.s_cdf, self._empty_cdf_row, np.int32(slot))

    def set_trace(self, slot: int, trace) -> None:
        """Swap the availability law of an occupied slot (TraceShift)."""
        if not 0 <= slot < self.capacity:
            raise IndexError(f"slot {slot} out of range [0, {self.capacity})")
        with self.telemetry.span("engine.set_trace", slot=slot):
            self.s_cdf = _slot_write(
                self.s_cdf, self._put_row(trace_cdf_row(trace, self.E)),
                np.int32(slot))

    # -- jitted chunk builders ------------------------------------------------
    def _round_core(self, params, data, alpha, idx, tau, p,
                    rb_tau0, rb_boost, lr_shift):
        # the round's phases carry jax.named_scope names (fed.sample,
        # fed.gather, fed.local_sgd, fed.flatten, fed.aggregate) in the
        # HLO's op_name metadata, so a device profile attributes its ops
        # to them; the compiled program is otherwise unchanged
        with jax.named_scope("fed.gather"):
            # the (C, E, B, *row_shape) rows reach the loss as they are
            # (_step_loss)
            rows = gather_rows(data, idx)
        with jax.named_scope("fed.aggregate"):
            s = jnp.sum(alpha, axis=-1)
            coeffs = scheme_coefficients(self.scheme, p, s, self.E)
            # fast-reboot boost, exact O((tau-tau0)^-2) decay at every
            # in-chunk tau; rb_boost == 1 for never-rebooted clients =>
            # multiplier 1
            dt = jnp.maximum(tau - rb_tau0, 0).astype(jnp.float32)
            coeffs = coeffs * (1.0 + (rb_boost - 1.0)
                               / jnp.square(1.0 + dt))
        eta = jnp.float32(self.eta0) / jnp.maximum(
            (tau + 1 - lr_shift).astype(jnp.float32), 1.0)
        pspecs = (self._param_specs(params) if self.sharding is not None
                  else None)
        if self.compression.active and pspecs is not None:
            # the quantizer works on the flattened-leaf layout, which the
            # model-sharded path cannot take (mixed-sharding leaf concat)
            raise ValueError(
                "compression is not supported with model-sharded params "
                "(task param_specs); use replicated params or "
                "compression='none'")
        if self.mode == "client_sequential":
            new_params, m = fed_round_sequential(
                self._step_loss, params, rows, alpha, coeffs, eta,
                with_metrics=self.with_metrics, sharding=self.sharding,
                param_specs=pspecs, compression=self.compression)
        else:
            # model-spec'd params must take the tree path: the flat
            # layout's shard_map returns each reduced leaf replicated
            # over the model axes (and the int8 wires concatenate
            # mixed-sharding delta leaves, the GSPMD pattern safe_concat
            # exists for)
            agg = "tree" if pspecs is not None else self.agg
            new_params, m = fed_round_parallel(
                self._step_loss, params, rows, alpha, coeffs, eta,
                agg=agg, with_metrics=self.with_metrics,
                sharding=self.sharding, param_specs=pspecs,
                compression=self.compression)
        return new_params, {"s": s, "eta": eta,
                            "delta_norm": m["delta_norm"]}

    def _agg_launches(self, params) -> int:
        """weighted_agg launches a round: the flat layout's
        (core.aggregation.agg_launches) where the round takes it, 0 on
        the tree path, the client-sequential mode and model-spec'd
        params."""
        flat = (self.mode == "client_parallel" and self.agg == "flat"
                and (self.sharding is None
                     or self._param_specs(params) is None))
        return agg_launches(params, self.compression) if flat else 0

    def _get_fn(self, R: int, sampled: bool):
        cache_key = (R, sampled)
        if cache_key in self._fns:
            return self._fns[cache_key]

        # round indices are derived INSIDE the jit from the scalar span
        # start (R is static per compiled chunk) — a host-side
        # jnp.arange per chunk costs a dispatch on the boundary path
        if sampled:
            def chunk(params, data, n, s_cdf, key, active, tau0,
                      p, rb_tau0, rb_boost, lr_shift):
                # trace-time side effect: the body runs only when jax
                # (re)traces, so this counts actual compiles — the
                # zero-recompile invariant's signal (the C++ fastpath
                # cache also keys on argument committed-ness, so its
                # _cache_size() over-reports)
                self.trace_count += 1
                self._m_traces.inc()
                taus = tau0 + jnp.arange(R, dtype=jnp.int32)

                def body(w, tau):
                    # per-round key: the draw for round tau is a pure
                    # function of (base key, tau), invariant to span and
                    # chunk structure — the checkpoint/resume contract
                    kt = jax.random.fold_in(key, tau)
                    alpha, idx = device_sample_round(
                        kt, active, n, s_cdf, self.E, self.B)
                    if self.sharding is not None:
                        # keep the per-round draws sharded on the client dim
                        alpha = self.sharding.constrain_client(alpha, 0)
                        idx = self.sharding.constrain_client(idx, 0)
                    return self._round_core(w, data, alpha, idx,
                                            tau, p, rb_tau0, rb_boost,
                                            lr_shift)
                return jax.lax.scan(body, params, taus)
        else:
            def chunk(params, data, alphas, idxs, tau0, p,
                      rb_tau0, rb_boost, lr_shift):
                self.trace_count += 1
                self._m_traces.inc()
                taus = tau0 + jnp.arange(R, dtype=jnp.int32)

                def body(w, xs):
                    alpha, idx, tau = xs
                    return self._round_core(w, data, alpha, idx,
                                            tau, p, rb_tau0, rb_boost,
                                            lr_shift)
                return jax.lax.scan(body, params, (alphas, idxs, taus))

        fn = jax.jit(chunk, donate_argnums=(0,) if self.donate else ())
        self._fns[cache_key] = fn
        return fn

    # -- host entry point -----------------------------------------------------
    def run_span(self, params, tau_start: int, n_rounds: int, *, p, active,
                 lr_shift_tau: int, reboot_tau0, reboot_boost,
                 plan=None, key=None, host_metrics: bool = True):
        """Run n_rounds starting at tau_start with fixed membership.

        plan: (alphas (R, C, E), idxs (R, C, E, B)) host-sampled arrays
        (numpy-RNG parity mode), or key: a jax PRNG key for fully
        on-device sampling.  Exactly one must be given.

        Returns (params, metrics) with metrics stacked over rounds:
        s (R, C), eta (R,), delta_norm (R,).  With
        ``host_metrics=False`` the metrics stay device-side as
        per-chunk lists ({key: [chunk arrays]}) and wire accounting is
        deferred — the caller converts later (``account_uploads``), so
        the host never blocks on the span and dispatch of the *next*
        span's boundary work overlaps this span's compute.
        """
        if (plan is None) == (key is None):
            raise ValueError("pass exactly one of plan= or key=")
        if n_rounds <= 0:
            # degenerate span: params unchanged, empty per-round metrics
            return params, {"s": np.zeros((0, self.capacity), np.float32),
                            "eta": np.zeros(0, np.float32),
                            "delta_norm": np.zeros(0, np.float32)}
        if self._d_total is None:
            # model size in floats, cached before params may be donated
            self._d_total = sum(
                int(np.prod(np.shape(leaf)))
                for leaf in jax.tree.leaves(params))
            self._g_agg_launches.set(self._agg_launches(params))
        # no-op for args already device-resident in the right dtype
        # (the StreamScheduler's cached span args) — an unconditional
        # jnp.asarray costs ~60us of python per arg per span
        p = _dev(p, jnp.float32)
        active = _dev(active, jnp.float32)
        rb_tau0 = _dev(reboot_tau0, jnp.int32)
        rb_boost = _dev(reboot_boost, jnp.float32)
        lr_shift = np.int32(lr_shift_tau)
        if plan is not None:
            alphas = jnp.asarray(plan[0], jnp.float32)
            idxs = jnp.asarray(plan[1], jnp.int32)
        if self.sharding is not None:
            # span args are per-slot columns -> shard with the buffers;
            # params enter replicated (small models) or stay sharded per
            # the task's model specs (the large-model FSDP x TP path)
            fs = self.sharding
            p, active, rb_tau0, rb_boost = (
                fs.put_client(a) for a in (p, active, rb_tau0, rb_boost))
            params = fs.put_params(params, self._param_specs(params))
            if plan is not None:
                alphas = fs.put_client(alphas, axis_dim=1)
                idxs = fs.put_client(idxs, axis_dim=1)

        tel = self.telemetry
        ms, off, tau = [], 0, tau_start
        with tel.span("engine.run_span", tau=tau_start, rounds=n_rounds):
            for r in _pow2_chunks(n_rounds, self.chunk_size):
                tau0 = np.int32(tau)     # round indices derive in-jit
                with tel.span("engine.dispatch", rounds=r):
                    if plan is not None:
                        fn = self._get_fn(r, sampled=False)
                        params, m = fn(params, self.data,
                                       alphas[off:off + r],
                                       idxs[off:off + r],
                                       tau0, p, rb_tau0, rb_boost, lr_shift)
                    else:
                        fn = self._get_fn(r, sampled=True)
                        # the base key passes through unchanged: per-round
                        # randomness folds tau inside the chunk body, so
                        # chunk splits never reuse (or re-shuffle) randomness
                        params, m = fn(params, self.data, self.n,
                                       self.s_cdf, key, active, tau0, p,
                                       rb_tau0, rb_boost, lr_shift)
                ms.append(jax.tree.map(np.asarray, m) if host_metrics
                          else m)
                off += r
                tau += r
        if not host_metrics:
            return params, {k: [m[k] for m in ms] for k in ms[0]}
        metrics = {k: np.concatenate([m[k] for m in ms]) for k in ms[0]}
        self.account_uploads(metrics["s"])
        return params, metrics

    def account_uploads(self, s: np.ndarray) -> None:
        """Charge fed_wire_bytes_total for a span's completed-epoch
        matrix — one delta upload per client-round with any epochs — and
        fed_local_steps_applied_total with its sum (run_span does this
        inline; deferred-metrics callers do it at conversion time)."""
        self._m_steps.inc(float(np.sum(s)))
        uploads = int((s > 0).sum())
        if uploads:
            self._m_wire.labels(self.compression.name).inc(
                wire_bytes(self._d_total, self.compression,
                           n_clients=uploads))
