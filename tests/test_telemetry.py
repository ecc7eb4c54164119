"""Observability plane: metrics registry, span tracing, paper gauges,
and the fed_top view.

The acceptance-critical properties pinned here:

  * with the default NullTelemetry the training path is bit-identical —
    same history, same params, same jit trace count — so observability
    can never perturb the science;
  * with telemetry enabled the overhead stays bounded (the plane is
    host-side numpy/dict work, far off the jit path);
  * histogram bucket math and the Prometheus exposition agree with the
    cumulative-``le`` semantics scrapers expect;
  * fed_top renders a frame headlessly against a live FederationService.
"""
import json
import math
import time
import warnings

import jax
import numpy as np
import pytest

from repro.obs import (DEFAULT_BUCKETS, MetricsRegistry, NullTelemetry,
                       Telemetry, Tracer, resolve, scheme_mass)
from repro.obs.telemetry import NULL

from test_stream import make_clients, make_scheduler

NO_EVAL = 10 ** 9


# -- metrics registry ----------------------------------------------------------

def test_counter_and_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "a counter")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g", "a gauge")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0


def test_histogram_le_inclusive_bucket_math():
    reg = MetricsRegistry()
    h = reg.histogram("h_seconds", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 8.0):
        h.observe(v)
    # cumulative form, le-inclusive: 1.0 lands in le="1"
    assert h.buckets() == [(1.0, 2), (2.0, 4), (4.0, 4), (math.inf, 5)]
    assert h.count == 5
    assert h.sum == pytest.approx(13.0)


def test_observe_many_matches_scalar_observe():
    reg = MetricsRegistry()
    a = reg.histogram("a_seconds")
    b = reg.histogram("b_seconds")
    vals = np.abs(np.random.default_rng(0).normal(0.01, 0.05, 500))
    for v in vals:
        a.observe(float(v))
    b.observe_many(vals)
    assert a.buckets() == b.buckets()
    assert a.sum == pytest.approx(b.sum)


def test_registry_idempotent_and_kind_mismatch():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total")
    c2 = reg.counter("x_total")
    assert c1 is c2
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    fam = reg.counter("y_total", labelnames=("site",))
    assert fam.labels("a") is fam.labels("a")
    assert fam.labels("a") is not fam.labels("b")


def test_prom_rendering_roundtrip():
    reg = MetricsRegistry()
    reg.counter("ev_total", "events").inc(3)
    h = reg.histogram("lat_seconds", "latency", labelnames=("name",),
                      buckets=(0.1, 1.0))
    h.labels("run").observe(0.05)
    h.labels("run").observe(0.5)
    h.labels("run").observe(5.0)
    text = reg.render_prom()
    lines = text.splitlines()
    assert "# TYPE ev_total counter" in lines
    assert "ev_total 3" in lines
    assert 'lat_seconds_bucket{name="run",le="0.1"} 1' in lines
    assert 'lat_seconds_bucket{name="run",le="1"} 2' in lines
    assert 'lat_seconds_bucket{name="run",le="+Inf"} 3' in lines
    assert 'lat_seconds_count{name="run"} 3' in lines
    # snapshot mirrors the same numbers as plain data (JSONL sink path)
    snap = reg.snapshot()
    assert snap["ev_total"]["samples"][0]["value"] == 3
    s = snap["lat_seconds"]["samples"][0]
    assert s["labels"] == {"name": "run"} and s["count"] == 3
    json.dumps(snap)                      # JSON-serializable throughout


# -- tracing -------------------------------------------------------------------

def test_span_nesting_and_jsonl_export(tmp_path):
    tr = Tracer(capacity=16)
    with tr.span("outer", k=1):
        with tr.span("inner"):
            pass
    spans = tr.peek(10)
    by_name = {s["name"]: s for s in spans}
    assert by_name["inner"]["parent"] == by_name["outer"]["id"]
    assert by_name["outer"]["parent"] is None
    assert by_name["outer"]["attrs"] == {"k": 1}
    assert all(s["dur_s"] >= 0 for s in spans)
    path = tmp_path / "spans.jsonl"
    n = tr.export_jsonl(str(path))
    assert n == 2
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert {x["name"] for x in lines} == {"outer", "inner"}
    assert tr.peek(10) == []              # export drained the ring


def test_tracer_ring_drops_oldest():
    tr = Tracer(capacity=2)
    for j in range(5):
        with tr.span(f"s{j}"):
            pass
    assert tr.recorded == 5
    assert tr.dropped == 3
    assert [s["name"] for s in tr.peek(10)] == ["s3", "s4"]


def test_telemetry_span_feeds_latency_histogram():
    tel = Telemetry()
    with tel.span("work"):
        pass
    h = tel.registry.histogram("span_seconds",
                               labelnames=("name",)).labels("work")
    assert h.count == 1


# -- null telemetry ------------------------------------------------------------

def test_null_telemetry_is_inert():
    tel = resolve(None)
    assert tel is NULL and not tel.enabled
    assert isinstance(tel, NullTelemetry)
    c = tel.counter("whatever")
    c.inc()
    assert c.value == 0.0
    with tel.span("x", a=1):
        pass
    # no ring and no registry: the span is a profiler annotation alone
    assert tel.tracer is None and tel.registry is None
    assert tel.render_prom() == ""


def test_null_telemetry_history_bit_identical_and_no_recompiles():
    """The tentpole invariant: instrumentation off the jit path, null by
    default — identical history, identical params, identical number of
    scan traces."""
    from repro.fed.stream import Arrival, TraceShift
    from repro.core.participation import TRACES

    def run_one(telemetry):
        clients = make_clients(6, seed=2)
        late = make_clients(8, seed=2)[7]
        sch = make_scheduler(clients, capacity=8, seed=2,
                             telemetry=telemetry,
                             events=[TraceShift(3, client_id=1,
                                                trace=TRACES[0]),
                                     Arrival(5, client=late, client_id=9)])
        sch.run(10, eval_every=4)
        return sch

    a = run_one(None)
    b = run_one(Telemetry())
    assert a.engine.trace_count == b.engine.trace_count
    assert len(a.history) == len(b.history)
    for ra, rb in zip(a.history, b.history):
        assert ra.tau == rb.tau and ra.event == rb.event
        assert ra.n_active == rb.n_active
        np.testing.assert_array_equal(np.asarray(ra.s), np.asarray(rb.s))
        assert (ra.loss == rb.loss or
                (ra.loss != ra.loss and rb.loss != rb.loss))
    for la, lb in zip(jax.tree.leaves(a.params),
                      jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_enabled_overhead_bounded():
    """Telemetry-on rounds/sec stays within a pinned fraction of
    telemetry-off (generous pin: the plane is host-side accounting)."""
    def rps(telemetry, rounds=48, reps=3):
        sch = make_scheduler(make_clients(6, seed=0), seed=0,
                             telemetry=telemetry)
        sch.run(4, eval_every=NO_EVAL)    # compile warmup
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            sch.run(rounds, eval_every=NO_EVAL)
            best = min(best, time.perf_counter() - t0)
        return rounds / best

    off, on = rps(None), rps(Telemetry())
    assert on >= 0.4 * off, (off, on)


# -- profiler alignment --------------------------------------------------------

def _profile_host_events(tmp_path, fn):
    """Run fn under a JAX profile; the host events as (name, stats)."""
    import glob

    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    with warnings.catch_warnings():
        # jaxlib's stats iterator warns about its own type on every read
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    out.extend((e.name, dict(e.stats)) for e in line.events)
    return out


def test_null_telemetry_spans_reach_the_profiler(tmp_path):
    """With telemetry=None the scheduler and engine spans still name the
    host's work in an active profile: one sched.run per call, one
    engine.dispatch per chunk (with its rounds), one sched.flush."""
    sch = make_scheduler(make_clients(4, seed=0), seed=0, chunk_size=4)
    sch.run(4, eval_every=NO_EVAL)        # compile outside the profile
    events = _profile_host_events(
        tmp_path, lambda: sch.run(8, eval_every=NO_EVAL))
    names = [n for n, _ in events]
    assert names.count("sched.run") == 1
    assert names.count("sched.flush") == 1
    dispatch = [st for n, st in events if n == "engine.dispatch"]
    assert [st["rounds"] for st in dispatch] == [4, 4]
    assert {"sched.run_span", "engine.run_span"} <= set(names)


def test_telemetry_spans_keep_their_ring_and_reach_the_profiler(tmp_path):
    tel = Telemetry()

    def work():
        with tel.span("demo.outer", k=2):
            with tel.span("demo.inner"):
                pass

    events = _profile_host_events(tmp_path, work)
    assert ("demo.outer", {"k": 2}) in events
    assert "demo.inner" in [n for n, _ in events]
    assert [s["name"] for s in tel.tracer.peek()] == ["demo.inner",
                                                      "demo.outer"]
    h = tel.registry.histogram("span_seconds", labelnames=("name",))
    assert h.labels("demo.outer").count == 1


def _sampled_chunk(**kw):
    """The engine's sampled 2-round chunk, lowered (not compiled)."""
    sch = make_scheduler(make_clients(4, seed=0), seed=0, **kw)
    eng = sch.engine
    a = sch.state.span_args(0)
    args = (sch.params, eng.data, eng.n, eng.s_cdf, sch.state.key,
            np.asarray(a["active"], np.float32), np.int32(0),
            np.asarray(a["p"], np.float32),
            np.asarray(a["reboot_tau0"], np.int32),
            np.asarray(a["reboot_boost"], np.float32), np.int32(0))
    return eng._get_fn(2, sampled=True).lower(*args)


def _sampled_chunk_hlo(**kw):
    """The lowered chunk as text with its op_name locations."""
    return _sampled_chunk(**kw).as_text(debug_info=True)


@pytest.mark.parametrize("kw, scopes", [
    # the f32 wire stages nothing: each delta leaf enters its launch
    (dict(agg="flat"), {"sample", "gather", "local_sgd", "aggregate"}),
    (dict(agg="flat", compression="int8"),
     {"sample", "gather", "local_sgd", "flatten", "aggregate"}),
    (dict(agg="tree"), {"sample", "gather", "local_sgd", "aggregate"}),
    (dict(engine_mode="client_sequential"),
     {"sample", "gather", "local_sgd", "aggregate"}),
    (dict(agg="flat", compression="bf16"),
     {"sample", "gather", "local_sgd", "flatten", "aggregate"}),
], ids=["flat", "flat-int8", "tree", "sequential", "flat-bf16"])
def test_round_phases_are_named_scopes(kw, scopes):
    """Each phase of a round carries its jax.named_scope into the HLO's
    op_name metadata, on every aggregation layout, wire and mode."""
    import re
    hlo = _sampled_chunk_hlo(**kw)
    assert set(re.findall(r"fed\.([a-z_]+)", hlo)) == scopes


@pytest.mark.parametrize("kw, padded", [
    (dict(agg="flat"), False),
    (dict(agg="flat", compression="int8"), True),
], ids=["flat", "flat-int8"])
def test_aggregation_kernel_names_its_operand_pad(kw, padded):
    """The int8 wire's zero pad of the kernel's operands carries the
    kernel module's own scope, inside the round's fed.aggregate, and no
    phase name of the round; the f32 wire pads no operand at all."""
    import re

    from repro.kernels.weighted_agg import PAD_SCOPE
    hlo = _sampled_chunk(**kw).compile().as_text()
    pads = set(re.findall(r'op_name="([^"]*)"', hlo))
    pads = {n for n in pads if f"/{PAD_SCOPE}/" in n}
    if not padded:
        assert not pads
        return
    assert pads and all("/fed.aggregate/jit(weighted_agg" in n
                        and "fed.flatten" not in n for n in pads)


# -- paper gauges (fedmetrics) -------------------------------------------------

def test_scheme_mass_matches_core_coefficients():
    from repro.core.aggregation import scheme_coefficients
    rng = np.random.default_rng(0)
    p = rng.random(8)
    p /= p.sum()
    s = rng.integers(0, 6, 8).astype(float)
    for scheme in ("A", "B", "C"):
        want = float(np.sum(np.asarray(
            scheme_coefficients(scheme, p, s, E=5))))
        assert scheme_mass(scheme, p, s, 5) == pytest.approx(
            want, rel=1e-5)


def test_fed_observer_gauges_from_live_run():
    from repro.fed.stream import TraceShift
    from repro.core.participation import TRACES
    tel = Telemetry()
    sch = make_scheduler(make_clients(6, seed=1), seed=1, telemetry=tel,
                         events=[TraceShift(2, client_id=0,
                                            trace=TRACES[1])])
    sch.run(8, eval_every=NO_EVAL)
    reg = tel.registry

    assert reg.counter("fed_rounds_total").value == 8
    assert reg.counter("sched_events_applied_total",
                       labelnames=("kind",)).labels(
        "TraceShift").value == 1
    assert reg.histogram("fed_event_staleness_rounds").count == 1
    n_obj = reg.gauge("fed_objective_clients").value
    active = reg.gauge("fed_active_clients").value
    inactive = reg.gauge("fed_inactive_clients").value
    assert n_obj == 6 and 0 <= active <= 6
    assert inactive == max(0.0, n_obj - active)
    assert reg.gauge("fed_scheme_weight_mass").value > 0
    fam = reg.gauge("fed_participation_rate", labelnames=("stat",))
    lo, mid, hi = (fam.labels("min").value, fam.labels("mean").value,
                   fam.labels("max").value)
    assert 0.0 <= lo <= mid <= hi <= 1.0
    # observer exposes the per-client view fed_top prints
    part = sch.observer.participation()
    assert set(part) == set(range(6))
    assert all(0 <= k <= n for k, n in part.values())


def test_bound_gauges_with_tractable_problem():
    from repro.core.aggregation import theta_bound
    from repro.core.theory import quadratic_problem_constants
    tel = Telemetry()
    sch = make_scheduler(make_clients(4, seed=3), seed=3, telemetry=tel)
    rng = np.random.default_rng(3)
    A_list = [np.diag(rng.uniform(0.5, 2.0, 2)) for _ in range(4)]
    c_list = [rng.normal(size=2) for _ in range(4)]
    p = np.full(4, 0.25)
    pc, _ = quadratic_problem_constants(A_list, c_list, p)
    sch.observer.set_problem(pc, theta=theta_bound("C", 4, 5))
    sch.run(6, eval_every=NO_EVAL)
    fam = tel.registry.gauge("fed_bound", labelnames=("term",))
    value = fam.labels("value").value
    assert value > 0 and math.isfinite(value)
    assert fam.labels("D").value >= 0
    assert fam.labels("gamma").value > 0


# -- service + fed_top ---------------------------------------------------------

def test_service_counters_work_without_telemetry():
    """drain()/stats() rely on functional counters even when the shared
    telemetry is the null object — the service keeps a private
    registry."""
    from repro.fed.service import FederationService
    from repro.fed.stream import TraceShift
    from repro.core.participation import TRACES
    sch = make_scheduler(make_clients(4, seed=0), seed=0)
    svc = FederationService(sch, span_rounds=2, eval_every=NO_EVAL,
                            max_rounds=8)
    assert not svc.telemetry.enabled
    with svc:
        assert svc.submit(TraceShift(0, client_id=0, trace=TRACES[2]))
        assert svc.drain(timeout=30)
        assert svc.wait_rounds(8, timeout=60)
    st = svc.stats()
    assert st["events_submitted"] == st["events_ingested"] == 1
    rep = svc.chaos_report()
    assert rep["detect_latency_mean_s"] == 0.0
    assert rep["n_recoveries"] == 0


def test_fed_top_renders_headlessly_against_live_service():
    from repro.fed.service import FederationService
    from repro.launch.fed_top import FedTop
    tel = Telemetry()
    sch = make_scheduler(make_clients(4, seed=0), seed=0, telemetry=tel)
    svc = FederationService(sch, span_rounds=2, eval_every=NO_EVAL,
                            max_rounds=8)
    with svc:
        svc.wait_rounds(8, timeout=60)
        top = FedTop(svc)
        frame1 = top.frame()
        frame2 = top.frame()              # second frame: rate available
    for needle in ("fed_top", "rounds", "events", "service", "paper",
                   "tau=8"):
        assert needle in frame2, frame2
    assert "r/s" in frame2                # rate needs two frames
    assert frame1.count("\n") >= 6

    # null-telemetry service still renders (registry-backed counters)
    sch2 = make_scheduler(make_clients(4, seed=0), seed=0)
    svc2 = FederationService(sch2, span_rounds=2, eval_every=NO_EVAL,
                             max_rounds=4)
    with svc2:
        svc2.wait_rounds(4, timeout=60)
        frame = FedTop(svc2).frame()
    assert "fed_top" in frame and "paper" not in frame


# -- the hybrid LM's scopes and gauges (Nemotron-H layer pattern) ---------------

def _hybrid_cfg(**kw):
    import dataclasses

    from repro.configs import get_config
    small = dict(n_layers=5, layer_pattern="MEM*E", d_model=64, vocab=256,
                 n_heads=4, n_kv_heads=2, head_dim=16, n_experts=8, top_k=2,
                 moe_experts_held=4, moe_d_ff=32, moe_shared_d_ff=48,
                 ssm_d_inner=128, ssm_head_dim=16, ssm_d_state=16,
                 ssm_n_groups=8, ssm_norm_groups=8, ssm_chunk=16)
    small.update(kw)
    return dataclasses.replace(get_config("nemotron-3-nano-30b-a3b"),
                               **small)


def _hybrid_scheduler(telemetry=None, seq=32, batch=2):
    from repro.core.participation import TRACES
    from repro.fed import Client, LMTask, StreamScheduler
    task = LMTask(_hybrid_cfg(), seq_len=seq)
    rng = np.random.default_rng(0)
    clients = [Client(x=task.token_stream(rng, n=4, domain=d),
                      trace=TRACES[d]) for d in range(2)]
    return StreamScheduler(
        clients=clients, task=task,
        init_params=task.init_params(jax.random.PRNGKey(0)),
        local_epochs=2, batch_size=batch, scheme="C", eta0=0.05, seed=0,
        chunk_size=1, engine_mode="client_sequential", telemetry=telemetry)


def test_hybrid_layers_are_named_scopes_inside_local_sgd():
    """The pattern's attention and expert layers carry lm.attn and
    lm.moe, the router and sort lm.moe.route and the grouped matmuls and
    combine lm.moe.experts (both nested in lm.moe), forward and backward,
    inside the client-sequential round's fed.local_sgd; the mixer keeps
    lm.mixer with lm.ssd in it."""
    import re
    sch = _hybrid_scheduler()
    eng = sch.engine
    a = sch.state.span_args(0)
    args = (sch.params, eng.data, eng.n, eng.s_cdf, sch.state.key,
            np.asarray(a["active"], np.float32), np.int32(0),
            np.asarray(a["p"], np.float32),
            np.asarray(a["reboot_tau0"], np.int32),
            np.asarray(a["reboot_boost"], np.float32), np.int32(0))
    hlo = eng._get_fn(1, sampled=True).lower(*args).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("lm.attn", "lm.moe", "lm.moe.route", "lm.moe.experts",
                  "lm.mixer", "lm.ssd"):
        scoped = re.escape(scope) + r"(?![\w.])"
        assert any(re.search(r"fed\.local_sgd\)?/.*" + scoped, n)
                   for n in names), scope
        assert any(re.search(r"fed\.local_sgd\)?/.*transpose\(.*"
                             + scoped, n) for n in names), scope
    for inner in ("route", "experts"):
        assert any(re.search(r"lm\.moe/.*lm\.moe\." + inner, n)
                   for n in names), inner
    assert any(re.search(r"lm\.mixer/.*lm\.ssd", n) for n in names)


def test_hybrid_gauges_at_engine_build():
    """engine_moe_experts_held and engine_moe_rows_per_step (B x S x
    top_k x held / experts) are set once at engine build; the client-
    sequential round launches no weighted_agg; no SSD layer runs fused
    on the CPU."""
    tel = Telemetry()
    sch = _hybrid_scheduler(telemetry=tel, seq=32, batch=2)
    sch.run(1, eval_every=NO_EVAL)
    g = tel.registry.gauge
    assert g("engine_moe_experts_held").value == 4
    assert g("engine_moe_rows_per_step").value == 2 * 32 * 2 * 4 / 8
    assert g("engine_agg_launches").value == 0
    assert g("engine_ssd_fused_layers").value == 0
    mlp = Telemetry()
    make_scheduler(make_clients(3, seed=0), seed=0, telemetry=mlp)
    assert mlp.registry.gauge("engine_moe_experts_held").value == 0
    assert mlp.registry.gauge("engine_moe_rows_per_step").value == 0


def test_hybrid_kernels_on_a_one_chip_placement(monkeypatch):
    """At the nemotron3-fed cell's widths (7 layers MEMEM*E, 8 of 128
    experts, S=4096, chunk 128, 8 groups) a round placed on one TPU
    runs its 3 M layers on the fused scan and its experts on the grouped
    matmul kernel: engine_ssd_fused_layers reads 3, and 1,536 held
    slots a step; on the CPU, or sharded, it keeps the jnp paths."""
    import dataclasses

    from repro.configs import get_config
    from repro.fed import LMTask
    from repro.kernels import ssd_chunk
    from repro.models import moe
    cfg = dataclasses.replace(get_config("nemotron-3-nano-30b-a3b"),
                              n_layers=7, layer_pattern="MEMEM*E",
                              vocab=16384, moe_experts_held=8)
    task = LMTask(cfg, seq_len=4096)
    assert task.placed(1) is task                      # CPU: jnp paths
    assert task.placed(1).ssd_fused_layers == 0
    monkeypatch.setattr(ssd_chunk, "resolve_interpret", lambda i: False)
    monkeypatch.setattr(moe, "resolve_interpret", lambda i: False)
    one = task.placed(1)
    assert one.ssd_fused_layers == 3
    assert one.cfg.moe_kernel and not task.cfg.moe_kernel
    assert task.placed(4) is task
    assert 1 * 4096 * one.moe_slots_per_token == 1536
