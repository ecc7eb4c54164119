"""Subprocess body for tests/test_sharded_engine.py.

Multi-device sharding can only be exercised if XLA_FLAGS is set before
jax initializes, and the tier-1 pytest process has long since imported
jax — so the 4-virtual-device checks run here, in a fresh interpreter.
Any assertion failure exits nonzero with a traceback on stderr; on
success the last stdout line is ``RESULT {json}`` for the parent test to
parse.

Checks (the acceptance criteria of the sharded federation axis):
  1. weighted_agg_sharded == the single-device reduction, for both the
     single-block-K layout and the streamed multi-block-K (k_block) one;
  2. plan-mode parity: a sharded StreamScheduler matches the unsharded
     one round-for-round (identical RNG stream, params allclose) through
     arrival/departure churn, with capacity padded 6 -> 8 over 4 shards;
  3. device-mode sampling is sharding-invariant: identical s streams;
  4. zero scan recompiles across admit/evict/trace-shift churn under
     sharding (compile-cache entry counts are flat);
  5. null-vs-enabled telemetry on the *sharded* engine: bit-identical
     history and params, identical trace counts (the single-device
     pin lives in tests/test_telemetry.py);
  6. the batch gather stays slot-local: a 28x28 MLP engine's compiled
     chunk holds no collective (all-gather, all-reduce, ...) of a
     (slot, sample, 784) row stack, of its label stack or of gathered
     784-wide rows.
"""
import re  # noqa: E402
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import numpy as np  # noqa: E402

import _subproc  # noqa: E402
from repro.configs.paper import SYNTHETIC_LR  # noqa: E402
from repro.core.participation import TRACES  # noqa: E402
from repro.data import synthetic_federation  # noqa: E402
from repro.fed import (Arrival, Client, Departure,  # noqa: E402
                       StreamScheduler, TraceShift, make_fed_sharding)
from repro.models.small import init_small, make_loss_fn  # noqa: E402

CFG = SYNTHETIC_LR
RESULTS = {}


def make_clients(n=6, seed=0):
    train, test = synthetic_federation(0.5, 0.5, n, seed=seed)
    rng = np.random.default_rng(seed)
    return [Client(x=tr[0], y=tr[1], trace=TRACES[rng.integers(0, 8)],
                   x_test=te[0], y_test=te[1])
            for tr, te in zip(train, test)]


def make_sched(sharding, mode, agg="auto", capacity=7, chunk_size=16):
    newcomer = make_clients(1, seed=99)[0]
    return StreamScheduler(
        clients=make_clients(), init_params=init_small(
            jax.random.PRNGKey(0), CFG),
        loss_fn=make_loss_fn(CFG), capacity=capacity, max_samples=60,
        local_epochs=5, batch_size=10, scheme="C", eta0=0.5, seed=0,
        mode=mode, agg=agg, sharding=sharding, chunk_size=chunk_size,
        events=[Arrival(3, client=newcomer),
                Departure(6, client_id=2, policy="exclude")])


def check_kernel_psum(fs):
    from repro.kernels.weighted_agg import (weighted_agg,
                                            weighted_agg_sharded)
    K, D = 64, 600
    coeffs = jax.random.uniform(jax.random.PRNGKey(0), (K,))
    deltas = jax.random.normal(jax.random.PRNGKey(1), (K, D))
    want = np.asarray(weighted_agg(coeffs, deltas))
    for kb in (None, 8):   # single-block K and streamed multi-block K
        got = np.asarray(weighted_agg_sharded(
            coeffs, deltas, mesh=fs.mesh, k_block=kb))
        err = float(np.abs(got - want).max())
        RESULTS[f"kernel_err_kblock_{kb}"] = err
        assert err < 1e-4, f"psum epilogue diverges (k_block={kb}): {err}"


def check_plan_parity(fs):
    single = make_sched(None, "plan")
    sharded = make_sched(fs, "plan")
    assert sharded.engine.capacity == 8, sharded.engine.capacity  # 7 -> 8
    assert single.engine.capacity == 7
    maxerr = 0.0
    for _ in range(12):
        single.run(1, eval_every=4)
        sharded.run(1, eval_every=4)
        for a, b in zip(jax.tree.leaves(single.params),
                        jax.tree.leaves(sharded.params)):
            maxerr = max(maxerr, float(np.abs(np.asarray(a, np.float32)
                                              - np.asarray(b, np.float32)
                                              ).max()))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-3, atol=3e-5)
    for h1, h2 in zip(single.history, sharded.history):
        np.testing.assert_array_equal(h1.s, h2.s[:len(h1.s)])
        assert (h2.s[len(h1.s):] == 0).all()    # padded slots never train
        assert h1.event == h2.event
    RESULTS["plan_parity_rounds"] = 12
    RESULTS["plan_parity_max_err"] = maxerr


def check_device_sampling_invariance(fs):
    # equal capacity on both sides: the on-device uniform draw is shaped
    # (R, capacity), so only the mesh layout may differ — the sampled s
    # stream must not (threefry is placement-invariant under GSPMD)
    single = make_sched(None, "device", capacity=8)
    sharded = make_sched(fs, "device", capacity=8)
    single.run(10, eval_every=5)
    sharded.run(10, eval_every=5)
    for h1, h2 in zip(single.history, sharded.history):
        np.testing.assert_array_equal(h1.s, h2.s)
    RESULTS["device_s_stream_identical"] = True


def check_zero_recompile_churn(fs):
    # chunk_size=2 bounds the pow2 chunk lengths to {1, 2}; the first run
    # (with its own events at tau 3 and 6) warms both, so any new cache
    # entry afterwards is a genuine membership-churn recompile
    sch = make_sched(fs, "device", agg="flat", chunk_size=2)
    sch.run(10, eval_every=5)           # warm every pow2 chunk + events
    eng = sch.engine
    fns = dict(eng._fns)
    assert fns, "expected compiled chunk fns"
    sizes = {k: f._cache_size() for k, f in fns.items()}
    sch.push(Arrival(12, client=make_clients(1, seed=123)[0]),
             TraceShift(13, client_id=0, trace=TRACES[3]),
             Departure(15, client_id=1, policy="exclude"))
    sch.run(10, eval_every=5)
    for k, f in eng._fns.items():
        if k in sizes:
            assert f._cache_size() == sizes[k], f"chunk {k} recompiled"
    assert set(eng._fns) == set(fns), "new scan lengths compiled"
    RESULTS["recompiles_across_churn"] = 0
    RESULTS["events_applied"] = sch.events_applied


def check_null_telemetry(fs):
    # PR 7 pinned null-vs-enabled telemetry bit-identity on the single-
    # device engine only; the sharded engine threads telemetry through
    # shard_map'd spans, so the contract needs its own pin here
    from repro.obs.telemetry import Telemetry

    def build(telemetry):
        newcomer = make_clients(1, seed=99)[0]
        sch = StreamScheduler(
            clients=make_clients(), init_params=init_small(
                jax.random.PRNGKey(0), CFG),
            loss_fn=make_loss_fn(CFG), capacity=8, max_samples=60,
            local_epochs=5, batch_size=10, scheme="C", eta0=0.5, seed=0,
            mode="device", sharding=fs, chunk_size=4,
            telemetry=telemetry,
            events=[Arrival(3, client=newcomer),
                    Departure(6, client_id=2, policy="exclude"),
                    TraceShift(5, client_id=1, trace=TRACES[3])])
        sch.run(10, eval_every=4)
        return sch

    a = build(None)
    b = build(Telemetry())
    assert a.engine.trace_count == b.engine.trace_count, \
        (a.engine.trace_count, b.engine.trace_count)
    assert len(a.history) == len(b.history)
    for ra, rb in zip(a.history, b.history):
        assert ra.tau == rb.tau and ra.event == rb.event
        assert ra.n_active == rb.n_active and ra.eta == rb.eta
        np.testing.assert_array_equal(np.asarray(ra.s), np.asarray(rb.s))
    for la, lb in zip(jax.tree.leaves(a.params),
                      jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    RESULTS["null_telemetry_bit_identical"] = True
    RESULTS["null_telemetry_trace_count"] = int(a.engine.trace_count)


def check_gather_slot_local(fs):
    from repro.configs.paper import PaperModelConfig
    from repro.fed import RoundEngine

    cfg = PaperModelConfig(name="mlp28", kind="mlp", input_shape=(28, 28),
                           n_classes=10, hidden=16)
    rng = np.random.default_rng(0)
    nmax = 61                            # not a multiple of 8
    clients = [Client(x=rng.standard_normal((n, 28, 28)).astype(np.float32),
                      y=rng.integers(0, 10, n).astype(np.int32),
                      trace=TRACES[i]) for i, n in enumerate([61, 30, 17])]
    eng = RoundEngine(loss_fn=make_loss_fn(cfg), clients=clients,
                      local_epochs=2, batch_size=4, capacity=7,
                      sharding=fs, agg="tree")
    cap = eng.capacity
    assert eng.data["x"].shape == (cap, nmax, 784), eng.data["x"].shape
    ones = np.ones(cap, np.float32)
    args = fs.put_client(ones)
    hlo = eng._get_fn(2, sampled=True).lower(
        init_small(jax.random.PRNGKey(0), cfg), eng.data, eng.n, eng.s_cdf,
        jax.random.PRNGKey(1), args, np.int32(0), args,
        fs.put_client(np.zeros(cap, np.int32)), args,
        np.int32(0)).compile().as_text()
    data = re.compile(rf"\[(?:{cap}|{cap // fs.n_shards}),{nmax}[,\]]"
                      r"|\[[0-9,]+,784\]")
    colls = [ln for ln in hlo.splitlines() if re.search(
        r"\b(all-gather|all-reduce|all-to-all|collective-permute|"
        r"reduce-scatter)(-start)?\(", ln)]
    assert colls, "expected the aggregation's all-reduce"
    bad = [ln for ln in colls if data.search(ln)]
    assert not bad, bad[:2]
    RESULTS["data_collectives"] = len(bad)


def main():
    n_dev = len(jax.devices())
    assert n_dev == 4, f"expected 4 virtual devices, got {n_dev}"
    fs = make_fed_sharding(4)
    assert fs.n_shards == 4
    check_kernel_psum(fs)
    check_plan_parity(fs)
    check_device_sampling_invariance(fs)
    check_zero_recompile_churn(fs)
    check_null_telemetry(fs)
    check_gather_slot_local(fs)
    RESULTS["n_devices"] = n_dev
    _subproc.emit(RESULTS)


if __name__ == "__main__":
    main()
