"""Subprocess body for tests/test_compression.py.

Multi-device sharding needs XLA_FLAGS set before jax initializes, so the
4-virtual-device quantized-aggregation checks run here in a fresh
interpreter (same pattern as tests/_sharded_check.py).  On success the
last stdout line is ``RESULT {json}``.

Checks (the sharded acceptance criteria of the compressed-delta path):
  1. weighted_agg_sharded with scales == the single-device int8 kernel
     (identical codes/scales, shard-local dequant matvec + f32 psum
     epilogue vs one full reduction), single- and multi-block-K;
  2. a sharded int8 StreamScheduler matches the single-device int8 one
     (equal capacity, identical s streams, params within the same
     tolerance the f32 plan-parity check uses);
  3. zero scan recompiles across admit/evict/trace-shift churn on the
     quantized flat path.
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import numpy as np  # noqa: E402

import _subproc  # noqa: E402
from repro.configs.paper import SYNTHETIC_LR  # noqa: E402
from repro.core.compression import quantize_chunked  # noqa: E402
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


def make_sched(sharding, capacity=8, chunk_size=4):
    newcomer = make_clients(1, seed=99)[0]
    return StreamScheduler(
        clients=make_clients(), init_params=init_small(
            jax.random.PRNGKey(0), CFG),
        loss_fn=make_loss_fn(CFG), capacity=capacity, max_samples=60,
        local_epochs=5, batch_size=10, scheme="C", eta0=0.5, seed=0,
        mode="device", agg="flat", compression="int8",
        sharding=sharding, chunk_size=chunk_size,
        events=[Arrival(3, client=newcomer),
                Departure(6, client_id=2, policy="exclude")])


def check_quant_kernel_psum(fs):
    from repro.kernels.weighted_agg import (weighted_agg,
                                            weighted_agg_sharded)
    K, D, chunk = 64, 600, 64
    coeffs = jax.random.uniform(jax.random.PRNGKey(0), (K,))
    flat = jax.random.normal(jax.random.PRNGKey(1), (K, D)) * 0.3
    payload, scales = quantize_chunked(flat, chunk=chunk)
    want = np.asarray(weighted_agg(coeffs, payload, scales, chunk=chunk))
    for kb in (None, 8):   # single-block K and streamed multi-block K
        got = np.asarray(weighted_agg_sharded(
            coeffs, payload, scales, chunk=chunk, mesh=fs.mesh,
            k_block=kb))
        err = float(np.abs(got - want).max())
        RESULTS[f"quant_kernel_err_kblock_{kb}"] = err
        assert err < 1e-4, \
            f"quant psum epilogue diverges (k_block={kb}): {err}"


def check_quant_scheduler_parity(fs):
    # equal capacity on both sides so the (R, capacity) uniform draw —
    # and therefore the quantization input trajectory — coincides; only
    # the f32 reduction order differs (shard partials + psum vs one
    # accumulating grid), which amplifies like the documented flat-vs-
    # tree case, so the tolerance matches the f32 plan-parity gate
    single = make_sched(None)
    sharded = make_sched(fs)
    assert single.engine.compression.name == "int8"
    assert sharded.engine.compression.name == "int8"
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
        np.testing.assert_array_equal(h1.s, h2.s)
        assert h1.event == h2.event
    RESULTS["quant_parity_rounds"] = 12
    RESULTS["quant_parity_max_err"] = maxerr


def check_zero_recompile_churn(fs):
    # chunk_size=2 bounds the pow2 chunk lengths to {1, 2}; the first
    # run (with its own events at tau 3 and 6) warms both, so any new
    # cache entry afterwards is a genuine membership-churn recompile
    sch = make_sched(fs, chunk_size=2)
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


def main():
    n_dev = len(jax.devices())
    assert n_dev == 4, f"expected 4 virtual devices, got {n_dev}"
    fs = make_fed_sharding(4)
    assert fs.n_shards == 4
    check_quant_kernel_psum(fs)
    check_quant_scheduler_parity(fs)
    check_zero_recompile_churn(fs)
    RESULTS["n_devices"] = n_dev
    _subproc.emit(RESULTS)


if __name__ == "__main__":
    main()
