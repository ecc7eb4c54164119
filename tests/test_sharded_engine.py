"""The sharded federation axis (fed/sharding.py + engine sharding).

Two layers of coverage:

* in-process tests on a degenerate 1-device 'data' mesh — the sharded
  code path (committed NamedShardings, shard_map psum epilogue, capacity
  padding) with trivially-verifiable arithmetic, cheap enough for every
  tier-1 run;
* a single subprocess (tests/_sharded_check.py) with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` set before jax
  initializes, pinning the real multi-device contracts: round-for-round
  parity of the sharded engine vs the single-device engine, sampling
  invariance, the cross-device psum reduction for both weighted_agg
  layouts, and zero scan recompiles across membership churn.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _subproc

from repro.configs.paper import SYNTHETIC_LR
from repro.core.participation import TRACES
from repro.data import synthetic_federation
from repro.fed import Client, FederatedTrainer, make_fed_sharding
from repro.fed.sharding import FedSharding
from repro.launch.mesh import make_mesh
from repro.models.small import init_small, logits_small, make_loss_fn

CFG = SYNTHETIC_LR


# -- spec unit tests (no mesh computation) ------------------------------------

def test_pad_capacity_whole_slots_per_shard():
    fs = make_fed_sharding(1)
    assert fs.pad_capacity(6) == 6
    mesh = make_mesh((1,), ("data",))

    class FourShards(FedSharding):
        n_shards = 4
    fs4 = FourShards(mesh=mesh)
    assert [fs4.pad_capacity(c) for c in (1, 4, 6, 8, 9)] == [4, 4, 8, 8, 12]


def test_client_spec_axis_dim():
    fs = make_fed_sharding(1)
    assert fs.client_spec(3) == jax.sharding.PartitionSpec(
        "data", None, None)
    assert fs.client_spec(4, axis_dim=1) == jax.sharding.PartitionSpec(
        None, "data", None, None)
    assert fs.n_shards == 1


def test_fed_sharding_requires_named_axis():
    mesh = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="no 'data' axis"):
        FedSharding(mesh=mesh)


def test_weighted_agg_sharded_rejects_ragged_client_axis():
    from repro.kernels.weighted_agg import weighted_agg_sharded
    fs = make_fed_sharding(1)
    # a 1-device mesh can't produce the error, so check the guard directly
    with pytest.raises(ValueError, match="not divisible"):
        class FakeMesh:
            shape = {"data": 2}
        weighted_agg_sharded(jnp.ones(3), jnp.ones((3, 8)), mesh=FakeMesh())
    # happy path on the real mesh
    out = weighted_agg_sharded(jnp.ones(4), jnp.ones((4, 10)), mesh=fs.mesh)
    np.testing.assert_allclose(np.asarray(out), 4.0, rtol=1e-6)


@pytest.mark.parametrize("K", [4, 70])          # 70: the streamed K slabs
@pytest.mark.parametrize("wire", ["f32", "bf16", "int8"])
def test_weighted_agg_sharded_equals_weighted_agg(wire, K):
    """The shard_map wrapper (local launch + psum) returns what the local
    kernel does, on every wire."""
    from repro.core.compression import quantize_chunked
    from repro.kernels.weighted_agg import weighted_agg, weighted_agg_sharded
    fs = make_fed_sharding(1)
    rng = np.random.default_rng(K)
    coeffs = jnp.asarray(rng.uniform(0, 1, K), jnp.float32)
    flat = jnp.asarray(rng.normal(size=(K, 700)), jnp.float32)
    args, kw = (flat,), {}
    if wire == "bf16":
        args = (flat.astype(jnp.bfloat16),)
    elif wire == "int8":
        args, kw = quantize_chunked(flat, chunk=128), {"chunk": 128}
    want = weighted_agg(coeffs, *args, **kw)
    got = weighted_agg_sharded(coeffs, *args, mesh=fs.mesh, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- 1-device mesh: sharded path == unsharded path ----------------------------

def _make_clients(n=6, seed=0):
    train, test = synthetic_federation(0.5, 0.5, n, seed=seed)
    rng = np.random.default_rng(seed)
    return [Client(x=tr[0], y=tr[1], trace=TRACES[rng.integers(0, 8)],
                   x_test=te[0], y_test=te[1])
            for tr, te in zip(train, test)]


def _eval_fn(params, x, y):
    lg = logits_small(params, CFG, x)
    ll = jax.nn.log_softmax(lg)
    loss = -jnp.mean(jnp.take_along_axis(
        ll, y[:, None].astype(jnp.int32), axis=1))
    acc = jnp.mean((jnp.argmax(lg, -1) == y).astype(jnp.float32))
    return float(loss), float(acc)


@pytest.mark.parametrize("agg", ["tree", "flat"])
def test_one_device_mesh_matches_unsharded(agg):
    """On a (1,) 'data' mesh the sharded engine runs the identical
    arithmetic (psum over one shard is the identity), so plan-mode
    trajectories must agree tightly with the unsharded engine."""
    def trainer(sharding):
        return FederatedTrainer(
            loss_fn=make_loss_fn(CFG), eval_fn=_eval_fn,
            init_params=init_small(jax.random.PRNGKey(0), CFG),
            clients=_make_clients(), local_epochs=5, batch_size=10,
            scheme="C", eta0=0.5, seed=0, engine="plan", agg=agg,
            sharding=sharding)

    t0 = trainer(None)
    t1 = trainer(make_fed_sharding(1))
    t0.run(6, eval_every=3)
    t1.run(6, eval_every=3)
    assert t1.engine.sharding is not None
    for a, b in zip(jax.tree.leaves(t0.params), jax.tree.leaves(t1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    for h0, h1 in zip(t0.history, t1.history):
        np.testing.assert_array_equal(h0.s, h1.s)


# -- 4-virtual-device subprocess ----------------------------------------------

@pytest.fixture(scope="module")
def sharded_check():
    """Run tests/_sharded_check.py once under a 4-device CPU mesh."""
    return _subproc.run_check("_sharded_check.py")


def test_sharded_engine_round_for_round_parity(sharded_check):
    r = sharded_check
    assert r["n_devices"] == 4
    assert r["plan_parity_rounds"] == 12
    assert r["plan_parity_max_err"] < 3e-3
    assert r["device_s_stream_identical"] is True


def test_sharded_psum_aggregation_both_layouts(sharded_check):
    assert sharded_check["kernel_err_kblock_None"] < 1e-4
    assert sharded_check["kernel_err_kblock_8"] < 1e-4


def test_sharded_churn_zero_recompiles(sharded_check):
    assert sharded_check["recompiles_across_churn"] == 0
    assert sharded_check["events_applied"] >= 5


def test_sharded_null_telemetry_bit_identity(sharded_check):
    # the single-device pin lives in tests/test_telemetry.py; this one
    # covers the shard_map'd span path
    assert sharded_check["null_telemetry_bit_identical"] is True
    assert sharded_check["null_telemetry_trace_count"] > 0


def test_sharded_row_gather_stays_slot_local(sharded_check):
    # a 28x28 MLP's (slot, sample, 784) row store: no collective of the
    # stack or of gathered rows in the compiled chunk
    assert sharded_check["data_collectives"] == 0
