"""Device-resident round engine: parity vs the seed per-round host loop.

The plan-mode engine consumes the trainer's numpy RNG in the seed draw
order, so alpha masks and batch indices are sample-for-sample identical to
the legacy loop; with the matching ("tree") aggregation layout the
trajectories agree to f32 tolerance over many rounds including
arrival/departure events and a mid-chunk decaying reboot boost.

The pytree-flat Pallas aggregation is parity-tested at the aggregation
level (tight allclose vs aggregate_deltas across f32/bf16 leaves) and over
a short multi-round run.  Long chained runs under post-event dynamics
(reboot boost + LR restart) amplify the f32 sum-order difference between
the two layouts chaotically (observed 1e-7 -> 1e-2 within ~9 rounds), so
layout-crossed trajectory comparisons are intentionally short.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper import SYNTHETIC_LR, PaperModelConfig
from repro.core.aggregation import (aggregate_deltas, aggregate_deltas_flat,
                                    accumulate_delta, scheme_coefficients)
from repro.core.fed_step import fed_round_parallel, fed_round_sequential
from repro.core.participation import TRACES
from repro.data import synthetic_federation
from repro.fed import Client, FederatedTrainer, LMTask, RoundEngine
from repro.fed.engine import (_pow2_chunks, device_sample_round,
                              gather_rows, trace_s_cdf)
from repro.fed.task import BufferSpec, ClientTask
from repro.models.small import init_small, logits_small, make_loss_fn

CFG = SYNTHETIC_LR


def eval_fn(params, x, y):
    lg = logits_small(params, CFG, x)
    ll = jax.nn.log_softmax(lg)
    loss = -jnp.mean(jnp.take_along_axis(
        ll, y[:, None].astype(jnp.int32), axis=1))
    acc = jnp.mean((jnp.argmax(lg, -1) == y).astype(jnp.float32))
    return float(loss), float(acc)


def make_clients(n=8, seed=0, with_events=False):
    train, test = synthetic_federation(0.5, 0.5, n, seed=seed)
    rng = np.random.default_rng(seed)
    clients = [Client(x=tr[0], y=tr[1], trace=TRACES[rng.integers(0, 8)],
                      x_test=te[0], y_test=te[1])
               for tr, te in zip(train, test)]
    if with_events:
        clients[-1].active_from = 3   # arrival => reboot boost from tau=3
        clients[2].departs_at = 6
    return clients


def make_trainer(clients, *, scheme="C", engine="plan", agg="auto", **kw):
    return FederatedTrainer(
        loss_fn=make_loss_fn(CFG), eval_fn=eval_fn,
        init_params=init_small(jax.random.PRNGKey(0), CFG),
        clients=clients, local_epochs=5, batch_size=10, scheme=scheme,
        eta0=1.0, seed=0, engine=engine, agg=agg, **kw)


def assert_params_close(p1, p2, rtol=3e-4, atol=1e-5):
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("scheme", ["A", "B", "C"])
def test_engine_matches_host_loop_with_midchunk_reboot(scheme):
    """Fused multi-round scan == per-round host loop for schemes A/B/C,
    including an arrival at tau=3 whose reboot boost decays *inside* the
    subsequent chunk (eval_every=12 keeps rounds 3..11 in one span)."""
    th = make_trainer(make_clients(with_events=True), scheme=scheme,
                      engine="host")
    te = make_trainer(make_clients(with_events=True), scheme=scheme,
                      engine="plan", agg="tree", chunk_size=16)
    h1 = th.run(12, eval_every=12)
    h2 = te.run(12, eval_every=12)
    assert_params_close(th.params, te.params)
    assert th.objective == te.objective
    assert len(te.reboots) == len(th.reboots) == 1
    for r1, r2 in zip(h1, h2):
        np.testing.assert_array_equal(r1.s, r2.s)  # identical RNG stream
        np.testing.assert_allclose(r1.eta, r2.eta, rtol=1e-6)
        assert r1.event == r2.event
        assert np.isnan(r1.loss) == np.isnan(r2.loss)


def test_engine_flat_agg_short_trajectory_parity():
    """The flat Pallas layout tracks the host loop over a short run (before
    f32 sum-order differences can amplify through the training map)."""
    th = make_trainer(make_clients(), engine="host")
    tf = make_trainer(make_clients(), engine="plan", agg="flat")
    th.run(5, eval_every=5)
    tf.run(5, eval_every=5)
    assert_params_close(th.params, tf.params, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("dtypes", [(jnp.float32, jnp.float32),
                                    (jnp.float32, jnp.bfloat16)])
def test_flat_aggregation_matches_tree(dtypes):
    """aggregate_deltas_flat (one weighted_agg launch over the flattened
    model) == aggregate_deltas (per-leaf scaled-add) on mixed-dtype trees."""
    dt_a, dt_b = dtypes
    key = jax.random.PRNGKey(0)
    C = 6
    params = {"w": jax.random.normal(key, (37, 11), dt_a),
              "b": jax.random.normal(key, (11,), dt_b),
              "nested": {"v": jax.random.normal(key, (5, 3, 2), dt_a)}}
    deltas = jax.tree.map(
        lambda p: jax.random.normal(jax.random.fold_in(key, p.size),
                                    (C,) + p.shape, p.dtype), params)
    coeffs = jax.random.uniform(jax.random.PRNGKey(1), (C,))
    want = aggregate_deltas(params, deltas, coeffs)
    got = aggregate_deltas_flat(params, deltas, coeffs)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert w.dtype == g.dtype
        np.testing.assert_allclose(np.asarray(w, np.float32),
                                   np.asarray(g, np.float32),
                                   rtol=2e-2 if dt_b == jnp.bfloat16
                                   else 1e-5, atol=1e-3)


def _model_tree(name):
    """A model's parameter tree: the paper's MNIST 2NN (6 f32 leaves) or
    mamba2-130m's 15 leaves at reduced widths, bf16 matrices beside f32
    norms and SSD scalars as at its published widths."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.paper import MNIST_MLP
    key = jax.random.PRNGKey(3)
    if name == "mnist-mlp":
        return init_small(key, MNIST_MLP)
    cfg = dataclasses.replace(get_config("mamba2-130m").reduced(),
                              dtype="bfloat16")
    return LMTask(cfg, seq_len=8).init_params(key)


def _stacked_deltas(params, C):
    """f32 client deltas (C, ...) per leaf, as local_sgd returns them."""
    return jax.tree.map(
        lambda p: jax.random.normal(jax.random.fold_in(
            jax.random.PRNGKey(4), p.size), (C,) + p.shape), params)


@pytest.mark.parametrize("tree,wire", [("mnist-mlp", "none"),
                                       ("mamba2", "none"),
                                       ("mamba2", "bf16")])
def test_flat_aggregation_matches_tree_on_model_trees(tree, wire):
    """aggregate_deltas_flat, one launch per leaf where the leaf lies, ==
    aggregate_deltas on the cells' trees; the bf16 wire == the tree
    reduction of the bf16-rounded deltas."""
    params = _model_tree(tree)
    C = 7
    deltas = _stacked_deltas(params, C)
    coeffs = jax.random.uniform(jax.random.PRNGKey(5), (C,))
    sent = deltas if wire == "none" else jax.tree.map(
        lambda d: d.astype(jnp.bfloat16), deltas)
    want = aggregate_deltas(params, sent, coeffs)
    got = aggregate_deltas_flat(params, deltas, coeffs, compression=wire)
    assert len(jax.tree.leaves(got)) == (6 if tree == "mnist-mlp" else 15)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert w.dtype == g.dtype and w.shape == g.shape
        # a bf16 leaf may round the same f32 sum to the neighbouring ulp
        tol = 1e-2 if w.dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(w, np.float32),
                                   np.asarray(g, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("tree", ["mnist-mlp", "mamba2"])
def test_flat_aggregation_never_concatenates_or_pads_deltas(tree):
    """On the f32 wire no (C, ...) delta is concatenated or padded outside
    the kernel: each leaf enters its launch where it lies."""
    params = _model_tree(tree)
    C = 7
    deltas = _stacked_deltas(params, C)
    jaxpr = jax.make_jaxpr(aggregate_deltas_flat)(
        params, deltas, jnp.ones(C, jnp.float32))
    launches = []

    def walk(jxp):
        for eqn in jxp.eqns:
            if eqn.primitive.name == "pallas_call":
                launches.append(eqn)
                continue
            if eqn.primitive.name in ("concatenate", "pad"):
                for v in eqn.invars:
                    shape = getattr(getattr(v, "aval", None), "shape", ())
                    assert not (len(shape) >= 2 and shape[0] == C), (
                        f"{eqn.primitive.name} of a {shape} delta")
            for val in eqn.params.values():
                if hasattr(val, "eqns"):                # Jaxpr
                    walk(val)
                elif hasattr(val, "jaxpr"):             # ClosedJaxpr
                    walk(val.jaxpr)
    walk(jaxpr.jaxpr)
    assert len(launches) == len(jax.tree.leaves(params))


@pytest.mark.parametrize("model,wire,agg,launches", [
    ("mnist-mlp", "none", "flat", 6), ("mnist-mlp", "int8", "flat", 1),
    ("mnist-mlp", "none", "tree", 0), ("mamba2", "none", "flat", 15)])
def test_agg_launches_gauge(model, wire, agg, launches):
    """engine_agg_launches, set with the first span: one weighted_agg
    launch per parameter leaf on the f32 wire (the paper's MLP: 6; an LM
    task: its leaf count), one on the int8 wire, none on the tree path."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.paper import MNIST_MLP
    from repro.obs.telemetry import Telemetry

    rng = np.random.default_rng(0)
    if model == "mnist-mlp":
        kw = dict(loss_fn=make_loss_fn(MNIST_MLP),
                  clients=mlp28_clients([12, 9]))
        params = init_small(jax.random.PRNGKey(0), MNIST_MLP)
    else:
        cfg = dataclasses.replace(get_config("mamba2-130m").reduced(),
                                  dtype="bfloat16")
        task = LMTask(cfg, seq_len=8)
        kw = dict(task=task, clients=[
            Client(x=task.token_stream(rng, n=3, domain=d),
                   trace=TRACES[d]) for d in range(2)])
        params = task.init_params(jax.random.PRNGKey(0))
    tel = Telemetry()
    eng = RoundEngine(local_epochs=1, batch_size=2, agg=agg,
                      compression=wire, telemetry=tel, **kw)
    eng.run_span(params, 0, 1, p=np.full(2, 0.5, np.float32),
                 active=np.ones(2, np.float32), lr_shift_tau=0,
                 reboot_tau0=np.zeros(2, np.int32),
                 reboot_boost=np.ones(2, np.float32),
                 key=jax.random.PRNGKey(1))
    assert tel.registry.gauge("engine_agg_launches").value == launches


def test_engine_device_sampling_distribution():
    """On-device inverse-CDF sampling reproduces Trace.sample_s's law:
    per-client mean of s within a few stderr of the host sampler."""
    clients = make_clients(6, seed=1)
    eng = RoundEngine(loss_fn=make_loss_fn(CFG), clients=clients,
                      local_epochs=5, batch_size=4)
    from repro.fed.engine import device_sample_span
    alphas, idxs = device_sample_span(
        jax.random.PRNGKey(0), 600, jnp.ones(len(clients)), eng.n,
        eng.s_cdf, 5, 4)
    s_dev = np.asarray(alphas.sum(-1))        # (600, C)
    rng = np.random.default_rng(0)
    s_host = np.stack([[c.trace.sample_s(rng, 5) for c in clients]
                       for _ in range(600)])
    np.testing.assert_allclose(s_dev.mean(0), s_host.mean(0), atol=0.35)
    # batch indices in range
    n = np.asarray(eng.n)
    assert (np.asarray(idxs) < n[None, :, None, None]).all()
    assert (np.asarray(idxs) >= 0).all()


def test_engine_device_mode_trains():
    tr = make_trainer(make_clients(12, seed=2), engine="device",
                      chunk_size=8)
    hist = tr.run(30, eval_every=30)
    assert len(hist) == 30
    loss0 = hist[0].loss                  # evaluated at tau=0
    loss_end, _ = tr.evaluate()
    assert np.isfinite(loss0) and loss_end < 0.8 * loss0
    # all rounds carried realized participation counts
    assert all(h.n_active >= 1 for h in hist)


def test_engine_events_at_chunk_boundaries():
    """Arrivals/departures land on exact rounds even with large chunks."""
    tr = make_trainer(make_clients(with_events=True), engine="plan",
                      chunk_size=16)
    hist = tr.run(10, eval_every=10)
    assert any("arrival:7" in h.event for h in hist if h.tau == 3)
    assert any("departure" in h.event for h in hist if h.tau == 6)
    assert tr.lr_shift_tau == 6
    assert 7 in tr.objective and 2 not in tr.objective


def test_round_records_honest_nan_when_not_evaluated():
    """Satellite fix: rounds without an eval record NaN, never a stale
    copy of the previous eval."""
    for engine in ("host", "plan"):
        tr = make_trainer(make_clients(), engine=engine)
        hist = tr.run(6, eval_every=2)
        for h in hist:
            if h.tau % 2 == 0:
                assert np.isfinite(h.loss) and np.isfinite(h.acc)
            else:
                assert np.isnan(h.loss) and np.isnan(h.acc)


def test_accumulate_delta_accepts_plain_float():
    acc = {"w": jnp.zeros((3,), jnp.float32)}
    delta = {"w": jnp.ones((3,), jnp.bfloat16)}
    out = accumulate_delta(acc, delta, 0.5)
    np.testing.assert_allclose(np.asarray(out["w"]), 0.5)
    out2 = accumulate_delta(acc, delta, jnp.float32(2.0))
    np.testing.assert_allclose(np.asarray(out2["w"]), 2.0)


def test_run_span_zero_rounds():
    """Regression: n_rounds == 0 used to crash on ms[0] of an empty
    metrics list; it must return params unchanged with empty metrics."""
    clients = make_clients(4)
    eng = RoundEngine(loss_fn=make_loss_fn(CFG), clients=clients,
                      local_epochs=5, batch_size=4)
    params = init_small(jax.random.PRNGKey(0), CFG)
    C = len(clients)
    for kw in (dict(key=jax.random.PRNGKey(1)),
               dict(plan=(np.zeros((0, C, 5), np.float32),
                          np.zeros((0, C, 5, 4), np.int64)))):
        out, m = eng.run_span(params, 3, 0, p=np.ones(C) / C,
                              active=np.ones(C), lr_shift_tau=0,
                              reboot_tau0=np.zeros(C, np.int32),
                              reboot_boost=np.ones(C, np.float32), **kw)
        assert_params_close(params, out, rtol=0, atol=0)
        assert m["s"].shape == (0, C)
        assert m["eta"].shape == (0,)
        assert m["delta_norm"].shape == (0,)


def test_sequential_mode_matches_parallel_same_seeds():
    """client_sequential streams deltas into one accumulator instead of
    vmapping the client axis; with the same device-mode key (identical
    sampling) the two modes implement the same Eq. (2) round — params
    agree to f32 reassociation tolerance over a multi-chunk span."""
    clients = make_clients(6)
    params = init_small(jax.random.PRNGKey(0), CFG)
    outs, mets = {}, {}
    for mode in ("client_parallel", "client_sequential"):
        eng = RoundEngine(loss_fn=make_loss_fn(CFG), clients=make_clients(6),
                          local_epochs=5, batch_size=10, scheme="C",
                          eta0=1.0, chunk_size=4, mode=mode,
                          with_metrics=True)
        cap = eng.capacity
        p = np.array([c.n for c in clients], np.float64)
        p = p / p.sum()
        outs[mode], mets[mode] = eng.run_span(
            params, 0, 10, p=p, active=np.ones(cap, np.float32),
            lr_shift_tau=0, reboot_tau0=np.zeros(cap, np.int32),
            reboot_boost=np.ones(cap, np.float32),
            key=jax.random.PRNGKey(7))
    # identical on-device sampling stream...
    np.testing.assert_array_equal(mets["client_parallel"]["s"],
                                  mets["client_sequential"]["s"])
    # ...and matching trajectories + delta norms
    assert_params_close(outs["client_parallel"], outs["client_sequential"],
                        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(mets["client_parallel"]["delta_norm"],
                               mets["client_sequential"]["delta_norm"],
                               rtol=1e-4)


def test_engine_rejects_bad_mode_and_double_task():
    clients = make_clients(2)
    with pytest.raises(ValueError, match="client_parallel"):
        RoundEngine(loss_fn=make_loss_fn(CFG), clients=clients,
                    local_epochs=2, batch_size=5, mode="bogus")
    from repro.fed.task import ArrayTask
    task = ArrayTask(make_loss_fn(CFG), clients[0].x.shape[1:])
    with pytest.raises(ValueError, match="exactly one"):
        RoundEngine(loss_fn=make_loss_fn(CFG), task=task, clients=clients,
                    local_epochs=2, batch_size=5)


def test_admit_many_matches_single_admits():
    """One fused admit_many burst stages the same slot state as the
    equivalent sequence of single admits (including pow2 padding that
    repeats the last row)."""
    clients = make_clients(4)
    fresh = make_clients(3, seed=77)
    engs = []
    for _ in range(2):
        engs.append(RoundEngine(loss_fn=make_loss_fn(CFG),
                                clients=make_clients(4), local_epochs=5,
                                batch_size=10, capacity=8,
                                max_samples=max(c.n for c in fresh)))
    engs[0].admit_many([(4, fresh[0]), (5, fresh[1]), (6, fresh[2])])
    for slot, c in [(4, fresh[0]), (5, fresh[1]), (6, fresh[2])]:
        engs[1].admit(slot, c)
    for name in engs[0].data:
        np.testing.assert_array_equal(np.asarray(engs[0].data[name]),
                                      np.asarray(engs[1].data[name]))
    np.testing.assert_array_equal(np.asarray(engs[0].n),
                                  np.asarray(engs[1].n))
    np.testing.assert_array_equal(np.asarray(engs[0].s_cdf),
                                  np.asarray(engs[1].s_cdf))


def test_trainer_plumbs_engine_options():
    """Satellite: donate/with_metrics reach the RoundEngine the trainer
    constructs (they were silently dropped before)."""
    tr = make_trainer(make_clients(4), engine="plan", donate=False,
                      with_metrics=True)
    eng = tr.engine
    assert eng.donate is False
    assert eng.with_metrics is True
    # defaults still resolve (donate=None -> backend-dependent bool)
    tr2 = make_trainer(make_clients(4), engine="plan")
    assert isinstance(tr2.engine.donate, bool)
    assert tr2.engine.with_metrics is False


def test_pow2_chunking():
    assert _pow2_chunks(13, 8) == [8, 4, 1]
    assert _pow2_chunks(32, 32) == [32]
    assert _pow2_chunks(1, 16) == [1]
    assert _pow2_chunks(0, 16) == []


def test_trace_s_cdf_properties():
    clients = make_clients(8, seed=3)
    cdf = trace_s_cdf(clients, 5)
    assert cdf.shape == (8, 6)
    assert np.all(np.diff(cdf, axis=1) >= -1e-6)      # monotone
    np.testing.assert_allclose(cdf[:, -1], 1.0)
    for i, c in enumerate(clients):
        if c.trace.p_inactive == 0:
            assert cdf[i, 0] == 0.0                   # s >= 1 clamp
        else:
            assert cdf[i, 0] >= c.trace.p_inactive - 1e-6


# -- lane-dense row store: per-sample buffers as (slot, sample, F) rows -------

MLP28 = PaperModelConfig(name="mlp28", kind="mlp", input_shape=(28, 28),
                         n_classes=10, hidden=16)


def old_gather(stacks, idx):
    """The gather before the row store: vmapped take over (capacity, Nmax)
    + spec.shape stacks."""
    take = jax.vmap(lambda d, i: jnp.take(d, i, axis=0))
    return {name: take(buf, idx) for name, buf in stacks.items()}


def old_stacks(task, clients, capacity, nmax):
    """(capacity, Nmax) + spec.shape host stacks, empty slots zero."""
    stacks = {name: np.zeros((capacity, nmax) + spec.shape, spec.dtype)
              for name, spec in task.buffers.items()}
    for i, c in enumerate(clients):
        for name, arr in task.client_arrays(c).items():
            stacks[name][i, :c.n] = arr
    return stacks


class OneBuffer(ClientTask):
    """A task with one per-sample buffer of any shape (gather tests)."""

    def __init__(self, shape, dtype):
        self.buffers = {"v": BufferSpec(tuple(shape), dtype)}

    def client_arrays(self, client):
        return {"v": client.x}


def mlp28_clients(sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [Client(x=rng.standard_normal((n, 28, 28)).astype(np.float32),
                   y=rng.integers(0, 10, n).astype(np.int32),
                   trace=TRACES[i % 8]) for i, n in enumerate(sizes)]


@pytest.mark.parametrize("shape, dtype", [
    ((28, 28), np.float32), ((784,), np.float32), ((), np.int32),
    ((9,), np.int32), ((9, 2), np.int32)],
    ids=["28x28", "784", "scalar", "tokens", "codebooks"])
def test_row_store_gather_matches_old_gather(shape, dtype):
    """The row store's gather returns exactly the samples of the old
    vmap(take) over (capacity, Nmax) + shape stacks: Nmax 13 (not a
    multiple of 8), two empty slots (n = 1) gathered at index 0."""
    rng = np.random.default_rng(3)
    sizes = [13, 5, 9, 1]
    clients = [Client(x=(rng.standard_normal((n,) + shape) * 50).astype(
        dtype), trace=TRACES[i]) for i, n in enumerate(sizes)]
    task = OneBuffer(shape, dtype)
    eng = RoundEngine(task=task, clients=clients, local_epochs=3,
                      batch_size=4, capacity=6, max_samples=13)
    row_shape = (int(np.prod(shape)),) if len(shape) >= 2 else shape
    assert eng.data["v"].shape == (6, 13) + row_shape
    _, idx = device_sample_round(jax.random.PRNGKey(5),
                                 jnp.ones(6, jnp.float32), eng.n,
                                 eng.s_cdf, 3, 4)
    got = np.asarray(gather_rows(eng.data, idx)["v"])
    want = np.asarray(old_gather(old_stacks(task, clients, 6, 13), idx)["v"])
    assert got.shape == (6, 3, 4) + row_shape
    np.testing.assert_array_equal(got.reshape(want.shape), want)
    assert (np.asarray(idx)[4:] == 0).all()      # the empty slots


def _reference_span(mode, loss_fn, params, stacks, eng, key, p, R):
    """R rounds of fed_round_parallel / fed_round_sequential fed with
    batches from the old gather, under the engine's own draws."""
    active = jnp.ones(eng.capacity, jnp.float32)

    def body(w, tau):
        alpha, idx = device_sample_round(jax.random.fold_in(key, tau),
                                         active, eng.n, eng.s_cdf,
                                         eng.E, eng.B)
        batches = old_gather(stacks, idx)
        coeffs = scheme_coefficients("C", p, jnp.sum(alpha, -1), eng.E)
        eta = jnp.float32(eng.eta0) / jnp.maximum(
            (tau + 1).astype(jnp.float32), 1.0)
        if mode == "client_parallel":
            w, _ = fed_round_parallel(loss_fn, w, batches, alpha, coeffs,
                                      eta, agg="tree", with_metrics=False)
        else:
            w, _ = fed_round_sequential(loss_fn, w, batches, alpha, coeffs,
                                        eta, with_metrics=False)
        return w, None

    return jax.jit(lambda w: jax.lax.scan(
        body, w, jnp.arange(R, dtype=jnp.int32))[0])(params)


@pytest.mark.parametrize("mode", ["client_parallel", "client_sequential"])
def test_row_store_engine_bit_identical_to_old_gather(mode):
    """A 16-round span on a 28x28 MLP federation gives the parameters of
    the round functions fed by the old gather, bit for bit on the CPU;
    the x buffer is the one stored as rows."""
    from repro.obs.telemetry import Telemetry

    clients = mlp28_clients([30, 21, 45, 12])
    loss_fn = make_loss_fn(MLP28)
    tel = Telemetry()
    eng = RoundEngine(loss_fn=loss_fn, clients=clients, local_epochs=3,
                      batch_size=5, scheme="C", eta0=0.1, chunk_size=16,
                      capacity=5, max_samples=45, mode=mode, agg="tree",
                      telemetry=tel)
    assert tel.registry.gauge("engine_lane_dense_buffers").value == 1
    assert eng.data["x"].shape == (5, 45, 784)
    params = init_small(jax.random.PRNGKey(1), MLP28)
    n = np.array([c.n for c in clients] + [0], np.float64)
    p = jnp.asarray(n / n.sum(), jnp.float32)
    key = jax.random.PRNGKey(11)
    got, _ = eng.run_span(params, 0, 16, p=p, active=np.ones(5, np.float32),
                          lr_shift_tau=0, reboot_tau0=np.zeros(5, np.int32),
                          reboot_boost=np.ones(5, np.float32), key=key)
    stacks = jax.device_put(old_stacks(eng.task, clients, 5, 45))
    want = _reference_span(mode, loss_fn, params, stacks, eng, key, p, 16)
    assert_params_close(got, want, rtol=0, atol=0)


def test_lane_dense_gauge_reads_zero_for_token_rows():
    """LM token streams (S+1,) are rows already: nothing is reshaped."""
    from repro.configs import get_config
    from repro.obs.telemetry import Telemetry

    task = LMTask(get_config("mamba2-130m").reduced(), seq_len=8)
    tel = Telemetry()
    rng = np.random.default_rng(0)
    clients = [Client(x=task.token_stream(rng, n=3, domain=d),
                      trace=TRACES[d]) for d in range(2)]
    eng = RoundEngine(task=task, clients=clients, local_epochs=1,
                      batch_size=2, telemetry=tel)
    assert tel.registry.gauge("engine_lane_dense_buffers").value == 0
    assert eng.data["tokens"].shape == (2, 3, 9)


@pytest.mark.parametrize("path", ["admit", "admit_many", "commit_burst",
                                  "stager"])
def test_admit_paths_write_rows_without_retrace(path):
    """Every way a client reaches a slot lands its (Nmax, 28, 28) host
    rows in the row store: the next round gathers client.x[idx] as
    784-wide rows, and no chunk function retraces."""
    from repro.fed.bank import CohortStager, pad_rows

    founding = mlp28_clients([20, 14])
    fresh = mlp28_clients([17, 9, 20], seed=9)
    eng = RoundEngine(loss_fn=make_loss_fn(MLP28), clients=founding,
                      local_epochs=2, batch_size=4, chunk_size=2,
                      capacity=5, max_samples=20, agg="tree")
    params = init_small(jax.random.PRNGKey(0), MLP28)
    span = dict(p=np.full(5, 0.2, np.float32),
                active=np.ones(5, np.float32), lr_shift_tau=0,
                reboot_tau0=np.zeros(5, np.int32),
                reboot_boost=np.ones(5, np.float32))
    key = jax.random.PRNGKey(2)
    params, _ = eng.run_span(params, 0, 2, key=key, **span)
    traces = eng.trace_count
    slots = [2, 3, 4]
    if path == "admit":
        for s, c in zip(slots, fresh):
            eng.admit(s, c)
    elif path == "admit_many":
        eng.admit_many(list(zip(slots, fresh)))
    elif path == "commit_burst":
        # staged in reverse order, committed through a reordering idx
        staged = [pad_rows(eng.task, eng.nmax, c) for c in fresh[::-1]]
        assert staged[0]["x"].shape == (20, 28, 28)
        dev = eng.put_burst({name: np.stack([r[name] for r in staged]
                                            + [staged[-1][name]])
                             for name in eng.task.buffers})
        eng.commit_burst(dev, slots=slots, ns=[c.n for c in fresh],
                         cdfs=[trace_s_cdf([c], eng.E)[0] for c in fresh],
                         idx=[2, 1, 0])
    else:
        stager = CohortStager(eng)
        stager.submit([(None, c) for c in fresh])
        cohort = stager.collect()
        stager.close()
        assert cohort is not None and cohort.k == 3
        eng.commit_burst(cohort.dev, slots=slots, ns=[c.n for c in fresh],
                         cdfs=[trace_s_cdf([c], eng.E)[0] for c in fresh],
                         idx=[cohort.index[id(c)] for c in fresh])
    assert eng.data["x"].shape == (5, 20, 784)
    _, idx = device_sample_round(jax.random.fold_in(key, 2),
                                 jnp.ones(5, jnp.float32), eng.n, eng.s_cdf,
                                 eng.E, eng.B)
    rows = np.asarray(gather_rows(eng.data, idx)["x"])
    idx = np.asarray(idx)
    for s, c in zip(slots, fresh):
        np.testing.assert_array_equal(rows[s].reshape(-1, 784),
                                      c.x[idx[s].ravel()].reshape(-1, 784))
    params, _ = eng.run_span(params, 2, 2, key=key, **span)
    assert eng.trace_count == traces
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(params))
