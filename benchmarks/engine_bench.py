"""End-to-end rounds/sec benchmark: seed host loop vs device-resident engine.

Measures FederatedTrainer.run throughput on the paper's small-model config
(SYNTHETIC logreg, E=5, B=20) in four configurations:

  seed_host   the seed per-round host loop with the seed's original
              take_along_axis loss formulation (faithful baseline),
  host        the same host loop with the current (one-hot) loss,
  engine_plan host-RNG sampling, device-resident chunked rounds,
  engine      fully fused on-device sampling + pytree-flat Pallas
              aggregation (the fast path).

Timing is best-of-k over repeated spans (the CI box is a shared 2-core
container; mean timings are dominated by scheduler noise).  Emits
BENCH_engine.json with rounds/sec per mode, the engine speedup over the
seed loop, the host-overhead fraction of the seed loop (instrumented
round_fn device time vs wall), and the weighted_agg single-launch µs.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper import SYNTHETIC_LR
from repro.core.participation import TRACES
from repro.data import synthetic_federation
from repro.fed import Client, FederatedTrainer
from repro.models.small import init_small, logits_small, make_loss_fn

CFG = SYNTHETIC_LR


def _seed_loss_fn(cfg):
    """The seed's loss formulation (take_along_axis NLL), kept here so the
    benchmark baseline stays faithful to the seed host loop even after the
    repo's loss moved to the one-hot form."""
    def loss_fn(params, batch):
        x, y = batch["x"], batch["y"]
        lg = logits_small(params, cfg, x)
        ll = jax.nn.log_softmax(lg)
        return -jnp.mean(jnp.take_along_axis(
            ll, y[:, None].astype(jnp.int32), axis=1))
    return loss_fn


def _null_eval(params, x, y):
    return 0.0, 0.0


def _make_trainer(engine, *, loss_fn, n_clients, seed=0, chunk=32,
                  agg="auto", compression=None):
    train, test = synthetic_federation(0.5, 0.5, n_clients, seed=seed)
    rng = np.random.default_rng(seed)
    clients = [Client(x=tr[0], y=tr[1], trace=TRACES[rng.integers(0, 5)],
                      x_test=te[0], y_test=te[1])
               for tr, te in zip(train, test)]
    return FederatedTrainer(
        loss_fn=loss_fn, eval_fn=_null_eval,
        init_params=init_small(jax.random.PRNGKey(0), CFG),
        clients=clients, local_epochs=5, batch_size=20, scheme="C",
        eta0=1.0, seed=seed, engine=engine, chunk_size=chunk, agg=agg,
        compression=compression)


def _rounds_per_sec(tr, span, reps):
    tr.run(2 * span, eval_every=10 ** 9)          # warmup + compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        tr.run(span, eval_every=10 ** 9)
        best = min(best, time.perf_counter() - t0)
    return span / best


def _host_overhead_fraction(tr, span):
    """Instrument the legacy loop: fraction of wall time NOT spent inside
    the jitted round step (sampling, batch build, transfers, coeff sync)."""
    tr.run(4, eval_every=10 ** 9)   # warmup: keep compile out of the split
    orig = tr.round_fn
    dev = [0.0]

    def timed(*a, **k):
        t0 = time.perf_counter()
        out = orig(*a, **k)
        jax.block_until_ready(out)
        dev[0] += time.perf_counter() - t0
        return out

    tr.round_fn = timed
    t0 = time.perf_counter()
    tr.run(span, eval_every=10 ** 9)
    total = time.perf_counter() - t0
    tr.round_fn = orig
    return max(0.0, 1.0 - dev[0] / total)


def _time(fn, *args, reps=5):
    """Mean wall microseconds of fn(*args) after one warm-up call."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def _agg_us(n_clients):
    """Single-launch weighted_agg time at the benchmark model size."""
    from repro.kernels.weighted_agg import weighted_agg
    params = init_small(jax.random.PRNGKey(0), CFG)
    D = sum(p.size for p in jax.tree.leaves(params))
    key = jax.random.PRNGKey(0)
    c = jax.random.uniform(key, (n_clients,))
    d = jax.random.normal(key, (n_clients, D), jnp.float32)
    return _time(lambda: weighted_agg(c, d, block=1024)), D


def run(span=32, reps=7, n_clients=12, chunk=32):
    seed_loss = _seed_loss_fn(CFG)
    cur_loss = make_loss_fn(CFG)

    rps = {}
    rps["seed_host"] = _rounds_per_sec(
        _make_trainer("host", loss_fn=seed_loss, n_clients=n_clients),
        span, reps)
    rps["host"] = _rounds_per_sec(
        _make_trainer("host", loss_fn=cur_loss, n_clients=n_clients),
        span, reps)
    rps["engine_plan"] = _rounds_per_sec(
        _make_trainer("plan", loss_fn=cur_loss, n_clients=n_clients,
                      chunk=chunk), span, reps)
    rps["engine"] = _rounds_per_sec(
        _make_trainer("device", loss_fn=cur_loss, n_clients=n_clients,
                      chunk=chunk), span, reps)
    # the fused Pallas aggregation layout, explicitly (on CPU this runs the
    # interpreter, so agg="auto" prefers the jnp tree; on TPU they coincide)
    rps["engine_flat_agg"] = _rounds_per_sec(
        _make_trainer("device", loss_fn=cur_loss, n_clients=n_clients,
                      chunk=chunk, agg="flat"), span, reps)

    overhead = _host_overhead_fraction(
        _make_trainer("host", loss_fn=seed_loss, n_clients=n_clients),
        span)
    agg_us, D = _agg_us(n_clients)

    out = {
        "config": {"dataset": "synthetic", "model": "logreg",
                   "n_clients": n_clients, "local_epochs": 5,
                   "batch_size": 20, "scheme": "C", "span": span,
                   "reps": reps, "chunk_size": chunk, "d_total": D,
                   "backend": jax.default_backend()},
        "rounds_per_sec": {k: round(v, 2) for k, v in rps.items()},
        "speedup_engine_vs_seed": round(rps["engine"] / rps["seed_host"], 3),
        "speedup_plan_vs_seed": round(
            rps["engine_plan"] / rps["seed_host"], 3),
        "host_overhead_fraction_seed_loop": round(overhead, 4),
        "weighted_agg_single_launch_us": round(agg_us, 1),
    }
    return out


def compression_run(span=32, reps=7, n_clients=12, chunk=32):
    """Compressed-delta aggregation series: wire bytes moved per round for
    each payload format (analytic, from the format's exact layout) and
    quantized-vs-f32 rounds/sec through the same device engine."""
    from repro.core.compression import resolve_compression, wire_bytes

    cur_loss = make_loss_fn(CFG)
    params = init_small(jax.random.PRNGKey(0), CFG)
    D = sum(p.size for p in jax.tree.leaves(params))

    kinds = ["none", "bf16", "int8", "int8-topk"]
    bytes_per_round = {
        k: wire_bytes(D, resolve_compression(k), n_clients=n_clients)
        for k in kinds}

    rps = {}
    for label, comp in [("f32", None), ("bf16", "bf16"), ("int8", "int8")]:
        rps[label] = _rounds_per_sec(
            _make_trainer("device", loss_fn=cur_loss, n_clients=n_clients,
                          chunk=chunk, compression=comp), span, reps)

    out = {
        "config": {"dataset": "synthetic", "model": "logreg",
                   "n_clients": n_clients, "span": span, "reps": reps,
                   "chunk_size": chunk, "d_total": D,
                   "quant_chunk": resolve_compression("int8").chunk,
                   "backend": jax.default_backend()},
        "bytes_per_round": bytes_per_round,
        "bytes_reduction_vs_f32": {
            k: round(bytes_per_round["none"] / bytes_per_round[k], 2)
            for k in kinds if k != "none"},
        "rounds_per_sec": {k: round(v, 2) for k, v in rps.items()},
        "slowdown_int8_vs_f32": round(
            max(0.0, 1.0 - rps["int8"] / rps["f32"]), 4),
    }
    return out


def compression_main(path="BENCH_engine.json", **kw):
    """Merge the compression series into BENCH_engine.json under the
    "compression" key (same merge pattern as sharded_bench)."""
    import os
    res = compression_run(**kw)
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data["compression"] = res
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")
    return res


def main(path="BENCH_engine.json", **kw):
    out = run(**kw)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    return out


if __name__ == "__main__":
    print(json.dumps(main(), indent=2))
