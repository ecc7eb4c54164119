"""Bring-up smoke: the federated round engine on a TPU, through the entry
points a user calls.

  python chip_smoke.py             # one chip: the `paper` and `lm` phases
  python chip_smoke.py --chips 4   # the 4-chip host: the `sharded` phase

Phases:

  paper    The paper's MNIST experiment (§5.1) at its published width:
           MNIST_MLP (784-200-200-10, 199,210 parameters) on 100
           label-sorted non-IID clients of seeded pseudo-images with
           Table-2 traces; scheme C, E=5, B=10, 16 rounds in two
           FederatedTrainer(engine="device").run() spans, for the `none`
           and `int8` wires.  The engine must pick agg="flat" with
           compiled (not interpreted) kernels; 100 slots take the
           K-tiled kernel grid.  Checks, per wire:
             kernel  one aggregation call at (K=100, D=199,210) against
                     the jnp reference in kernels/ref.py; on the `none`
                     wire also one per parameter leaf (K=100, each leaf
                     where it lies) and at mamba2-130m's awkward leaves
                     and ragged row tiles and K slabs;
             parity  the trained params against a plain reference: the
                     same seeds with agg="tree" (per-leaf jnp).  Both
                     sides run with every XLA matmul at "highest"
                     precision, because XLA's default f32 matmul on a
                     TPU is one bf16 pass; the difference is then the
                     aggregation path's alone;
             loss    the run at default precision, as users run it: loss
                     finite and lower after round 16 than at round 0.
  lm       mamba2-130m at its published widths (d_model 768, 24 layers,
           d_state 128, vocab 50,280) federated for 4 rounds through
           repro.launch.fed_train in the config's client_parallel mode;
           the probe loss must be finite.
  sharded  (--chips 4) the client axis over make_fed_sharding(4) against
           one device on the same seed, for the `none` and `int8` wires:
           both kernel layouts through the psum epilogue, host-planned
           parity with slots padded to whole shards, device-sampled
           parity through arrival/departure churn, and zero recompiles
           across a second round of churn.

Each phase prints what it ran; the lines before the last are smoke
output, not benchmark numbers.  The last line of stdout is one JSON object
naming the device, printed only when every phase passed.  Without a TPU
the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

PARITY_BOUND = 1e-3   # max |param diff|, flat kernel path vs jnp reference
KERNEL_BOUND = 1e-4   # max |out diff|, one kernel call vs jnp reference
PAPER_ROUNDS = 16
PAPER_CLIENTS = 100
PAPER_ETA0 = 0.05     # the image-task rate of benchmarks/paper_tables.py
WIRES = ("none", "int8")


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def peak_bytes():
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def max_diff(a, b) -> float:
    import jax
    import numpy as np
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# -- paper ------------------------------------------------------------------
def paper_clients(n_clients: int, seed: int = 0):
    """§5.1 federation: label-sorted non-IID shards with Pareto sample
    counts, one Table-2 trace per device."""
    import numpy as np

    from repro.core.participation import TRACES
    from repro.data import label_sorted_partition, make_class_dataset
    from repro.fed import Client

    x, y = make_class_dataset(10, 600, seed=seed)
    train, test = label_sorted_partition(x, y, n_clients, seed=seed)
    rng = np.random.default_rng(seed)
    return [Client(x=tr[0], y=tr[1], trace=TRACES[rng.integers(0, len(TRACES))],
                   x_test=te[0], y_test=te[1])
            for tr, te in zip(train, test)]


def paper_run(clients, *, wire: str, agg: str, highest: bool,
              rounds: int = PAPER_ROUNDS, seed: int = 0):
    """One trainer through `rounds` rounds in two run() spans; returns
    (trainer, [span seconds])."""
    import jax

    from repro.configs.paper import MNIST_MLP
    from repro.fed import FederatedTrainer
    from repro.models.small import init_small, make_loss_fn

    ctx = (jax.default_matmul_precision("highest") if highest
           else contextlib.nullcontext())
    with ctx:
        tr = FederatedTrainer(
            loss_fn=make_loss_fn(MNIST_MLP),
            init_params=init_small(jax.random.PRNGKey(seed), MNIST_MLP),
            clients=clients, local_epochs=5, batch_size=10, scheme="C",
            eta0=PAPER_ETA0, engine="device", agg=agg, compression=wire,
            seed=seed)
        spans = []
        half = rounds // 2
        for n in (half, rounds - half):
            t0 = time.perf_counter()
            # eval rounds split spans; evaluating at 0 and `half` gives
            # both run() calls the same scan lengths, so the second
            # reuses the first's compiled programs
            tr.run(n, eval_every=half)
            jax.block_until_ready(tr.params)
            spans.append(time.perf_counter() - t0)
    return tr, spans


def kernel_parity(wire: str, K: int, shape: tuple, **tiles) -> float:
    """One aggregation kernel call over a (K, *shape) operand against
    kernels/ref.py; ``tiles`` forces ragged tiles (block, k_block)."""
    import jax

    from repro.core.compression import quantize_chunked
    from repro.kernels import ref
    from repro.kernels.weighted_agg import weighted_agg

    kc, kd = jax.random.split(jax.random.PRNGKey(7))
    coeffs = jax.random.uniform(kc, (K,))
    deltas = 0.01 * jax.random.normal(kd, (K,) + tuple(shape))
    args, kw = (deltas,), {}
    if wire == "int8":
        args, kw = quantize_chunked(deltas, chunk=256), {"chunk": 256}
    with jax.default_matmul_precision("highest"):
        got = weighted_agg(coeffs, *args, **kw, **tiles)
        want = ref.weighted_agg_ref(coeffs, *args, **kw)
    return max_diff(got, want)


# leaves the f32 wire reduces where they lie, beside the paper MLP's
# own: mamba2-130m's lane-sparse and second-minor-4 leaves, a ragged
# last row tile and a ragged last K slab
LEAF_CASES = ((4, (24, 768, 24), {}), (4, (24, 4, 1792), {}),
              (5, (20, 130), {"block": 8}), (7, (16, 300), {"k_block": 3}))


def paper_phase(n_clients: int = PAPER_CLIENTS,
                rounds: int = PAPER_ROUNDS) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.paper import MNIST_MLP
    from repro.kernels.weighted_agg import resolve_interpret
    from repro.models.small import init_small, make_loss_fn

    clients = paper_clients(n_clients)
    x_eval = jnp.asarray(np.concatenate([c.x_test for c in clients]))
    y_eval = jnp.asarray(np.concatenate([c.y_test for c in clients]))
    loss_fn = jax.jit(make_loss_fn(MNIST_MLP))

    def eval_loss(params):
        with jax.default_matmul_precision("highest"):
            return float(loss_fn(params, {"x": x_eval, "y": y_eval}))

    for wire in WIRES:
        d_total = None
        runs = {}
        for name, agg, highest in (("system", "auto", False),
                                   ("system_highest", "auto", True),
                                   ("reference", "tree", True)):
            tr, spans = paper_run(clients, wire=wire, agg=agg,
                                  highest=highest, rounds=rounds)
            runs[name] = tr
            if name == "system":
                eng = tr.engine
                interp = resolve_interpret(None)
                d_total = sum(x.size for x in jax.tree.leaves(tr.params))
                log(f"paper[{wire}] agg={eng.agg} interpret={interp} "
                    f"slots={eng.capacity} params={d_total:,}")
                check(eng.agg == "flat" and interp is False,
                      f"engine chose agg={eng.agg} interpret={interp}; "
                      f"the chip path is flat + compiled")
            log(f"paper[{wire}] {name}: span1_s={spans[0]:.3f} (compile "
                f"+ {rounds // 2} rounds) span2_s={spans[1]:.3f} "
                f"({rounds - rounds // 2} rounds, warm) "
                f"compile_s~{spans[0] - spans[1]:.3f}")
        cases = [(n_clients, (d_total,), {})]
        if wire == "none":
            cases += [(n_clients, x.shape, {})
                      for x in jax.tree.leaves(tr.params)] + list(LEAF_CASES)
        for K, shape, tiles in cases:
            k_err = kernel_parity(wire, K, shape, **tiles)
            log(f"paper[{wire}] kernel K={K} shape={shape} {tiles} "
                f"max_abs_err={k_err:.3e} (bound {KERNEL_BOUND:g})")
            check(k_err <= KERNEL_BOUND,
                  f"{wire} kernel {shape} differs by {k_err}")
        par = max_diff(runs["system_highest"].params,
                       runs["reference"].params)
        drift = max_diff(runs["system"].params, runs["reference"].params)
        log(f"paper[{wire}] parity max_abs_param_diff={par:.3e} "
            f"(bound {PARITY_BOUND:g}); default-precision run vs "
            f"reference {drift:.3e} (not bounded)")
        check(par <= PARITY_BOUND, f"{wire} parity {par} > {PARITY_BOUND}")
        l0 = eval_loss(init_small(jax.random.PRNGKey(0), MNIST_MLP))
        l1 = eval_loss(runs["system"].params)
        log(f"paper[{wire}] loss round0={l0:.4f} round{rounds}={l1:.4f}")
        check(np.isfinite(l1) and l1 < l0,
              f"{wire} loss did not fall: {l0} -> {l1}")
    log(f"paper peak_bytes_in_use={peak_bytes()}")


# -- lm ---------------------------------------------------------------------
# A few clients at a seq and batch that fit one 16 GB chip: the compile
# for a described v5e puts one such round at ~5.5 GB of temporaries, and
# capacity = clients keeps the f32 client deltas at 4 rows.
LM_ARGV = ("--arch", "mamba2-130m", "--full", "--rounds", "4",
           "--clients", "4", "--capacity", "4", "--samples", "8",
           "--seq", "1024", "--batch", "4", "--local-epochs", "2",
           "--eval-every", "2")


def lm_phase(argv=LM_ARGV) -> None:
    import math

    from repro.launch import fed_train

    res = fed_train.main(list(argv))
    log(f"lm arch={res['arch']} mode={res['mode']} params={res['params']:,} "
        f"wire={res['compression']} capacity={res['capacity']} "
        f"wall_s={res['wall_s']} (compile + {res['rounds']} rounds) "
        f"probe_loss={res['final_loss']:.4f} "
        f"peak_bytes_in_use={peak_bytes()}")
    check(math.isfinite(res["final_loss"]),
          f"non-finite probe loss {res['final_loss']}")


# -- sharded (--chips 4) ----------------------------------------------------
N_SHARDS = 4


def sharded_kernels(fs, K: int, D: int) -> None:
    """Both kernel layouts (single-block K and k_block slabs of the local
    K) through the psum epilogue against one single-device call."""
    import jax

    from repro.core.compression import quantize_chunked
    from repro.kernels.weighted_agg import weighted_agg, weighted_agg_sharded

    kc, kd = jax.random.split(jax.random.PRNGKey(7))
    coeffs = jax.random.uniform(kc, (K,))
    deltas = 0.01 * jax.random.normal(kd, (K, D))
    for wire in WIRES:
        args, kw = (deltas,), {}
        if wire == "int8":
            args, kw = quantize_chunked(deltas, chunk=256), {"chunk": 256}
        want = weighted_agg(coeffs, *args, **kw)
        for kb in (None, 8):
            got = weighted_agg_sharded(coeffs, *args, mesh=fs.mesh,
                                       k_block=kb, **kw)
            err = max_diff(got, want)
            log(f"sharded[{wire}] kernel psum K={K} D={D} k_block={kb} "
                f"max_abs_err={err:.3e} (bound {KERNEL_BOUND:g})")
            check(err <= KERNEL_BOUND, f"{wire} psum kernel differs: {err}")


def sharded_scheduler(sharding, wire: str, n_clients: int, *,
                      mode: str = "device", capacity: int | None = None):
    """Scheduler over the paper federation with one arrival and one
    departure.  In device mode equal capacity on both sides keeps the
    on-device draws identical, so only the mesh layout differs; in plan
    mode the host draws them, and the mesh pads the slots to whole
    shards."""
    import jax

    from repro.configs.paper import MNIST_MLP
    from repro.fed import Arrival, Departure, StreamScheduler
    from repro.models.small import init_small, make_loss_fn

    clients = paper_clients(n_clients)
    return StreamScheduler(
        clients=clients[:-4], init_params=init_small(
            jax.random.PRNGKey(0), MNIST_MLP),
        loss_fn=make_loss_fn(MNIST_MLP), capacity=capacity or n_clients,
        max_samples=max(c.n for c in clients), local_epochs=5,
        batch_size=10, scheme="C", eta0=PAPER_ETA0, seed=0, mode=mode,
        agg="flat", compression=wire, sharding=sharding, chunk_size=2,
        events=[Arrival(3, client=clients[-4]),
                Departure(6, client_id=2, policy="exclude")])


def plan_parity(fs, wire: str, n_clients: int) -> None:
    """Host-planned rounds, round for round: the mesh pads n_clients - 2
    slots to whole shards, the padded slots never train, and the params
    match the single-device run."""
    import numpy as np

    cap = n_clients - 2
    single = sharded_scheduler(None, wire, n_clients, mode="plan",
                               capacity=cap)
    shard = sharded_scheduler(fs, wire, n_clients, mode="plan",
                              capacity=cap)
    for sch in (single, shard):
        sch.run(8, eval_every=4)
    same_s = all(np.array_equal(a.s, b.s[:len(a.s)])
                 and not b.s[len(a.s):].any() and a.event == b.event
                 for a, b in zip(single.history, shard.history))
    err = max_diff(single.params, shard.params)
    log(f"sharded[{wire}] plan slots={single.engine.capacity}->"
        f"{shard.engine.capacity} rounds={len(shard.history)} "
        f"s_streams_identical={same_s} max_abs_param_diff={err:.3e} "
        f"(bound {PARITY_BOUND:g})")
    check(shard.engine.capacity % N_SHARDS == 0
          and shard.engine.capacity > cap, "mesh did not pad the slots")
    check(same_s, f"{wire}: planned s streams differ across layouts")
    check(err <= PARITY_BOUND, f"{wire}: plan parity {err}")


def sharded_phase(n_clients: int = PAPER_CLIENTS) -> None:
    import jax
    import numpy as np

    from repro.core.participation import TRACES
    from repro.fed import Arrival, Departure, TraceShift, make_fed_sharding

    check(len(jax.devices()) >= N_SHARDS,
          f"needs {N_SHARDS} devices, found {len(jax.devices())}")
    fs = make_fed_sharding(N_SHARDS)
    sharded_kernels(fs, 128, 199_210)
    for wire in WIRES:
        plan_parity(fs, wire, n_clients)
        single = sharded_scheduler(None, wire, n_clients)
        shard = sharded_scheduler(fs, wire, n_clients)
        # chunk_size=2 bounds the compiled scan lengths to {1, 2}; this
        # first stretch (events at tau 3 and 6) compiles both
        for sch in (single, shard):
            t0 = time.perf_counter()
            sch.run(10, eval_every=5)
            jax.block_until_ready(sch.params)
            log(f"sharded[{wire}] {'single' if sch is single else 'mesh'}"
                f" span_s={time.perf_counter() - t0:.3f} (compile + 10 "
                f"rounds) slots={sch.engine.capacity}")
        eng = shard.engine
        sizes = {k: f._cache_size() for k, f in eng._fns.items()}
        traces = eng.trace_count
        for sch in (single, shard):
            fresh = paper_clients(n_clients)
            sch.push(Arrival(12, client=fresh[-3]),
                     TraceShift(13, client_id=0, trace=TRACES[3]),
                     Departure(15, client_id=1, policy="exclude"))
            sch.run(10, eval_every=5)
        recompiles = (eng.trace_count - traces
                      + sum(f._cache_size() - sizes.get(k, 0)
                            for k, f in eng._fns.items()))
        same_s = all(np.array_equal(a.s, b.s)
                     for a, b in zip(single.history, shard.history))
        err = max_diff(single.params, shard.params)
        log(f"sharded[{wire}] rounds={len(shard.history)} "
            f"events={shard.events_applied} s_streams_identical={same_s} "
            f"max_abs_param_diff={err:.3e} (bound {PARITY_BOUND:g}) "
            f"recompiles_across_churn={recompiles}")
        check(same_s, f"{wire}: sampled s streams differ across layouts")
        check(err <= PARITY_BOUND, f"{wire}: sharded parity {err}")
        check(recompiles == 0, f"{wire}: {recompiles} recompiles in churn")
    log(f"sharded peak_bytes_in_use={peak_bytes()}")


# -- main -------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: paper + lm phases on one chip; 4: only the "
                         "sharded-vs-single phase on the 4-chip host")
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU found (jax backend is {backend!r}); "
              f"this smoke runs only on the chip", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    log(f"compile_cache={enable_compile_cache()}")
    devices = jax.devices()
    dev = devices[0]
    log(f"device_kind={dev.device_kind} platform={dev.platform} "
        f"count={len(devices)}")

    phases = ([("sharded", sharded_phase)] if args.chips == 4
              else [("paper", paper_phase), ("lm", lm_phase)])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
            log(f"phase {name}: pass ({time.perf_counter() - t0:.1f} s)")
        except Exception:
            traceback.print_exc()
            log(f"phase {name}: FAIL ({time.perf_counter() - t0:.1f} s)")
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
