"""Run one cell of BENCHMARK.json: set-up, measured window, the check.

Everything a cell needs is found by name: the configuration
``bench/configs/<config>.json`` with its family's plain reference
``bench/configs/<family>_ref.py`` and its program adapter
``bench/families/<family>.py``; the traffic mix ``bench/traffic/<mix>.json``
and the driver of its kind ``bench/drivers/<kind>.py``; each metric's
reader ``bench/metrics/<metric>.py`` (or, for a metric split by a suffix
such as ``mfu.lm``, ``bench/metrics/<mfu>.py``).

A run:

  set-up   weights from the seed on the device (one jitted call), the
           federation's data from the benchmark's own generators, the
           program built over them by the mix's driver; then the first
           three steps, each one call of the window (the first
           compiles), with the parameters read back after each;
  window   more such calls until ``seconds`` have passed, ended at a call
           boundary after block_until_ready; with ``trace`` a profile of
           a shorter window instead, and only per-layer metrics;
  check    once the window has closed, the peak memory read and the
           program's state freed: the plain reference replays the first
           three steps from the same seed, and the numbers compared are
           printed beside their limits.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from types import SimpleNamespace

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
STEPS_CHECKED = 3
TRACE_SECONDS = 5.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace("-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell_of(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_of(man: dict, name: str) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(ROOT, c["file"]))
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def metrics_of(man: dict, cell: str, kind: str) -> list:
    """The cell's end_to_end or per_layer metric entries."""
    out = []
    for m in man[kind]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        if kind == "per_layer" and "workloads" not in m:
            moves = [e for e in man["end_to_end"] if e["name"] == m["moves"]]
            if moves and "workloads" in moves[0] \
                    and cell not in moves[0]["workloads"]:
                continue
        out.append(m)
    return out


def reader_path(name: str) -> str:
    for base in (name, name.split(".")[0]):
        p = os.path.join(BENCH, "metrics", f"{base}.py")
        if os.path.exists(p):
            return p
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"bench/metrics/")


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment says, so that only a cell's first
    run in a checkout compiles and two checkouts share nothing.  Every
    program is cached, however quick to compile."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    path = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def seed_key(seed: int):
    """A PRNG key from any whole seed (more bits than 32 fold in)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    hi = seed >> 32
    return jax.random.fold_in(key, hi) if hi else key


# -- comparison ------------------------------------------------------------------
def leaf_norms(tree) -> dict:
    import jax
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
        np.asarray(v, np.float64).ravel()))
        for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def diff(a, b):
    import jax
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


def norm_gap(prog: dict, ref: dict, counted) -> tuple:
    """Worst leaf of |‖prog‖ - ‖ref‖| / max(‖ref‖, median leaf's ‖ref‖)
    over the counted leaves; returns (gap, leaf)."""
    med = float(np.median([ref[k] for k in counted]))
    worst, leaf = 0.0, ""
    for k in counted:
        g = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if g > worst:
            worst, leaf = g, k
    return worst, leaf


def compare(prog_snaps, ref_snaps, prog_s, ref_s, loss_at,
            grad_norms) -> dict:
    """The numbers a training cell compares: see PERF.md section 2.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's are nought to rounding and are left out."""
    med = float(np.median(list(grad_norms.values())))
    counted = [k for k, v in grad_norms.items() if v >= 1e-3 * med]
    r1 = leaf_norms(diff(ref_snaps[1], ref_snaps[0]))
    p1 = leaf_norms(diff(prog_snaps[1], prog_snaps[0]))
    r3 = leaf_norms(diff(ref_snaps[-1], ref_snaps[0]))
    p3 = leaf_norms(diff(prog_snaps[-1], prog_snaps[0]))
    u1, u1_leaf = norm_gap(p1, r1, counted)
    c3, c3_leaf = norm_gap(p3, r3, counted)
    lg = 0.0
    for k in range(1, len(ref_snaps)):
        lp, lr = loss_at(prog_snaps[k]), loss_at(ref_snaps[k])
        lg = max(lg, abs(lp - lr) / max(abs(lr), 1e-30))
    mism = int(np.sum(np.asarray(prog_s) != np.asarray(ref_s)))
    return {"update1_gap": u1, "change3_gap": c3, "loss_gap": lg,
            "s_mismatch": mism, "_leaves": {"update1_gap": u1_leaf,
                                            "change3_gap": c3_leaf},
            "_excluded": sorted(set(grad_norms) - set(counted))}


# -- the federation ----------------------------------------------------------------
class Cell:
    """Everything one run builds from (cell, seed), program and reference
    alike."""

    def __init__(self, man: dict, workload: str, seed: int,
                 overrides: dict = None):
        from bench.lib import fedref, fleet, traffic

        self.spec = cell_of(man, workload)
        self.cfg = merge(config_of(man, self.spec["config"]), overrides)
        self.mix = traffic.load(self.spec["traffic"])
        self.driver = load_module(traffic.driver_path(self.mix))
        self.seed = seed
        fam = self.cfg["family"]
        self.family = load_module(os.path.join(BENCH, "families",
                                               f"{fam}.py"))
        self.ref = load_module(os.path.join(BENCH, "configs",
                                            f"{fam}_ref.py"))
        t = self.cfg["training"]
        self.E, self.B, self.R = t["local_steps"], t["batch"], \
            t["span_rounds"]
        clients, probe = fleet.make(self.cfg, seed)
        self.host_clients = clients
        self.data, self.n = self.ref.stack(self.cfg, clients)
        self.probe = self.ref.probe_batch(self.cfg, probe)
        self.cdf = np.stack([fedref.cdf_row(fleet.TRACES[c["trace"]],
                                            self.E) for c in clients])
        self.p = (self.n / self.n.sum()).astype(np.float32)

    def weights(self, policy):
        return self.ref.init(seed_key(self.seed), self.cfg, policy)

    def participation_key(self):
        from bench.lib import traffic
        return traffic.participation_key(self.mix, self.seed)

    def program_clients(self) -> list:
        """The federation's clients as the program takes them."""
        from repro.core.participation import Trace
        from repro.fed import Client
        from bench.lib import fleet

        return [Client(x=c["x"], y=c.get("y"),
                       trace=Trace(*fleet.TRACES[c["trace"]]))
                for c in self.host_clients]

    def scheduler_kwargs(self) -> dict:
        """What the program's StreamScheduler takes for this
        configuration, apart from clients, weights, key and events."""
        t = self.cfg["training"]
        return dict(
            capacity=len(self.host_clients), max_samples=self.data_nmax(),
            local_epochs=self.E, batch_size=self.B, scheme=t["scheme"],
            eta0=t["eta0"], chunk_size=t["chunk_size"], agg=t["agg"],
            compression=t["wire"], engine_mode=t["engine_mode"],
            seed=self.seed & 0x7FFFFFFF, mode="device",
            **self.family.program_kwargs(self.cfg))

    def data_nmax(self) -> int:
        return int(next(iter(self.data.values())).shape[1])

    def reference(self, policy, steps: int = STEPS_CHECKED):
        """Parameter snapshots after 0..steps steps and the s streams,
        from the plain reference under ``policy``."""
        import jax
        import jax.numpy as jnp

        from bench.lib import fedref

        t = self.cfg["training"]
        rounds = fedref.make_rounds(
            self.ref.make_loss(self.cfg, policy), self.ref.make_batch,
            E=self.E, B=self.B, eta0=t["eta0"], policy=policy)
        args = jax.device_put((self.data, self.n, self.cdf))
        key = self.participation_key()
        p = jnp.asarray(self.p)
        active = jnp.ones(len(self.n), jnp.float32)
        w = self.weights(policy)
        snaps, ss = [jax.device_get(w)], []
        for k in range(steps):
            w, s = rounds(w, *args, key, p, active,
                          jnp.int32(k * self.R), R=self.R)
            snaps.append(jax.device_get(w))
            ss.append(np.asarray(s))
        del w
        return snaps, np.concatenate(ss)

    def loss_fn(self, grad: bool = False):
        """The reference's loss (or per-leaf gradient norms) on the probe
        batch, in float32 at the highest precision, for any stored
        parameters."""
        import jax

        from bench.lib.fedref import Policy

        loss = self.ref.make_loss(self.cfg, Policy())
        fn = jax.jit(jax.grad(loss) if grad else loss)
        probe = jax.device_put(self.probe)

        def at(params):
            with jax.default_matmul_precision("highest"):
                f = jax.tree.map(lambda a: np.asarray(a, np.float32),
                                 params)
                out = fn(f, probe)
                return leaf_norms(out) if grad else float(out)
        return at


# -- one run -------------------------------------------------------------------------
class CompileCounter:
    """Counts XLA backend compilations (persistent-cache hits excluded)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def first_steps(c: Cell, counter: CompileCounter):
    """Set-up of the program: weights, the program, the first checked
    steps through the window's own call.  Returns (program, parameter
    snapshots after 0..STEPS_CHECKED steps)."""
    import jax

    from bench.lib.fedref import Policy

    stamps = [("start", time.perf_counter())]
    params = c.weights(Policy())
    snaps = [jax.device_get(params)]
    stamps.append(("weights", time.perf_counter()))
    drv = c.driver
    prog = drv.program(c, params)
    del params                      # the first span donates it
    stamps.append(("program", time.perf_counter()))
    for k in range(STEPS_CHECKED):
        with jax.profiler.TraceAnnotation("bench.call"):
            drv.call(c, prog)
        snaps.append(jax.device_get(drv.params(prog)))
        stamps.append((f"step{k + 1}", time.perf_counter()))
    log("set-up " + " ".join(f"{n}={t - stamps[0][1]:.3f}"
                             for n, t in stamps[1:])
        + f" compiles={counter.n} traces={drv.engine(prog).trace_count}")
    return prog, snaps


def check(c: Cell, snaps, prog_s, ref=None) -> dict:
    """The numbers compared for program snapshots ``snaps`` (and its s
    streams) against the plain reference (computed here unless given)."""
    from bench.lib.fedref import Policy

    ref_snaps, ref_s = ref if ref is not None else c.reference(Policy())
    return compare(snaps, ref_snaps, prog_s, ref_s, c.loss_fn(),
                   c.loss_fn(grad=True)(ref_snaps[0]))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, man: dict = None,
             overrides: dict = None) -> dict:
    import jax

    from bench.lib import trace as tr
    from bench.lib.peaks import chip_peaks

    man = man or manifest()
    counter = CompileCounter()
    c = Cell(man, workload, seed, overrides)
    chips = int(c.spec["chips"])
    dev0 = jax.devices()[0]
    peaks = chip_peaks(dev0.device_kind) if dev0.platform == "tpu" else None

    drv = c.driver
    prog, snaps = first_steps(c, counter)
    eng = drv.engine(prog)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s:.3f} agg={eng.agg} slots={eng.capacity}")

    # -- window ---------------------------------------------------------------
    length = min(seconds, TRACE_SECONDS) if trace else seconds
    compiles0, traces0 = counter.n, eng.trace_count
    prof = None
    if trace:
        trace_dir = os.path.join(ROOT, ".bench_trace",
                                 f"{workload}-{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        prof = jax.profiler.TraceAnnotation("bench.window")
        prof.__enter__()
    t0 = time.perf_counter()
    calls = rounds = 0
    while True:
        with jax.profiler.TraceAnnotation("bench.call"):
            rounds += drv.call(c, prog)
        calls += 1
        if time.perf_counter() - t0 >= length:
            break
    with jax.profiler.TraceAnnotation("bench.wait"):
        jax.block_until_ready(drv.params(prog))
    t1 = time.perf_counter()
    if prof is not None:
        prof.__exit__(None, None, None)
        jax.profiler.stop_trace()
    window_s = t1 - t0
    first = STEPS_CHECKED * c.R
    s_win = drv.s_rows(prog, first, first + rounds)
    win_compiles = counter.n - compiles0
    win_traces = eng.trace_count - traces0
    log(f"window_s={window_s:.3f} calls={calls} rounds={rounds} "
        f"window_compiles={win_compiles} window_traces={win_traces}")

    device = device_info(chips)
    prog_s = drv.s_rows(prog, 0, first)
    K, D = eng.capacity, eng._d_total
    extra = drv.stats(c, prog)
    drv.close(prog)
    del prog, eng
    gc.collect()

    run = SimpleNamespace(
        cfg=c.cfg, cell=c.spec, chips=chips, peaks=peaks, setup_s=setup_s,
        window_s=window_s, rounds=rounds,
        applied_steps=float(s_win.sum()), work=c.family.work(c.cfg),
        K=K, D=D, trace=None, trace_lo=None, trace_hi=None, **extra)
    result_extra = {}
    if trace:
        t_ = tr.load(trace_dir)
        lo, hi = tr.window(t_)
        run.trace, run.trace_lo, run.trace_hi = t_, lo, hi
        device["busy_s"] = tr.mean_busy_s(t_, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        result_extra["breakdown"] = tr.breakdown(t_, lo, hi)
        log(f"trace ops={sum(map(len, t_.devices.values()))} "
            f"busy_s={device['busy_s']:.6f} "
            f"window_s={device['window_s']:.6f}")
        shutil.rmtree(trace_dir, ignore_errors=True)

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(man, workload, kind):
        v = load_module(reader_path(m["name"])).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # -- check: the plain reference over the same steps -----------------------
    t_ref = time.perf_counter()
    got = check(c, snaps, prog_s)
    log(f"reference_s={time.perf_counter() - t_ref:.3f} "
        f"worst leaves {got['_leaves']} excluded {got['_excluded']}")
    checks, correct = {}, True
    for name, lim in c.cfg["limits"].items():
        v = got[name]
        correct &= bool(math.isfinite(v) and v <= lim)
        checks[name] = {"value": v, "limit": lim}
    if win_compiles or win_traces:
        log(f"compiled inside the window: {win_compiles} compiles, "
            f"{win_traces} traces")
    for name, ch in checks.items():
        log(f"check {name} {ch['value']!r} limit {ch['limit']!r}")
    out = {"correct": bool(correct), "attempted": run.rounds, "failed": 0,
           "metrics": metrics, "device": device}
    out.update(result_extra)
    out["checks"] = checks
    return out
