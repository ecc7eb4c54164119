"""Where a cell's round spends the device, by the program's own names.

  python bench/profile_scopes.py --workload <cell> --seed <n>

Makes the benchmark's own traced run of the cell (``harness.run_cell``
with the trace on) and reads its profile once more with
``bench/lib/scopes.py``, before the harness deletes it.  Prints one JSON
line: the run's ``correct`` and per-layer metrics, device self time per
round under each ``fed.*`` scope of the round and the unscoped remainder
by op, and device idle per round in gaps inside each program span
(``sched.*``, ``engine.*``, ...).  Runs only on the chip; the benchmark's
own runs never call it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)

    import jax

    if jax.default_backend() != "tpu":
        print("profile_scopes: runs only on the chip", file=sys.stderr)
        return 2
    from bench.lib import harness, scopes
    from bench.lib import trace as tr

    harness.compile_cache()
    read, kept = tr.load, []

    def load(trace_dir):
        # the harness reads its profile here: hand it the same trace with
        # scopes and spans, read by scopes.load over the plain reader
        tr.load = read
        kept.append(scopes.load(trace_dir))
        return kept[-1]

    tr.load = load
    res = harness.run_cell(args.workload, args.seed, harness.TRACE_SECONDS,
                           True, t_start=T_START)
    t = kept[0]
    lo, hi = tr.window(t)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "device": jax.devices()[0].device_kind,
                      "correct": res["correct"], "metrics": res["metrics"],
                      **scopes.report(t, lo, hi, res["attempted"])}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
