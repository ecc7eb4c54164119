"""Readings behind the limits of a training cell's check.

  python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--variants control,half_batch]

For each seed, in one process: the program's first checked steps
(set-up only, no window) against the plain reference (the lower
readings), then each variant of the reference put in the program's
place against the same reference (the upper readings): ``control``, the
reference computed one step down in precision; ``half_batch``, the
reference whose every step takes the mean over half its batch.  A state
left unchanged reads 1 on both gaps by definition and needs no run.
One JSON line per (seed, side) on standard output.  Runs only on the
chip; the benchmark's own runs never call it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="control,half_batch")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)

    import jax

    if jax.default_backend() != "tpu":
        print("calibrate: runs only on the chip", file=sys.stderr)
        return 2
    from bench.lib import harness

    harness.compile_cache()
    from bench.lib.fedref import DOWN, Policy

    man = harness.manifest(ROOT)
    counter = harness.CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        c = harness.Cell(man, args.workload, seed)
        t0 = time.perf_counter()
        prog, snaps = harness.first_steps(c, counter)
        prog_s = c.driver.s_rows(prog, 0, harness.STEPS_CHECKED * c.R)
        c.driver.close(prog)
        del prog
        gc.collect()
        ref = c.reference(Policy())
        rows = [("program", harness.check(c, snaps, prog_s, ref))]
        stated = c.cfg["model"]["dtype"]
        for v in filter(None, args.variants.split(",")):
            pol = (Policy(lower=DOWN[stated]) if v == "control"
                   else Policy(fault=v))
            v_snaps, v_s = c.reference(pol)
            rows.append((v, harness.check(c, v_snaps, v_s, ref)))
        for side, got in rows:
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "side": side, "seconds":
                              time.perf_counter() - t0, **got}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
