"""The benchmark: run one cell of BENCHMARK.json on the chips of this
machine and print its result as the last line of standard output.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With --trace 0 the result carries the cell's end-to-end metrics, with
--trace 1 its per-layer metrics read from a profile of a shorter window.
The numbers the check compares are printed beside their limits as the
last lines of standard error and under "checks", the last key of the
result.  Without a TPU, or with fewer chips than the cell asks for, or
without the program beside the benchmark, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail("the program (src/repro) is not beside the benchmark")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)

    from bench.lib import harness

    man = harness.manifest(ROOT)
    try:
        cell = harness.cell_of(man, args.workload)
    except KeyError as e:
        return fail(str(e))

    import jax

    if jax.default_backend() != "tpu":
        return fail(f"no TPU (jax backend is {jax.default_backend()!r}); "
                    f"the benchmark runs only on the chip")
    if len(jax.devices()) < int(cell["chips"]):
        return fail(f"{args.workload} needs {cell['chips']} chips, "
                    f"found {len(jax.devices())}")
    cache = harness.compile_cache()
    print(f"compile_cache={cache}", file=sys.stderr, flush=True)

    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START, man=man)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
