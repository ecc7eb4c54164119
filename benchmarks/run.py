"""Benchmark harness: one function per paper table + roofline summary.
Prints ``name,us_per_call,derived`` style CSV blocks.

  PYTHONPATH=src python -m benchmarks.run            # quick set
  PYTHONPATH=src python -m benchmarks.run --full     # full paper tables
"""
from __future__ import annotations

import argparse
import sys
import time


def scenario_smoke(name: str, *, rounds: int = 8, seed: int = 0) -> dict:
    """Tiny end-to-end streaming scenario (the --scenario smoke path):
    replays the named event stream for a handful of rounds so the tier-1
    suite / CI can exercise the subsystem without the full benchmark."""
    from repro.fed.scenarios import make_scenario, run_scenario

    sc = make_scenario(name, seed=seed)
    t0 = time.perf_counter()
    _, summary = run_scenario(sc, mode="device", n_rounds=rounds,
                              eval_every=max(1, rounds // 2))
    summary["wall_s"] = round(time.perf_counter() - t0, 3)
    return summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-size paper tables (slower)")
    ap.add_argument("--skip-tables", action="store_true")
    ap.add_argument("--skip-engine", action="store_true",
                    help="skip the rounds/sec engine benchmark")
    ap.add_argument("--skip-stream", action="store_true",
                    help="skip the streaming-participation benchmark")
    ap.add_argument("--skip-bank", action="store_true",
                    help="skip the client-bank / cohort-prefetch benchmark")
    ap.add_argument("--skip-service", action="store_true",
                    help="skip the concurrent-ingestion service benchmark")
    ap.add_argument("--skip-fuzz", action="store_true",
                    help="skip the invariant-fuzzer + chaos-soak benchmark")
    ap.add_argument("--fuzz-seeds", type=int, default=None, metavar="N",
                    help="fuzz corpus size (default: 48, or 128 with "
                         "--full; the validator/backend/chaos corpora "
                         "scale down from it)")
    ap.add_argument("--skip-telemetry", action="store_true",
                    help="skip the telemetry-overhead benchmark")
    ap.add_argument("--skip-sharded", action="store_true",
                    help="skip the sharded-vs-single engine benchmark")
    ap.add_argument("--skip-compression", action="store_true",
                    help="skip the compressed-delta aggregation benchmark")
    ap.add_argument("--skip-fedmodel", action="store_true",
                    help="skip the transformer-federation benchmark")
    ap.add_argument("--check-docs", action="store_true",
                    help="execute the fenced python snippets in README.md "
                         "and docs/*.md, then exit (CI docs-rot gate)")
    ap.add_argument("--bench-json", default="BENCH_engine.json",
                    help="where to write the machine-readable engine "
                         "benchmark (default: BENCH_engine.json)")
    ap.add_argument("--stream-json", default="BENCH_stream.json",
                    help="where to write the streaming benchmark "
                         "(default: BENCH_stream.json)")
    ap.add_argument("--scenario", default=None, metavar="NAME",
                    help="smoke mode: run only a tiny named streaming "
                         "scenario end-to-end and exit (no benchmarks)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.check_docs:
        from benchmarks.check_docs import main as check_docs_main
        sys.exit(check_docs_main())

    if args.scenario is not None:
        summary = scenario_smoke(args.scenario)
        print("# scenario smoke: key,value")
        for k, v in summary.items():
            if k != "events":
                print(f"{k},{v}")
        return

    if not args.skip_engine:
        from benchmarks.engine_bench import main as engine_main
        span = 64 if args.full else 32
        res = engine_main(args.bench_json, span=span)
        print("\n# engine: mode,rounds_per_sec")
        for mode, rps in res["rounds_per_sec"].items():
            print(f"{mode},{rps}")
        print(f"engine_speedup_vs_seed,{res['speedup_engine_vs_seed']}")
        print(f"host_overhead_fraction_seed_loop,"
              f"{res['host_overhead_fraction_seed_loop']}")
        print(f"weighted_agg_single_launch_us,"
              f"{res['weighted_agg_single_launch_us']}")
        print(f"# wrote {args.bench_json}")
        sys.stdout.flush()

    if not args.skip_compression:
        from benchmarks.engine_bench import compression_main
        res = compression_main(args.bench_json)
        print("\n# compression: kind,bytes_per_round,reduction_vs_f32")
        for kind, nbytes in res["bytes_per_round"].items():
            red = res["bytes_reduction_vs_f32"].get(kind, 1.0)
            print(f"{kind},{nbytes},{red}")
        print("# compression: wire,rounds_per_sec")
        for wire, rps in res["rounds_per_sec"].items():
            print(f"{wire},{rps}")
        print(f"slowdown_int8_vs_f32,{res['slowdown_int8_vs_f32']}")
        print(f"# merged into {args.bench_json}")
        sys.stdout.flush()

    if not args.skip_sharded:
        from benchmarks.sharded_bench import main as sharded_main
        res = sharded_main(args.bench_json)
        print("\n# sharded engine: mode,rounds_per_sec")
        for mode, rps in res["rounds_per_sec"].items():
            print(f"{mode},{rps}")
        print(f"speedup_sharded_vs_single,"
              f"{res['speedup_sharded_vs_single']}")
        print(f"admit_us_sharded,{res['admit_us_sharded']}")
        print(f"# merged into {args.bench_json}")
        sys.stdout.flush()

    if not args.skip_fedmodel:
        from benchmarks.fedmodel_bench import main as fedmodel_main
        res = fedmodel_main(args.bench_json)
        print("\n# fedmodel: mode,rounds_per_sec")
        for mode, rps in res["rounds_per_sec"].items():
            print(f"{mode},{rps}")
        print(f"# merged into {args.bench_json}")
        sys.stdout.flush()

    if not args.skip_stream:
        from benchmarks.stream_bench import main as stream_main
        res = stream_main(args.stream_json)
        print("\n# stream: mode,rounds_per_sec")
        for mode, rps in res["rounds_per_sec"].items():
            print(f"{mode},{rps}")
        print(f"churn_overhead_fraction,{res['churn_overhead_fraction']}")
        print(f"events_per_sec_absorbed,{res['events_per_sec_absorbed']}")
        print(f"admit_us,{res['admit_us']}")
        print(f"evict_us,{res['evict_us']}")
        print(f"# wrote {args.stream_json}")
        sys.stdout.flush()

    if not args.skip_bank:
        from benchmarks.bank_bench import main as bank_main
        res = bank_main(args.stream_json)
        print("\n# bank: metric,value")
        for mode, rps in res["rounds_per_sec"].items():
            print(f"{mode},{rps}")
        print(f"speedup_prefetch_vs_sync,{res['speedup_prefetch_vs_sync']}")
        print(f"staging_overlap_fraction,{res['staging_overlap_fraction']}")
        print("# bank sweep: fleet,hot_slots,rounds_per_sec")
        for row in res["fleet_sweep"]:
            print(f"{row['fleet']},{row['hot_slots']},"
                  f"{row['rounds_per_sec']}")
        print(f"# merged into {args.stream_json}")
        sys.stdout.flush()

    if not args.skip_service:
        from benchmarks.service_bench import main as service_main
        res = service_main(args.stream_json)
        print("\n# service: metric,value")
        for k in ("ingest_events_per_sec", "rounds_per_sec_under_traffic",
                  "rounds_per_sec_blocking", "service_overhead_fraction",
                  "snapshot_ms", "snapshot_to_disk_ms"):
            print(f"{k},{res[k]}")
        print(f"# merged into {args.stream_json}")
        sys.stdout.flush()

    if not args.skip_telemetry:
        from benchmarks.telemetry_bench import main as telemetry_main
        res = telemetry_main(args.stream_json)
        print("\n# telemetry: metric,value")
        for k in ("rounds_per_sec_disabled", "rounds_per_sec_enabled",
                  "rounds_overhead_fraction", "events_per_sec_disabled",
                  "events_per_sec_enabled", "events_overhead_fraction"):
            print(f"{k},{res[k]}")
        print(f"# merged into {args.stream_json}")
        sys.stdout.flush()

    if not args.skip_fuzz:
        from benchmarks.fuzz_bench import main as fuzz_main
        n_seeds = args.fuzz_seeds if args.fuzz_seeds is not None \
            else (128 if args.full else 48)
        res = fuzz_main(args.stream_json, n_seeds=n_seeds)
        print("\n# fuzz: metric,value")
        for k in ("n_seeds", "cases_per_sec", "total_rounds",
                  "total_kills", "violations"):
            print(f"{k},{res['fuzz'][k]}")
        print("# fuzz.validator: metric,value")
        for k in ("n_seeds", "runs_per_sec", "rounds_per_sec",
                  "max_margin", "violations"):
            print(f"{k},{res['fuzz']['validator'][k]}")
        print("# fuzz.backends: metric,value")
        for k in ("n_seeds", "cases_per_sec", "max_param_err",
                  "violations"):
            print(f"{k},{res['fuzz']['backends'][k]}")
        print("# fuzz.fuzzed_chaos: metric,value")
        for k in ("n_seeds", "cases_per_sec", "recoveries",
                  "events_merged", "mttr_mean_s", "mttr_max_s",
                  "violations"):
            print(f"{k},{res['fuzz']['fuzzed_chaos'][k]}")
        print("# chaos: metric,value")
        for k in ("n_recoveries", "mttr_mean_s", "mttr_max_s",
                  "recovered_rounds", "snapshot_failures",
                  "events_merged", "bitexact"):
            print(f"{k},{res['chaos'][k]}")
        print(f"# merged into {args.stream_json}")
        sys.stdout.flush()

    if not args.skip_tables:
        from benchmarks.paper_tables import (table3_scheme_comparison,
                                             table4_fast_reboot,
                                             table5_departure_crossing)
        rounds = 100 if args.full else 40
        print("\n# table3: dataset,iid,|T|,acc_A,acc_B,acc_C,B-A,C-B")
        for row in table3_scheme_comparison(rounds=rounds):
            print(",".join(f"{x:.4f}" if isinstance(x, float) else str(x)
                           for x in row))
        sys.stdout.flush()

        print("\n# table4: tau0,recover_epochs_fast,recover_epochs_vanilla")
        for row in table4_fast_reboot(rounds_after=60 if args.full else 40):
            print(",".join(str(x) for x in row))
        sys.stdout.flush()

        print("\n# table5: alpha,beta,tau0,crossing_epochs")
        for row in table5_departure_crossing():
            print(",".join(str(x) for x in row))
        sys.stdout.flush()

    if not args.skip_tables:
        from benchmarks.bound_check import run as bound_run
        print("\n# thm3.1 envelope: tau,measured_err2,bound,within")
        for tau, err, bound in bound_run(rounds=100):
            print(f"{tau},{err:.6f},{bound:.4f},{err <= bound}")
        sys.stdout.flush()

    # roofline summary from dry-run artifacts (if present)
    from benchmarks.roofline import load_results
    ok = [r for r in load_results() if r["status"] == "ok"]
    if not ok:
        print("\n# roofline: skipped (no dry-run artifacts under "
              "experiments/artifacts)")
        return
    print("\n# roofline: arch,shape,dominant,compute_s,memory_s,"
          "collective_s,useful_ratio")
    for r in ok:
        print(f"{r['arch']},{r['shape']},{r['dominant']},"
              f"{r['t_compute_s']:.4g},{r['t_memory_s']:.4g},"
              f"{r['t_collective_s']:.4g},{r['useful_ratio']:.2f}")


if __name__ == "__main__":
    main()
