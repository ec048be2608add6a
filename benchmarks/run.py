"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  bench_weak_scaling   -> Figure 4/5 + Table 1 (model, validated vs paper)
  bench_overhead       -> Table 2 + Figure 6 (model + MEASURED local overhead)
  bench_strong_scaling -> Figure 7
  bench_kernels        -> fused dual-checksum ABFT-matmul kernel accounting
                          + checksummed flash-attention epilogue cost
  bench_train_step     -> live train-step ABFT overhead, diskless encode,
                          at-rest scrub verify wall
  bench_serving        -> continuous-batching throughput, ABFT on/off,
                          SDC-drill recovery latency, KV/params scrub cost
  bench_elastic        -> pod-loss shrink/re-grow drill: reshard wall,
                          bytes moved, recompile time, steps-to-parity
  bench_chaos          -> single-device chaos-campaign sweep: per-event
                          outcomes + coverage counters (missed_anywhere,
                          false_alarms and uncovered_surfaces must be 0)
  bench_traffic        -> heavy-traffic paged-KV serving: closed + open-loop
                          TTFT/throughput, the SAME trace drilled (SDC +
                          page-DRAM, zero missed), SLO scheduler stress
  roofline             -> per (arch x shape) roofline terms from the dry-run

``--json PATH`` additionally writes a machine-readable name -> {us, derived}
map, so the perf trajectory is diffable across PRs (see BENCH_PR2.json).
"""
import argparse
import json
import sys
import traceback


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--json", metavar="PATH", default=None,
                        help="also write rows as JSON {name: {us, derived}}")
    args = parser.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import (bench_chaos, bench_elastic, bench_kernels,
                            bench_overhead, bench_serving,
                            bench_strong_scaling, bench_traffic,
                            bench_train_step, bench_weak_scaling, roofline)
    mods = [bench_weak_scaling, bench_overhead, bench_strong_scaling,
            bench_kernels, bench_train_step, bench_serving, bench_elastic,
            bench_chaos, bench_traffic, roofline]
    print("name,us_per_call,derived")
    rows = {}
    failed = 0
    for mod in mods:
        try:
            for name, us, derived in mod.run():
                print(f"{name},{us},{derived}")
                rows[name] = {"us": us, "derived": derived}
        except Exception as e:  # noqa
            failed += 1
            print(f"{mod.__name__},ERROR,{e!r}", file=sys.stderr)
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(rows, fh, indent=1, sort_keys=True)
        print(f"wrote {len(rows)} rows to {args.json}", file=sys.stderr)
    if failed:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
