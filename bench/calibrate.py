#!/usr/bin/env python3
"""Read the numbers a cell compares, for many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 [--control 4,5,6] [--fault <name>=7,8,9 ...] \
        [--rates 1.0,2.0 (serving cells: one sweep run per rate)]

Each seed drives the cell's driver as a benchmark run does (set-up, the
window, release, the comparison with the reference) and prints one JSON
line with the numbers compared.  ``--control`` runs the driver's control
(its program or reference one precision below the configuration's),
``--fault`` one of the driver's planted faults.  The limits in the
workload files are set from these readings (see PERF.md); the benchmark's
own runs never run a control or a fault.

``--rates`` sweeps a serving cell's arrival rate instead and prints, per
rate, the end-to-end metrics and how the backlog grew over the window
(requests without a first token, requests unfinished, and the tokens they
still have to serve): the knee is the highest rate whose backlog does not
grow.
"""
import argparse
import copy
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)


def _seeds(s):
    return [int(x) for x in s.split(",") if x]


def one(harness, cell, seed, devices, mode="program", fault=None,
        seconds=10.0):
    drv_mod = harness.driver_module(cell)
    cell = copy.deepcopy(cell)
    drv = drv_mod.Driver(cell, seed, devices)
    if mode == "control":
        drv_mod.control(drv)
    elif mode == "fault":
        drv_mod.FAULTS[fault](drv)
    t0 = time.perf_counter()
    drv.setup()
    setup_s = time.perf_counter() - t0
    st = drv.window(seconds)
    e2e = drv.end_to_end(st)
    lines = drv.report_lines(st)
    backlog = st.get("backlog")
    drv.release()
    checks = drv.check(st)
    out = {"mode": mode if fault is None else f"fault:{fault}", "seed": seed,
           "setup_s": setup_s, "e2e": e2e,
           "checks": {c.name: c.value for c in checks},
           "correct": all(c.ok for c in checks), "report": lines}
    if backlog:
        half = [b for b in backlog if b[0] <= st["window_s"] / 2]
        mid = half[-1] if half else (0.0, 0, 0, 0)
        out["backlog_mid"], out["unfinished_mid"], out["tokens_due_mid"] = \
            mid[1:]
        out["backlog_end"], out["unfinished_end"], out["tokens_due_end"] = \
            backlog[-1][1:]
        out["submitted"], out["finished"] = st["submitted"], st["finished"]
    del drv, st
    gc.collect()
    print(json.dumps(out), flush=True)
    return out


def main(argv=None) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", action="append", default=[],
                    help="name=seed,seed,...")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    from bench.program import import_program
    import_program()
    try:
        devices = harness.find_devices(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    if args.rates:
        seed = (_seeds(args.seeds) or [0])[0]
        for rate in [float(r) for r in args.rates.split(",")]:
            c = copy.deepcopy(cell)
            c.traffic["rate_per_s"] = rate
            r = one(harness, c, seed, devices, seconds=args.seconds)
            print(json.dumps({"rate_per_s": rate, **{k: r.get(k) for k in (
                "e2e", "backlog_mid", "backlog_end", "unfinished_mid",
                "unfinished_end", "tokens_due_mid", "tokens_due_end",
                "submitted", "finished", "checks")}}),
                flush=True)
        return 0
    for s in _seeds(args.seeds):
        one(harness, cell, s, devices, seconds=args.seconds)
    for s in _seeds(args.control):
        one(harness, cell, s, devices, mode="control", seconds=args.seconds)
    for spec in args.fault:
        name, seeds = spec.split("=")
        for s in _seeds(seeds):
            one(harness, cell, s, devices, mode="fault", fault=name,
                seconds=args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
