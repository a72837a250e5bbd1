#!/usr/bin/env python3
"""Readings that a cell's limits and rate are set from, many seeds in one
process (set-up compiles once).  Not part of a benchmark run.

  python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 10 \
      [--control fp8] [--rates 4,6,8] [--fault half_batch]

For each seed (and each rate, where ``--rates`` is given) the cell's driver
sets up, runs a window of ``--seconds`` and reports the numbers its
``correct`` compares for the program and, with ``--control``, for the
plain reference computed at that lower precision in the program's place.
One JSON line per reading goes to standard output.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run as bench_run  # noqa: E402
from lib import common  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default=None)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--fault", default=None,
                    help="a fault of the driver's FAULTS planted in the step")
    args = ap.parse_args(argv)
    files = common.cell_files(args.workload)
    common.enable_compile_cache()
    clog = common.CompileLog().install()
    devices = common.check_devices(files["entry"]["chips"])
    drv = common.driver(files["cell"]["driver"])
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    for rate in rates:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            ctx = bench_run.Context(files, seed, args.seconds, False, devices,
                                    clog)
            kw = {"fault": args.fault} if args.fault else {}
            out = drv.calibrate(ctx, rate=rate, control=args.control, **kw)
            out.update(seed=seed, rate=rate, run_s=time.perf_counter() - t0,
                       memory_peak_bytes=ctx.memory_peak_bytes,
                       compiles_in_window=ctx.compiles_in_window)
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    try:
        main()
    except common.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        sys.exit(2)
