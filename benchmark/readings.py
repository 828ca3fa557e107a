#!/usr/bin/env python3
"""Readings that the limits in `limits/<cell>.json` are set from (not a
benchmark run): one set-up, then for each of `--seeds` seeds a short window at
the cell's own load compared as a run compares it, then the configuration's
control on `--control-seeds` seeds.  One JSON line per reading.

    python3 benchmark/readings.py --workload lte.mc --seeds 12 --seconds 8 --control-seeds 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_019)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    from benchmark import run
    from benchmark.manifest import Manifest

    manifest = Manifest(ROOT)
    entry = manifest.cell(args.workload)
    run.require_chips(int(entry["chips"]))
    cell = run.make_cell(manifest, args.workload, args.first_seed, ROOT)
    driver = manifest.driver(cell.traffic["driver"])
    state = driver.setup(cell)
    for i in range(args.seeds + args.control_seeds):
        seed = args.first_seed + 7919 * i
        driver.reseed(state, cell, seed)
        t0 = time.monotonic()
        if i < args.seeds:
            record = driver.window(state, cell, args.seconds)
            numbers, what = driver.check(state, cell, record), "sound"
            extra = dict(driver.end_to_end(state, cell, record),
                         attempted=driver.attempted(record))
        else:
            numbers, what = driver.control(state, cell, args.seconds), "control"
            extra = {}
        print(json.dumps(dict(reading=what, seed=seed, numbers=numbers,
                              seconds=time.monotonic() - t0, **extra)),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
