"""Readings that a cell's limits are set from.  Not part of a run.

    python3 bench/calibrate.py --workload <name> --program-seeds 1 2 ... \
        --planted-seeds 7 8 9 [--seconds 3] [--events N]

On the chip, at the cell's own size, in one process.  For each program
seed: the numbers that decide ``correct`` for the program (training: its
first rounds, with no window; what-if: a short run).  For each planted
seed: the same numbers for the reference put in the program's place,
computed one precision below the configuration's (the control) and with
each fault that the cell can have planted in it.  One JSON line each.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--planted-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0,
                    help="window of a what-if program reading")
    ap.add_argument("--events", type=int,
                    help="events of a what-if planted reading: as many as "
                         "a timed run replays (its events_replayed)")
    args = ap.parse_args(argv)

    R._prepare()
    import harness as H
    cell = H.find_cell(H.load_manifest(), args.workload)
    devices = H.require_chip(cell.chips)
    R._enable_cache()
    kind = H.kind_module(cell.traffic["kind"])
    whatif = cell.traffic["kind"] == "whatif"
    if whatif and args.planted_seeds and not args.events:
        ap.error("a what-if planted reading needs --events")
    for seed in args.program_seeds:
        got = (kind.program_reading(cell, seed, args.seconds, devices)
               if whatif else kind.program_reading(cell, seed))
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "reading": "program", **got}), flush=True)
    for seed in args.planted_seeds:
        got = (kind.planted_readings(cell, seed, args.events) if whatif
               else kind.planted_readings(cell, seed))
        for name, nums in got.items():
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "reading": name, **nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
