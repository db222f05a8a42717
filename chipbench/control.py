"""Readings that the limits of ``correct`` are set from (not run by run.py).

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed, one run of the cell at its own size with a short window,
in one process: the compared numbers of the program against the
reference (the sound readings) and of the reference computed at bfloat16
against the reference (the precision control).  ``--faults`` adds one
run per seed for each fault of ``faults.py`` planted under the timed
path.  One JSON line per seed (and fault).
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    if args.faults:
        return read_faults(args)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = run.run_cell(args.workload, seed, args.seconds, False,
                               control=True, log=lambda *_: None)
        except run.NoChip as e:
            print(f"control.py: {e}", file=sys.stderr)
            return 3
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "sound": {k: v["value"] for k, v in res["checks"].items()},
            "control": res["control_checks"],
            "limits": {k: v["limit"] for k, v in res["checks"].items()},
            "reference_s": res["reference_s"]}),
            flush=True)
    return 0


def read_faults(args) -> int:
    import faults
    import harness

    driver = harness.cell(args.workload)[2]["driver"]
    for seed in (int(s) for s in args.seeds.split(",")):
        for name, patch in faults.FAULTS[driver].items():
            res = run.run_cell(args.workload, seed, args.seconds, False,
                               log=lambda *_: None, patch=patch)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "fault": name,
                "correct": res["correct"],
                "readings": {k: v["value"] for k, v in
                             res["checks"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
