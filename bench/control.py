"""Read a cell's compared numbers for the program and for its control over
several seeds in one process (the control is never part of a benchmark run).

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For every seed it runs the cell as ``bench/run.py`` does (at the cell's own
sizes and load, for ``--seconds``) and prints the program's numbers beside
the control's: the plain reference computed one precision below what the
configuration states (bfloat16 at default matmul precision), put in the
program's place. Training cells also read the reference fed half of each
batch. The last line is one JSON object with every reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.harness import run_cell
    readings = []
    for seed in (int(s) for s in args.seeds.split(",")):
        result, lines = run_cell(ROOT, args.workload, seed, args.seconds,
                                 False, control=True)
        row = {"seed": seed, "correct": result["correct"],
               "program": {k: v["value"] for k, v in result["checks"].items()},
               "control": result["control"], "metrics": result["metrics"],
               "memory_peak_bytes": result["device"]["memory_peak_bytes"]}
        print(json.dumps(row), flush=True)
        for line in lines:
            print("  " + line, flush=True)
        readings.append(row)
        gc.collect()
    print(json.dumps({"workload": args.workload, "readings": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
