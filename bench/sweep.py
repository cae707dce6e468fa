"""Find a serving cell's knee: the highest offered rate at which the queue
does not grow over the window. One process, one window per rate, no check.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 0.4,0.6,0.8

For each rate it prints the requests still queued at the window's close,
the median time to first token of the first and of the last third of the
requests due in the window (a growing queue makes the last third wait
longer), and the cell's end-to-end metrics. The traffic file's rate key
(``rate_per_s``, or ``session_rate_per_s`` for sessions) is what varies.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.harness import load_cell, run_cell
    mix = load_cell(ROOT, args.workload)[3]
    key = "session_rate_per_s" if mix["arrivals"] == "sessions" else "rate_per_s"
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        result, _ = run_cell(ROOT, args.workload, args.seed, args.seconds, False,
                             mix_override={key: rate}, check=False)
        rec = result["record"]
        ttft = [t for _, t in rec["ttft_by_due"]]
        third = max(1, len(ttft) // 3)
        row = {"rate": rate, "waiting_at_end": rec["waiting_at_end"],
               "due": len(ttft),
               "ttft_p50_first_third": float(np.median(ttft[:third])) if ttft else None,
               "ttft_p50_last_third": float(np.median(ttft[-third:])) if ttft else None,
               "metrics": {k: v["value"] for k, v in result["metrics"].items()},
               "tokens": rec["tokens"], "window_s": rec["window_s"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
        gc.collect()
    print(json.dumps({"workload": args.workload, "sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
