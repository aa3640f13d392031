#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This (parent) process never imports jax. It finds the cell's files by
name (benchmark/workloads/<cell>.json, configs/<config>.json,
metrics/<metric>.json, readers/<reader>.py), hands the cell to the driver
its workload file names, and prints the result as the last line of its
standard output. It fails — another exit code than 0 and no result line —
without ``chips`` TPU chips of a kind in peaks.json. ``--rehearse`` walks
the same control flow at a tiny size on the CPU and always ends incorrect.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import harness, manifest, traceread  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on the CPU; never a result")
    p.add_argument("--control", default=None,
                   help="run a lower-precision control named in the "
                        "workload file (expected to end incorrect)")
    p.add_argument("--limit-seeds", type=lambda s: [int(x) for x in
                                                    s.split(",")],
                   default=None, help="train cells: read the compared "
                   "numbers on these seeds in one job, no window")
    args = p.parse_args(argv)
    bench = manifest.load()
    cell = manifest.cell(bench, args.workload)
    wl = manifest.workload_file(args.workload)
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    if args.limit_seeds:
        args.seconds = 0.0
    driver = importlib.import_module(f"benchmark.drivers.{wl['driver']}")
    try:
        res = driver.run(cell, wl, args, T_START)
    except harness.Failed as e:
        harness.log(f"FAILED: {e}")
        return 1
    breakdown = None
    if args.trace:
        art = res["artifacts"]
        if "trace" not in art:
            art["trace"] = traceread.load(art["trace_events"])
        busy_s, window_s = traceread.busy_share(art["trace"])
        res["device"].update(busy_s=busy_s, window_s=window_s)
        breakdown = {"device_ops": traceread.top_ops(art["trace"]),
                     "idle_gaps": traceread.idle_gaps(art["trace"])}
        metrics = manifest.read_layer_metrics(bench, cell, art)
    else:
        metrics = {m["name"]: {"value": res["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in manifest.cell_metrics(bench, cell, "end_to_end")}
    line = {"correct": res["correct"] and not args.rehearse,
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": res["device"]}
    if breakdown:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)
    return 1 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
