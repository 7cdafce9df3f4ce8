#!/usr/bin/env python3
"""The readings that set each compared number's limit (PERF.md section 2):
one process runs a window of the cell per seed and reads every compared
number twice, for the program's answers (the lower reading: the largest
over the seeds) and with the control's answers in the program's place
(reference.py with soundness broken: every proof that parses is accepted;
the upper reading: the smallest over the seeds).  The benchmark's own runs
never do this; run it on the chip at the cell's size:

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 10
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import harness  # noqa: E402
import traffic  # noqa: E402

harness.use_checkout_cache()


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    args = p.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = {w["name"]: w for w in harness.load_benchmark()["workloads"]}[args.workload]
    config = harness.load_config(cell["config"])
    work = tempfile.mkdtemp(prefix="cpzk-control-")
    try:
        run = harness.Run(workload=cell, config=config,
                          mix=traffic.load(cell["traffic"]), seed=seeds[0],
                          seconds=args.seconds, trace=False, work_dir=work, t0=T0)
        rows = harness.load_module("drivers", config["driver"]).readings(run, seeds)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    for row in rows:
        print("# " + json.dumps(row), flush=True)
    names = [n for n, _, _ in rows[0]["program"]]
    summary = {n: {"lower": max(dict((k, v) for k, v, _ in row["program"])[n]
                                for row in rows),
                   "upper": min(dict((k, v) for k, v, _ in row["control"])[n]
                                for row in rows)}
               for n in names}
    print(json.dumps({"workload": args.workload, "seeds": seeds,
                      "device": rows[0]["device"], "readings": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
