#!/usr/bin/env python3
"""Upper readings for a cell whose traffic holds no rejected proof.

There, control.py's control (every proof that parses is accepted) gives
the reference's own verdicts, so it cannot read not correct.  What such
traffic does exercise is that every valid proof is accepted and that the
report is faithful: each lying verdict in the log is counted in
``mismatched``.  This runs the program itself with one of these broken,
per seed its own log and one pass (a window of 0.01 s closes at the
first quantum), and reads every compared number with the cell's own
comparison (the driver's ``readings``):

``misfold``  the audit's fold drops every lying verdict (the record's
             outcome and the digest are kept; ``mismatched`` stays 0);
``flip``     faults.py's: the first row of every batch gets the opposite
             verdict, so a valid proof is refused.

The benchmark's own runs never do this; run it on the chip at the cell's
size:

    python3 benchmark/report_control.py --workload <cell> --seeds 1,2 \
        --faults misfold,flip
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))

FAULTS = ("misfold", "flip")


@contextlib.contextmanager
def planted(name: str):
    """``name`` planted in the program for the block."""
    if name != "misfold":
        import faults

        with faults.planted(name):
            yield
        return
    from cpzk_tpu.audit.pipeline import AuditState

    inner = AuditState.note

    def misfolded(self, rec, outcome, mismatch=False):
        inner(self, rec, outcome, mismatch=False)

    AuditState.note = misfolded
    try:
        yield
    finally:
        AuditState.note = inner


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--faults", default=",".join(FAULTS))
    args = p.parse_args()
    sys.path[:0] = [HERE, os.path.dirname(HERE)]
    import harness
    import traffic

    harness.use_checkout_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    names = args.faults.split(",")
    if not set(names) <= set(FAULTS):
        p.error(f"--faults: one or more of {', '.join(FAULTS)}")
    cell = {w["name"]: w
            for w in harness.load_benchmark()["workloads"]}[args.workload]
    config = harness.load_config(cell["config"])
    driver = harness.load_module("drivers", config["driver"])
    summary = {}
    for name in names:
        work = tempfile.mkdtemp(prefix="cpzk-report-control-")
        try:
            run = harness.Run(workload=cell, config=config,
                              mix=traffic.load(cell["traffic"]),
                              seed=seeds[0], seconds=0.01,
                              trace=False, work_dir=work, t0=T0)
            with planted(name):
                rows = driver.readings(run, seeds)
        finally:
            shutil.rmtree(work, ignore_errors=True)
        for row in rows:
            print("# " + json.dumps({"fault": name, "seed": row["seed"],
                                     "checks": row["program"]}), flush=True)
        summary[name] = {n: min(dict((k, v) for k, v, _ in row["program"])[n]
                                for row in rows)
                         for n, _, _ in rows[0]["program"]}
    print(json.dumps({"workload": args.workload, "seeds": seeds,
                      "device": rows[0]["device"], "upper": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
