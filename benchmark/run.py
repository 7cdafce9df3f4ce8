#!/usr/bin/env python3
"""Run one benchmark cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name (``BENCHMARK.json``, ``benchmark/configs``, ``benchmark/traffic``,
``benchmark/metrics``); the configuration names the driver that runs it
(``benchmark/drivers``).  Lines starting ``#`` are informational; the
last line of standard output is the result:

    {"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
     "checks"}

``checks`` (last) holds every number compared with the plain reference,
each with its limit; the same lines end standard error.  With no chip,
or fewer than the cell needs, the run exits 3 and prints no result.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _number(v):
    return v if isinstance(v, (int, float)) else float(v)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "cpzk_tpu")):
        print("benchmark: no cpzk_tpu/ in this checkout: nothing to measure",
              file=sys.stderr)
        return 2
    sys.path[:0] = [HERE, ROOT]
    import harness

    harness.use_checkout_cache()

    bench = harness.load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"benchmark: no workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config = harness.load_config(cell["config"])
    import traffic

    mix = traffic.load(cell["traffic"])
    driver = harness.load_module("drivers", config["driver"])
    wanted = harness.metrics_of(bench, cell["name"], bool(args.trace))
    readers = {m["name"]: harness.load_module("metrics", m["name"]) for m in wanted}
    work = tempfile.mkdtemp(prefix="cpzk-bench-")
    try:
        run = harness.Run(workload=cell, config=config, mix=mix, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          work_dir=work, t0=T0)
        try:
            out = driver.run(run)
        except harness.NoDevice as e:
            print(f"benchmark: {e}", file=sys.stderr)
            return 3
    finally:
        shutil.rmtree(work, ignore_errors=True)
    metrics = {}
    for m in wanted:
        value = readers[m["name"]].read(out.artifacts)
        if value is not None:
            metrics[m["name"]] = {"value": _number(value), "unit": m["unit"]}
    result = {"correct": out.correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": out.device}
    if out.breakdown is not None:
        result["breakdown"] = out.breakdown
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in out.checks}
    for name, v, lim in out.checks:
        print(f"check {name} = {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
