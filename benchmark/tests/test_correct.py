"""``correct`` comes out false for a broken program: the cell's run is
driven on the CPU at a small size (the harness's look for a chip skipped),
once as it is and once with each fault that the cell can have planted
under the timed path (faults.py), and the control in the program's place.

Slow: XLA on the CPU compiles and runs the verify kernels (minutes)."""

import copy
import os

import pytest

import harness
import traffic

SEED = 2**31 + 4242


def _run(tmp_path, cell_name, fault=None, control=False, trace=False):
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    config = copy.deepcopy(harness.load_config(cell["config"]))
    mix = traffic.load(cell["traffic"])
    sizes = {"records": 32, "statements": 4, "quantum": 8,
             "reject_frac": 0.25, "lie_frac": 0.0625}
    seconds = 0.01
    work = tmp_path / f"{fault}-{control}"
    os.makedirs(work)
    run = harness.Run(workload=cell, config=config, mix=mix, seed=SEED,
                      seconds=seconds, trace=trace, work_dir=str(work),
                      platform="cpu", fault=fault, sizes=sizes)
    driver = harness.load_module("drivers", config["driver"])
    if trace:
        return driver.run(run)
    if not control:
        return driver.run(run).checks
    # control.py's readings: the program's answers and the control's
    (row,) = driver.readings(run, [SEED])
    assert all(v <= lim for _, v, lim in row["program"]), row
    return row["control"]


def _correct(checks) -> bool:
    return all(v <= lim for _, v, lim in checks)


@pytest.mark.parametrize("fault", [None, "flip", "half", "control"])
def test_audit_cell(tmp_path, fault):
    checks = _run(tmp_path, "audit-log.replay-1pct",
                  fault=None if fault == "control" else fault,
                  control=fault == "control")
    assert _correct(checks) == (fault is None), checks


def test_audit_cell_traced(tmp_path):
    """A traced run is correct too, and its window is the one it held open."""
    out = _run(tmp_path, "audit-log.replay-1pct", trace=True)
    assert out.correct, out.checks
    t = out.artifacts["trace"]
    assert t["devices"] == 0 and 0 < t["window_s"] < out.artifacts["window_s"] + 1
