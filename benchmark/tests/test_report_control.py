"""On traffic with no rejected proof (``audit-log.replay-allvalid``)
``correct`` still comes out false for a broken program: a valid proof
refused (``flip``) or a lying verdict dropped from the report
(``misfold``, report_control.py).  control.py's control reads correct
there, which is why the cell takes its upper readings from
report_control.py.  The cell's run is driven on the CPU at a small size
with two lying verdicts, as ``test_correct.py`` drives the 1% cell.

Slow: XLA on the CPU compiles and runs the verify kernels (minutes)."""

import copy
import os

import pytest

import harness
import report_control
import traffic

SEED = 2**31 + 2727
CELL = "audit-log.replay-allvalid"


def _readings(tmp_path, fault):
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = copy.deepcopy(harness.load_config(cell["config"]))
    mix = traffic.load(cell["traffic"])
    assert mix["reject_frac"] == 0
    work = tmp_path / str(fault)
    os.makedirs(work)
    run = harness.Run(workload=cell, config=config, mix=mix, seed=SEED,
                      seconds=0.01, trace=False, work_dir=str(work),
                      platform="cpu",
                      sizes={"records": 32, "statements": 4, "quantum": 8,
                             "lie_frac": 0.0625})
    driver = harness.load_module("drivers", config["driver"])
    if fault is None:
        (row,) = driver.readings(run, [SEED])
        return row
    with report_control.planted(fault):
        (row,) = driver.readings(run, [SEED])
    return row


def _checks(row) -> dict:
    return {name: v for name, v, _ in row}


def test_the_program_and_the_control_read_correct(tmp_path):
    row = _readings(tmp_path, None)
    assert _checks(row["program"]) == {"quantum_counts_off": 0,
                                       "pass_digests_wrong": 0}
    # no reject in the log: accepting every proof that parses is right
    assert _checks(row["control"]) == _checks(row["program"])


@pytest.mark.parametrize("fault", report_control.FAULTS)
def test_a_broken_program_reads_not_correct(tmp_path, fault):
    got = _checks(_readings(tmp_path, fault)["program"])
    if fault == "misfold":
        # the two lies go uncounted; verdicts and digest are kept
        assert got == {"quantum_counts_off": 2, "pass_digests_wrong": 0}
    else:
        # 4 quanta, each with a refused valid proof: verified, rejected
        # and mismatched each off by one
        assert got == {"quantum_counts_off": 12, "pass_digests_wrong": 1}
