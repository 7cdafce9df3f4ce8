"""``dispatch.combined_share.bulk``: its reader computes the share from
built counters, and the all-valid cell, driven on the CPU at a small size
as ``test_correct.py`` drives the audit cell, reads correct with every
batch through the combined check, while the 1% cell's rejects skip it.

Slow: XLA on the CPU compiles and runs the verify kernels (minutes)."""

import copy
import os

import pytest

import harness
import traffic

SEED = 2**31 + 2727
READER = "dispatch.combined_share.bulk"
OUTCOMES = ("accepted", "rejected", "skipped")


def _counted() -> dict[str, float]:
    from cpzk_tpu.server import metrics

    return {o: metrics.read("batch.combined", labels={"outcome": o})
            for o in OUTCOMES}


def _run(tmp_path, cell_name, sizes):
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    config = copy.deepcopy(harness.load_config(cell["config"]))
    work = tmp_path / cell_name
    os.makedirs(work)
    run = harness.Run(workload=cell, config=config,
                      mix=traffic.load(cell["traffic"]), seed=SEED,
                      seconds=0.01, trace=False, work_dir=str(work),
                      platform="cpu", sizes=sizes)
    return harness.load_module("drivers", config["driver"]).run(run)


def test_combined_share_reader_on_built_counters(monkeypatch):
    from cpzk_tpu.server import metrics

    reader = harness.load_module("metrics", READER)
    counts = dict.fromkeys(OUTCOMES, 0.0)
    monkeypatch.setattr(metrics, "read",
                        lambda name, kind="c", labels=None:
                        counts[labels["outcome"]]
                        if name == "batch.combined" else 0)
    assert reader.read({}) is None  # nothing counted: a program without it
    # a pass of 16 quanta with a reject in each: combined once, 15 skipped
    counts.update(rejected=1, skipped=15)
    assert reader.read({}) == pytest.approx(1 / 16)
    counts.update(accepted=16, rejected=0, skipped=0)
    assert reader.read({}) == 1.0


@pytest.mark.parametrize("cell,sizes", [
    ("audit-log.replay-allvalid", {"records": 32, "statements": 4,
                                   "quantum": 8}),
    ("audit-log.replay-1pct", {"records": 32, "statements": 4, "quantum": 8,
                               "reject_frac": 0.25, "lie_frac": 0.0625}),
], ids=["allvalid", "1pct"])
def test_cells_on_the_cpu(tmp_path, cell, sizes):
    before = _counted()
    out = _run(tmp_path, cell, sizes)
    assert out.correct, out.checks
    n = {o: v - before[o] for o, v in _counted().items()}
    if cell.endswith("allvalid"):
        # every quantum of every pass, and the warm-up, passed the check
        assert n["rejected"] == n["skipped"] == 0 < n["accepted"]
    else:
        # a reject closes the gate: a pass's next quantum skips the check
        assert n["skipped"] > 0 and n["rejected"] > 0
