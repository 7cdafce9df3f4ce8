"""Each metric reader computes its value from canned artifacts: a reduced
trace and the window's host-clock counts."""

import json
import os

import pytest

import harness

TRACE = {"busy_s": 1.5, "window_s": 6.0, "devices": 1,
         "programs": {"jit__combined_impl": 1.0, "jit__each_shared_impl": 0.25,
                      "jit_convert_element_type": 0.25}}


def read(name, art):
    return harness.load_module("metrics", name).read(art)


def test_bulk_readers():
    art = {"settled": 100_000, "window_s": 10.0, "trace": TRACE,
           "traced": 50_000}
    assert read("verified_per_s", art) == 10_000.0
    assert read("setup_s", dict(art, setup_s=12.5)) == 12.5
    assert read("device.idle_share.bulk", art) == pytest.approx(0.75)
    # verify programs only: 1.25 s over 50,000 proofs
    assert read("kernel.us_per_proof.bulk", art) == pytest.approx(25.0)


def test_a_reader_with_nothing_to_read_returns_none():
    for m in ("setup_s", "verified_per_s", "device.idle_share.bulk",
              "kernel.us_per_proof.bulk"):
        assert read(m, {}) is None
    # a trace without a device plane gives no idle share, never 0 or 1
    assert read("device.idle_share.bulk", {"trace": dict(TRACE, devices=0)}) is None


def test_each_cell_reports_setup_another_end_to_end_and_a_per_layer_metric():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        e2e = {m["name"] for m in harness.metrics_of(bench, w["name"], False)}
        layer = harness.metrics_of(bench, w["name"], True)
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        for m in layer:
            assert m["moves"] in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.isfile(os.path.join(harness.HERE, "metrics",
                                           m["name"] + ".py"))
    json.dumps(bench)
