"""The readers of the program's own spans (spans.py) on a traced run of the
audit cell, driven on the CPU at a small size as ``test_correct.py``
drives it: they read nothing before the run and a positive number after
it, their sums with the quanta's self times make up the passes' wall
time, and the profiler's trace holds the audit stages as ``cpzk.audit.*``
host events on its own clock.

Slow: XLA on the CPU compiles and runs the verify kernels (minutes)."""

import copy
import os

import pytest

import harness
import spans
import trace_reduce
import traffic

SEED = 2**31 + 7373
CELL = "audit-log.replay-1pct"
READERS = ("audit.host_us_per_proof.bulk", "dispatch.host_us_per_proof.bulk",
           "dispatch.execute_us_per_proof.bulk")


def _read(name):
    return harness.load_module("metrics", name).read({})


def _self_seconds(trace, parent):
    """``parent``'s duration less the union of the spans inside it."""
    end = parent.start + parent.duration_s
    inside = sorted((s.start, s.start + s.duration_s) for s in trace.spans
                    if s is not parent and s.start >= parent.start
                    and s.start + s.duration_s <= end + 1e-9)
    covered, reach = 0.0, parent.start
    for a, b in inside:
        if b > reach:
            covered += b - max(a, reach)
            reach = b
    return parent.duration_s - covered


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from cpzk_tpu.observability.tracing import get_tracer

    get_tracer().clear()
    before = {m: _read(m) for m in READERS}
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = copy.deepcopy(harness.load_config(cell["config"]))
    sizes = {"records": 32, "statements": 4, "quantum": 8,
             "reject_frac": 0.25, "lie_frac": 0.0625}
    work = tmp_path_factory.mktemp("traced")
    run = harness.Run(workload=cell, config=config,
                      mix=traffic.load(cell["traffic"]), seed=SEED,
                      seconds=0.01, trace=True, work_dir=str(work),
                      platform="cpu", sizes=sizes)
    out = harness.load_module("drivers", config["driver"]).run(run)
    assert out.correct, out.checks
    yield before, {m: _read(m) for m in READERS}, str(work)
    get_tracer().clear()


def test_readers_read_the_run_alone(traced):
    before, after, _ = traced
    assert before == dict.fromkeys(READERS)
    assert all(v > 0 for v in after.values()), after


def test_stage_sums_and_self_times_make_up_the_passes(traced):
    _, after, _ = traced
    passes = spans.passes()
    assert passes and spans.settled(passes) == 32 * len(passes)
    summed = sum(after.values()) * spans.settled(passes) / 1e6
    self_s = sum(_self_seconds(t, q) for t in passes for q in t.spans
                 if q.name == "audit.quantum")
    wall = sum(t.duration_s for t in passes)
    assert abs(summed + self_s - wall) <= 0.1 * wall, (summed, self_s, wall)


def test_profiler_trace_holds_the_audit_stages(traced):
    *_, work = traced
    (path,) = [os.path.join(d, f) for d, _, files in os.walk(work)
               for f in files if f.endswith(".xplane.pb")]
    names = {n for p in trace_reduce.load(path) for line in p["lines"]
             for n, _, _ in line["events"]}
    assert {"cpzk.audit.open", "cpzk.audit.decode", "cpzk.audit.parse",
            "cpzk.pad_and_pack", "cpzk.device_dispatch"} <= names, sorted(
        n for n in names if n.startswith("cpzk"))
    assert "cpzk.audit.quantum" not in names
