"""The audit pipeline's own tracing: one ``audit.run`` trace per
``run_audit`` call with an ``audit.quantum`` parent and its stage spans per
quantum, one flight record per dispatch, the ``audit.records{outcome}``
counter, profiler annotations that never enclose one another, and a
signed report that tracing leaves byte-identical."""

import contextlib
import json
import threading
import time

import pytest

from cpzk_tpu.audit import run_audit
from cpzk_tpu.audit.__main__ import main as audit_main
from cpzk_tpu.observability import get_flight_recorder, get_tracer, tracing
from cpzk_tpu.server import metrics

RECORDS = 64
QUANTUM = 16
#: every quantum's children (CpuBackend reports no marshal or compile)
QUANTUM_STAGES = {
    "audit.decode", "audit.parse", "pad_and_pack", "device_dispatch",
    "execute", "unpack", "audit.fold", "audit.checkpoint",
}
OUTCOMES = ("verified", "rejected", "skipped")


@pytest.fixture(autouse=True)
def _fresh_rings():
    get_tracer().clear()
    get_flight_recorder().clear()
    yield
    get_tracer().clear()
    get_flight_recorder().clear()


@pytest.fixture(scope="module")
def proof_log(tmp_path_factory):
    path = tmp_path_factory.mktemp("audit-tracing") / "proofs.log"
    # a wrong-secret reject in every quantum: the combined check, then the
    # per-row fallback
    assert audit_main(["generate", "--n", str(RECORDS), "--out", str(path),
                       "--reject-frac", "0.05"]) == 0
    return str(path)


def _records_counted() -> dict[str, float]:
    return {o: metrics.read("audit.records", labels={"outcome": o})
            for o in OUTCOMES}


def _inside(span, outer) -> bool:
    return (span is not outer and span.start >= outer.start
            and span.start + span.duration_s
            <= outer.start + outer.duration_s + 1e-9)


def _covered(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


@pytest.mark.parametrize("max_batches", [None, 2],
                         ids=["complete", "checkpointed"])
def test_audit_run_trace(tmp_path, monkeypatch, proof_log, max_batches):
    annotations = []  # (thread, name, enter, exit)

    @contextlib.contextmanager
    def record(name):
        t0 = time.monotonic()
        yield
        annotations.append((threading.get_ident(), name, t0, time.monotonic()))

    monkeypatch.setattr(tracing, "_trace_annotation", record)
    report_path = str(tmp_path / "traced.json")
    key = str(tmp_path / "audit.key")
    counted = _records_counted()
    report = run_audit(proof_log, report_path, key_path=key, quantum=QUANTUM,
                       max_batches=max_batches)
    counted = {o: n - counted[o] for o, n in _records_counted().items()}
    quanta = max_batches or RECORDS // QUANTUM

    (trace,) = [t for t in get_tracer().completed() if t.name == "audit.run"]
    assert trace.status == ("complete" if report else "checkpointed")
    names = trace.span_names()
    assert names.count("audit.open") == 1
    assert names.count("audit.report") == (1 if report else 0)
    parents = [s for s in trace.spans if s.name == "audit.quantum"]
    assert [s.attrs["quantum"] for s in parents] == list(range(quanta))
    for q in parents:
        assert q.attrs["records"] == QUANTUM and q.attrs["settled"] == QUANTUM
        children = [s for s in trace.spans if _inside(s, q)]
        assert {s.name for s in children} == QUANTUM_STAGES, q.attrs
        for s in children:
            if s.name.startswith("audit."):
                assert s.attrs == {"quantum": q.attrs["quantum"],
                                   "records": QUANTUM}
        covered = _covered((s.start, s.start + s.duration_s) for s in children)
        assert covered >= 0.9 * q.duration_s, (covered, q.duration_s)

    # one flight record per quantum's dispatch
    flights = get_flight_recorder().snapshot()
    assert [f.batch for f in flights] == [QUANTUM] * quanta
    assert all(f.backend == "cpu" and f.jit_misses == 0 for f in flights)

    # the counter moved once per quantum by the quantum's outcomes
    if report is None:
        with open(report_path + ".cursor", encoding="utf-8") as f:
            totals = json.load(f)
    else:
        totals = report["totals"]
    assert counted == {o: totals[o] for o in OUTCOMES}
    assert counted["rejected"] == quanta  # one wrong secret per quantum

    # leaf annotations only, and none encloses or overlaps another
    annotated = {name for _, name, _, _ in annotations}
    assert "audit.quantum" not in annotated
    assert QUANTUM_STAGES - {"execute"} <= annotated
    for thread in {a[0] for a in annotations}:
        spans = sorted(a[2:] for a in annotations if a[0] == thread)
        for (_, end), (start, _) in zip(spans, spans[1:]):
            assert start >= end, "nested or overlapping cpzk.* annotations"

    # tracing changes nothing a report says: finish the run, then replay
    # with the tracer cleared and the real annotations; byte-identical
    if report is None:
        assert run_audit(proof_log, report_path, key_path=key,
                         quantum=QUANTUM) is not None
    monkeypatch.undo()
    get_tracer().clear()
    fresh = str(tmp_path / "fresh.json")
    assert run_audit(proof_log, fresh, key_path=key, quantum=QUANTUM)
    with open(report_path, "rb") as a, open(fresh, "rb") as b:
        assert a.read() == b.read()
