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

from cpzk_tpu.audit import pipeline, run_audit
from cpzk_tpu.audit.__main__ import main as audit_main
from cpzk_tpu.observability import get_flight_recorder, get_tracer, tracing
from cpzk_tpu.protocol.batch import CpuBackend
from cpzk_tpu.server import metrics

RECORDS = 64
QUANTUM = 16
#: every quantum's own spans, matched by their ``quantum`` attr
AUDIT_STAGES = {
    "audit.decode", "audit.parse", "audit.wait", "audit.fold",
    "audit.checkpoint",
}
#: the dispatch seam's spans, one each per quantum, in quantum order
#: (CpuBackend reports no marshal or compile)
SEAM_STAGES = {
    "pad_and_pack", "device_wait", "device_dispatch", "execute", "unpack",
}
#: the stages a profiler annotation marks (leaves only)
ANNOTATED = (AUDIT_STAGES | SEAM_STAGES) - {
    "audit.wait", "device_wait", "execute",
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
    # a wrong-secret reject in every quantum, each verified by the per-row
    # checks (the CPU oracle prefers them; a device backend's gate would
    # skip its combined check after the first quantum)
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
    seam = {name: [s for s in trace.spans if s.name == name]
            for name in SEAM_STAGES}
    assert {name: len(v) for name, v in seam.items()} == dict.fromkeys(
        SEAM_STAGES, quanta)
    for prev, q in zip(parents, parents[1:]):  # the parents tile the run
        assert q.start == pytest.approx(prev.start + prev.duration_s, abs=1e-6)
    for q in parents:
        index = q.attrs["quantum"]
        assert q.attrs["records"] == QUANTUM and q.attrs["settled"] == QUANTUM
        # a quantum's spans are matched by attr (audit.*) or by order (the
        # seam's), not by time: its host prep runs inside the previous
        # quantum's parent, while that quantum is on the worker
        mine = [s for s in trace.spans if s.name in AUDIT_STAGES
                and s.attrs.get("quantum") == index]
        assert [s.name for s in mine] == [
            "audit.decode", "audit.parse", "audit.wait", "audit.fold",
            "audit.checkpoint"], q.attrs
        assert all(s.attrs == {"quantum": index, "records": QUANTUM}
                   for s in mine)
        own = {s.name: s for s in mine}
        prep = parents[max(index - 1, 0)]
        assert all(_inside(s, prep) for s in (
            own["audit.decode"], own["audit.parse"],
            seam["pad_and_pack"][index])), q.attrs
        # (the worker's spans fall where the device phase runs: in either)
        assert all(_inside(s, q) for s in (
            own["audit.wait"], own["audit.fold"], own["audit.checkpoint"],
        )), q.attrs
        # every instant of the parent is some stage's, on either thread
        covered = _covered((s.start, s.start + s.duration_s)
                           for s in trace.spans if _inside(s, q))
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
    assert not {"audit.quantum", "audit.wait"} & annotated
    assert ANNOTATED <= annotated
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


class _SlowDevice(CpuBackend):
    """A device phase that waits with the GIL released, as a fetch from
    the chip does."""

    def verify_each(self, rows):
        time.sleep(0.05)
        return super().verify_each(rows)


def test_next_quantum_prepares_while_the_device_runs(tmp_path, monkeypatch,
                                                     proof_log):
    monkeypatch.setattr(pipeline, "build_backend",
                        lambda *a, **kw: _SlowDevice())
    key = str(tmp_path / "audit.key")
    slow = str(tmp_path / "slow.json")
    assert run_audit(proof_log, slow, key_path=key, quantum=QUANTUM)
    quanta = RECORDS // QUANTUM

    (trace,) = [t for t in get_tracer().completed() if t.name == "audit.run"]
    decode = {s.attrs["quantum"]: s for s in trace.spans
              if s.name == "audit.decode"}
    execute = [s for s in trace.spans if s.name == "execute"]
    assert len(execute) == quanta
    for n in range(quanta - 1):
        assert decode[n + 1].start < execute[n].start + execute[n].duration_s, n
    waits = sorted(s.attrs["quantum"] for s in trace.spans
                   if s.name == "audit.wait")
    assert waits == list(range(quanta))

    # the overlap moves work in time only: the stock backend signs the
    # same bytes
    monkeypatch.undo()
    stock = str(tmp_path / "stock.json")
    assert run_audit(proof_log, stock, key_path=key, quantum=QUANTUM)
    with open(slow, "rb") as a, open(stock, "rb") as b:
        assert a.read() == b.read()
