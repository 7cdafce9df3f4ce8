"""The reader of the audit pipeline's ``audit.wait`` spans
(``audit.wait_us_per_proof.bulk``) on hand-built ``audit.run`` traces in
the process's tracer: None where no pass holds such a span (a program
that does not overlap a quantum's host prep with the device records
none, and the result line leaves the metric out), else the spans'
seconds over every ``complete`` pass, per proof the quanta settled, in
us."""

import pytest

import harness

NAME = "audit.wait_us_per_proof.bulk"
QUANTUM = 4096


@pytest.fixture
def tracer():
    from cpzk_tpu.observability.tracing import get_tracer

    get_tracer().clear()
    yield get_tracer()
    get_tracer().clear()


def _pass(tracer, status: str, waits: list) -> None:
    """One ``audit.run`` trace: per quantum an ``audit.quantum`` parent
    that settled a whole quantum and, unless its wait is None, an
    ``audit.wait`` span of that many seconds."""
    from cpzk_tpu.observability.context import RequestContext, new_trace_id

    tid = new_trace_id()
    tracer.start(RequestContext(trace_id=tid), "audit.run")
    for i, wait in enumerate(waits):
        if wait is not None:
            tracer.add_span(tid, "audit.wait", 0.0, wait, quantum=i,
                            records=QUANTUM)
        tracer.add_span(tid, "audit.quantum", 0.0, 1.0, quantum=i,
                        records=QUANTUM, settled=QUANTUM)
    tracer.finish(tid, status)


def _read():
    return harness.load_module("metrics", NAME).read({})


def test_wait_reader_is_none_without_audit_wait_spans(tracer):
    assert _read() is None
    _pass(tracer, "complete", [None, None])
    assert _read() is None


def test_wait_reader_sums_the_waits_of_complete_passes(tracer):
    _pass(tracer, "complete", [None, None])
    _pass(tracer, "complete", [0.1, 0.2])
    _pass(tracer, "checkpointed", [5.0])  # the warm-up quantum: not read
    assert _read() == pytest.approx(1e6 * 0.3 / (4 * QUANTUM))
