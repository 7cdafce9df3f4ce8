"""The reader of the audit pipeline's ``audit.gc`` spans
(``audit.gc_us_per_proof.bulk``) on hand-built ``audit.run`` traces in
the process's tracer: None where the program does not count its
collections (no ``audit.gc.collections`` counter, so the result line
leaves the metric out), else the spans' seconds over every ``complete``
pass, per proof the quanta settled, in us, and 0 where no collection
ran."""

import pytest

import harness

NAME = "audit.gc_us_per_proof.bulk"
QUANTUM = 4096


@pytest.fixture
def tracer():
    from cpzk_tpu.observability.tracing import get_tracer

    get_tracer().clear()
    yield get_tracer()
    get_tracer().clear()


@pytest.fixture
def uncounted(monkeypatch):
    """The process as a program without the counter leaves it (an earlier
    run in this process may have registered it)."""
    from cpzk_tpu.server import metrics

    registered = metrics.registered
    monkeypatch.setattr(metrics, "registered", lambda: [
        m for m in registered() if m != ("c", "audit.gc.collections")])


def _counted():
    """The counter, registered as ``run_audit`` registers it."""
    from cpzk_tpu.server import metrics

    metrics.counter("audit.gc.collections", labelnames=("generation",))


def _pass(tracer, status: str, collections: list) -> None:
    """One ``audit.run`` trace: per quantum an ``audit.quantum`` parent
    that settled a whole quantum and an ``audit.gc`` span of each listed
    number of seconds."""
    from cpzk_tpu.observability.context import RequestContext, new_trace_id

    tid = new_trace_id()
    tracer.start(RequestContext(trace_id=tid), "audit.run")
    for i, secs in enumerate(collections):
        for s in secs:
            tracer.add_span(tid, "audit.gc", 0.0, s, generation=2,
                            collected=0)
        tracer.add_span(tid, "audit.quantum", 0.0, 1.0, quantum=i,
                        records=QUANTUM, settled=QUANTUM)
    tracer.finish(tid, status)


def _read():
    return harness.load_module("metrics", NAME).read({})


def test_gc_reader_is_none_for_a_program_without_the_counter(
        tracer, uncounted):
    assert _read() is None
    _pass(tracer, "complete", [[], []])
    assert _read() is None


def test_gc_reader_sums_the_collections_of_complete_passes(tracer):
    _counted()
    assert _read() is None  # no pass yet
    _pass(tracer, "complete", [[], []])
    assert _read() == 0.0  # counted, and none ran
    _pass(tracer, "complete", [[0.1], [0.05, 0.15]])
    _pass(tracer, "checkpointed", [[5.0]])  # the warm-up quantum: not read
    assert _read() == pytest.approx(1e6 * 0.3 / (4 * QUANTUM))
