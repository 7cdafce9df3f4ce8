"""The audit replay with the cyclic collector paused from the log scan to
the last checkpoint: ``run_audit`` leaves the collector as it found it on
every exit, a quantum leaves no cyclic garbage (so memory stays flat
through a pass), the signed report is byte-identical with the collector
running, and each collection that still runs during a replay shows as an
``audit.gc`` span and an ``audit.gc.collections{generation}`` count."""

import gc
import random
import types

import pytest

from cpzk_tpu import Parameters, Prover, SecureRng, Transcript, Witness
from cpzk_tpu.audit import pipeline, run_audit
from cpzk_tpu.audit.log import ProofLogWriter, proof_record
from cpzk_tpu.core.ristretto import Ristretto255
from cpzk_tpu.observability import get_tracer
from cpzk_tpu.protocol.batch import CpuBackend
from cpzk_tpu.server import metrics

QUANTUM = 16
SEED = 2**31 + 29


class _Boom(RuntimeError):
    pass


class _DeviceFault(CpuBackend):
    """Raises in the device phase of the second quantum (each quantum
    holds a wrong secret, so each dispatch reaches ``verify_each``)."""

    def __init__(self):
        super().__init__()
        self.calls = 0

    def verify_each(self, rows):
        self.calls += 1
        if self.calls == 2:
            raise _Boom("device phase")
        return super().verify_each(rows)


def _write_log(path, quanta: int, quantum: int = QUANTUM) -> str:
    """``quanta`` quanta of proof records over 4 statements, each quantum
    holding one proof made with a wrong secret at the same slot: every
    quantum takes the same dispatch path after the first."""
    rng = SecureRng()
    params = Parameters.new()
    provers = [Prover(params, Witness(Ristretto255.random_scalar(rng)))
               for _ in range(4)]
    eb = Ristretto255.element_to_bytes
    wrong = random.Random(SEED).randrange(quantum)
    records = []
    for i in range(quanta * quantum):
        owner = provers[i % 4]
        signer = provers[(i + 1) % 4] if i % quantum == wrong else owner
        ctx = rng.fill_bytes(32)
        t = Transcript()
        t.append_context(ctx)
        wire = signer.prove_with_transcript(rng, t).to_bytes()
        records.append(proof_record(
            f"u{i % 4}", eb(owner.statement.y1), eb(owner.statement.y2),
            ctx, wire, signer is owner))
    writer = ProofLogWriter(str(path), fsync="off")
    writer.append_proofs(records)
    writer.close()
    return str(path)


@pytest.fixture(scope="module")
def proof_log(tmp_path_factory):
    return _write_log(tmp_path_factory.mktemp("audit-gc") / "proofs.log", 4)


@pytest.fixture
def collector():
    """The collector's state and thresholds as the test found them, put
    back afterwards whatever the test did to them."""
    enabled, thresholds = gc.isenabled(), gc.get_threshold()
    yield
    gc.set_threshold(*thresholds)
    (gc.enable if enabled else gc.disable)()


def _failing(case: str, monkeypatch) -> None:
    """Plant the failure of ``case``: in the second quantum's device
    phase, or in the log scan itself, just after the pause begins."""
    if case == "raises-mid-loop":
        monkeypatch.setattr(pipeline, "build_backend",
                            lambda *a, **kw: _DeviceFault())
    else:
        def scan(*a, **kw):
            raise _Boom("scan")

        monkeypatch.setattr(pipeline, "scan_records", scan)


@pytest.mark.parametrize(
    "case", ["enabled", "disabled", "raises-mid-loop", "raises-in-scan"])
def test_run_audit_leaves_the_collector_as_it_found_it(
        tmp_path, monkeypatch, collector, proof_log, case):
    inside = []  # the collector's state at each quantum's progress call
    if case == "disabled":
        gc.disable()
    else:
        gc.enable()
    if case.startswith("raises"):
        _failing(case, monkeypatch)
        with pytest.raises(_Boom):
            run_audit(proof_log, str(tmp_path / "r.json"), quantum=QUANTUM,
                      progress=lambda st: inside.append(gc.isenabled()))
    else:
        run_audit(proof_log, str(tmp_path / "r.json"), quantum=QUANTUM,
                  progress=lambda st: inside.append(gc.isenabled()))
    assert gc.isenabled() == (case != "disabled")
    # paused in the loop; the scan failure stops the run before it
    assert inside == ([] if case == "raises-in-scan"
                      else [False] * (1 if case == "raises-mid-loop" else 4))


@pytest.mark.parametrize(
    "backend,lanes,mesh_devices,quantum",
    [("cpu", 1, 0, 64), ("cpu", 3, 0, 64), ("tpu", 1, 0, 8),
     ("tpu", 1, 1, 8)],
    ids=["cpu", "cpu-router", "tpu", "tpu-one-device"])
def test_a_quantum_leaves_no_cyclic_garbage(tmp_path, collector, backend,
                                            lanes, mesh_devices, quantum):
    """With the collector held off through whole passes, ``gc.collect()``
    after a pass of 2 quanta finds exactly what it finds after one of 8:
    reference counting frees every quantum, and what is left is the
    pass's own (ctypes array types, one per native buffer size, which
    ctypes caches weakly).  ``tpu`` is the device backend on XLA's CPU,
    sharded over every visible device; ``tpu-one-device`` the same
    backend on one device, as a one-chip host runs it; ``cpu-router``
    the ``lanes != 1`` path through the lane router."""
    logs = {n: _write_log(tmp_path / f"p{n}.log", n, quantum) for n in (2, 8)}
    kw = dict(quantum=quantum, backend=backend, lanes=lanes,
              mesh_devices=mesh_devices)
    # the first pass compiles and fills the process's caches
    run_audit(logs[2], str(tmp_path / "warm.json"), **kw)
    gc.collect()
    gc.disable()
    found = {}
    for n in (2, 8, 2):
        run_audit(logs[n], str(tmp_path / f"r{n}.json"), **kw)
        found.setdefault(n, set()).add(gc.collect())
    assert found[2] == found[8] and len(found[2]) == 1, found


def test_report_is_byte_identical_with_the_collector_running(
        tmp_path, monkeypatch, collector, proof_log):
    """The pause as shipped, and the pause made a no-op with the
    collector firing every few allocations: the same signed bytes."""
    key = str(tmp_path / "audit.key")
    paused = tmp_path / "paused.json"
    run_audit(proof_log, str(paused), key_path=key, quantum=QUANTUM)

    def nothing():
        pass

    running = types.SimpleNamespace(
        isenabled=gc.isenabled, enable=gc.enable, disable=nothing,
        callbacks=gc.callbacks)
    monkeypatch.setattr(pipeline, "gc", running)
    gc.enable()
    gc.set_threshold(50, 2, 2)
    inside = []
    earlier = get_tracer().completed()
    on = tmp_path / "on.json"
    run_audit(proof_log, str(on), key_path=key, quantum=QUANTUM,
              progress=lambda st: inside.append(gc.isenabled()))
    assert inside == [True] * 4
    (trace,) = [t for t in get_tracer().completed()
                if t.name == "audit.run" and t not in earlier]
    assert "audit.gc" in trace.span_names()  # it did fire in the loop
    assert on.read_bytes() == paused.read_bytes()


def test_collections_during_the_run_are_spans_and_counts(
        tmp_path, collector, proof_log):
    """A collection forced from each quantum's progress call runs inside
    the pause: each is one ``audit.gc`` span of the run's trace, with its
    generation and what it collected, and one count of its generation."""
    generations = ("0", "1", "2")

    def counted():
        return {g: metrics.read("audit.gc.collections",
                                labels={"generation": g})
                for g in generations}

    before = counted()
    earlier = get_tracer().completed()
    run_audit(proof_log, str(tmp_path / "r.json"), quantum=QUANTUM,
              progress=lambda st: gc.collect())
    delta = {g: n - before[g] for g, n in counted().items()}
    (trace,) = [t for t in get_tracer().completed()
                if t.name == "audit.run" and t not in earlier]
    spans = [s for s in trace.spans if s.name == "audit.gc"]
    assert sum(s.attrs["generation"] == 2 for s in spans) >= 4
    assert all(isinstance(s.attrs["collected"], int) and s.duration_s >= 0
               and trace.start <= s.start <= trace.start + trace.duration_s
               for s in spans)
    assert delta == {g: sum(str(s.attrs["generation"]) == g for s in spans)
                     for g in generations}


@pytest.mark.parametrize("case", ["complete", "raises-mid-loop",
                                  "raises-in-scan"])
def test_the_gc_hook_is_removed_after_the_run(
        tmp_path, monkeypatch, collector, proof_log, case):
    hooks = list(gc.callbacks)
    if case == "complete":
        assert run_audit(proof_log, str(tmp_path / "r.json"),
                         quantum=QUANTUM)
    else:
        _failing(case, monkeypatch)
        with pytest.raises(_Boom):
            run_audit(proof_log, str(tmp_path / "r.json"), quantum=QUANTUM)
    assert gc.callbacks == hooks
