"""The dispatch seam's check order (``protocol/batch.py`` ``CombinedGate``).

A backend that prefers the combined RLC check runs it first only while
the previous batch it verified came out all-valid; after a batch that
held a reject it goes straight to the per-row checks, until an all-valid
batch reopens the gate.  Verdicts never depend on the order: they are
compared here with the order before the gate (combined first on every
batch), on mixed sequences that include an undecodable deferred wire.
The backends run the host oracle with the device backend's preference
(``prefers_combined``) and its flight-record report."""

import sys
import threading

import pytest

from cpzk_tpu import Parameters, Prover, Ristretto255, SecureRng, Transcript, Witness
from cpzk_tpu.errors import InvalidProofEncoding
from cpzk_tpu.observability import flightrec
from cpzk_tpu.observability.tracing import BatchStages
from cpzk_tpu.protocol.batch import (
    BatchVerifier,
    CombinedGate,
    CpuBackend,
    FailoverBackend,
    PreparedBatch,
    VerifierBackend,
)
from cpzk_tpu.protocol.gadgets import Proof
from cpzk_tpu.server import metrics

ROWS = 4
OUTCOMES = ("accepted", "rejected", "skipped")


class TpuLike(CpuBackend):
    """The host oracle with the device backend's check order: prefers the
    combined check, reports its outcome to the flight recorder, and logs
    which check each call ran."""

    prefers_combined = True

    def __init__(self):
        self.calls: list[str] = []
        self.fail_each = False

    def verify_combined(self, rows, beta):
        self.calls.append("combined")
        ok = super().verify_combined(rows, beta)
        flightrec.note_combined(ok)
        return ok

    def verify_each(self, rows):
        self.calls.append("each")
        if self.fail_each:
            raise RuntimeError("injected device loss")
        return super().verify_each(rows)


class GateLess(TpuLike):
    """The order before the gate: a fresh (open) gate for every batch."""

    @property
    def combined_gate(self):
        return CombinedGate()


class Scripted(VerifierBackend):
    """Rows are their own per-row statuses: the combined check passes iff
    every row is 1, ``verify_each`` returns the rows."""

    def verify_combined(self, rows, beta):
        return all(r == 1 for r in rows)

    def verify_each(self, rows):
        return list(rows)


@pytest.fixture(scope="module")
def corpus():
    """(params, statement, wire, context) per row position."""
    rng = SecureRng()
    params = Parameters.new()
    out = []
    for i in range(ROWS):
        prover = Prover(params, Witness(Ristretto255.random_scalar(rng)))
        ctx = f"ctx-{i}".encode()
        t = Transcript()
        t.append_context(ctx)
        wire = prover.prove_with_transcript(rng, t).to_bytes()
        out.append((params, prover.statement, wire, ctx))
    return out


def _batch(backend, kinds, corpus) -> BatchVerifier:
    """One batch: ``ok`` a valid proof, ``bad`` a proof checked under the
    wrong context, ``undecodable`` a deferred wire whose r1 is no point."""
    bv = BatchVerifier(backend=backend)
    for kind, (params, stmt, wire, ctx) in zip(kinds, corpus, strict=True):
        if kind == "undecodable":
            wire = wire[:5] + b"\xff" * 32 + wire[37:]
        (proof,) = Proof.from_bytes_batch([wire], defer_point_validation=True)
        assert isinstance(proof, Proof)
        bv.add_with_context(params, stmt, proof,
                            b"wrong" if kind == "bad" else ctx)
    return bv


def _verdicts(results) -> list[str]:
    return ["ok" if r is None else f"{type(r).__name__}: {r}" for r in results]


def _counted() -> dict[str, float]:
    return {o: metrics.read("batch.combined", labels={"outcome": o})
            for o in OUTCOMES}


def _delta(before: dict[str, float]) -> dict[str, float]:
    after = _counted()
    return {o: after[o] - before[o] for o in OUTCOMES}


OK = ("ok",) * ROWS
BAD = ("ok", "bad", "ok", "ok")


def test_reject_bearing_batches_run_the_combined_check_once(corpus):
    backend = TpuLike()
    rng = SecureRng()
    before = _counted()
    for _ in range(3):
        results = _batch(backend, BAD, corpus).verify(rng)
        assert [r is None for r in results] == [True, False, True, True]
    assert backend.calls == ["combined", "each", "each", "each"]
    assert _delta(before) == {"accepted": 0, "rejected": 1, "skipped": 2}
    assert backend.combined_gate.open is False


def test_an_all_valid_batch_reopens_the_gate(corpus):
    backend = TpuLike()
    rng = SecureRng()
    for kinds in (BAD, OK, OK):
        _batch(backend, kinds, corpus).verify(rng)
    # the skipped all-valid batch reopened it: the next one runs combined
    assert backend.calls == ["combined", "each", "each", "combined"]
    assert backend.combined_gate.open is True


def test_an_all_valid_stream_runs_combined_on_every_batch(corpus):
    backend = TpuLike()
    rng = SecureRng()
    before = _counted()
    for _ in range(3):
        assert _batch(backend, OK, corpus).verify(rng) == [None] * ROWS
    assert backend.calls == ["combined"] * 3
    assert _delta(before) == {"accepted": 3, "rejected": 0, "skipped": 0}


@pytest.mark.parametrize("sequence", [
    (BAD, OK, ("bad", "bad", "ok", "ok"), OK, OK),
    (("undecodable", "ok", "ok", "ok"), BAD, ("ok", "ok", "ok", "undecodable"),
     OK, BAD),
    (("bad", "ok", "ok", "ok"), ("ok", "undecodable", "bad", "ok"), OK, OK,
     ("ok", "ok", "ok", "bad")),
    (OK, ("undecodable",) * 2 + ("ok",) * 2, OK, BAD, BAD, OK),
], ids=["rejects", "undecodable-first", "mixed", "all-valid-survivors"])
def test_verdicts_equal_the_gate_less_order(corpus, sequence):
    gated, gateless = TpuLike(), GateLess()
    rng = SecureRng()
    for kinds in sequence:
        got = _verdicts(_batch(gated, kinds, corpus).verify(rng))
        want = _verdicts(_batch(gateless, kinds, corpus).verify(rng))
        assert got == want, kinds
        for kind, v in zip(kinds, got):
            assert (v == "ok") == (kind == "ok"), (kinds, got)
            if kind == "undecodable":
                assert v.startswith(InvalidProofEncoding.__name__)
    # the gate only ever drops combined checks, never adds one
    assert gated.calls.count("combined") <= gateless.calls.count("combined")


def test_counter_and_flight_record_show_a_skip_as_not_run(corpus):
    backend = TpuLike()
    rng = SecureRng()
    before = _counted()
    records = []
    for kinds in (BAD, OK, OK, BAD):
        bv = _batch(backend, kinds, corpus)
        stages = BatchStages(None, [], batch_size=ROWS)
        bv.verify(rng, stages)
        records.append(stages.finalize(0.0).combined)
    assert records == [False, None, True, False]
    assert _delta(before) == {"accepted": 1, "rejected": 2, "skipped": 1}


@pytest.mark.parametrize("accepted,statuses,is_open", [
    (True, None, True),
    (False, [1, 1, True], True),
    (None, [1, 1, 1], True),
    (False, [1, 0, 1], False),
    (None, [1, False, 1], False),
    (None, [1, 2, 1], False),
])
def test_gate_rule(accepted, statuses, is_open):
    gate = CombinedGate()
    gate.open = not is_open
    gate.settle(accepted, statuses)
    assert gate.open is is_open


def test_a_deferred_decode_status_closes_the_gate():
    """A status-2 row from ``verify_each`` (an undecodable deferred wire
    settled on the backend) is a reject: it closes the gate."""
    backend = Scripted()
    before = _counted()
    bv = BatchVerifier(backend=backend)
    out = bv.run_prepared(PreparedBatch(n=3, rows=[1, 2, 1]))
    assert out[0] is None and out[2] is None
    assert isinstance(out[1], InvalidProofEncoding)
    assert backend.combined_gate.open is False
    assert bv.run_prepared(PreparedBatch(n=2, rows=[1, 1])) == [None, None]
    assert _delta(before) == {"accepted": 0, "rejected": 1, "skipped": 1}


def test_backends_without_the_combined_preference_count_nothing(corpus):
    rng = SecureRng()
    before = _counted()
    for kinds in (BAD, OK):
        _batch(CpuBackend(), kinds, corpus).verify(rng)
    assert _delta(before) == {o: 0 for o in OUTCOMES}


def test_failover_under_a_closed_gate(corpus):
    """With the gate closed the failover wrapper sees ``verify_each``
    calls only: a primary that raises there degrades to the fallback,
    and the half-open probe runs through ``verify_each`` and re-arms it."""
    now = [0.0]
    primary = TpuLike()
    backend = FailoverBackend(primary, CpuBackend(), recovery_after_s=5.0,
                              clock=lambda: now[0])
    rng = SecureRng()
    want = [True, False, True, True]
    assert [r is None for r in _batch(backend, BAD, corpus).verify(rng)] == want
    assert backend.combined_gate.open is False and not backend.degraded

    primary.fail_each = True
    assert [r is None for r in _batch(backend, BAD, corpus).verify(rng)] == want
    assert backend.degraded
    assert primary.calls == ["combined", "each", "each"]

    primary.fail_each = False
    now[0] = 10.0  # past recovery_after_s: the next batch is the probe
    assert [r is None for r in _batch(backend, BAD, corpus).verify(rng)] == want
    assert not backend.degraded
    # the probe re-verified on the primary through verify_each alone
    assert primary.calls == ["combined", "each", "each", "each"]


def test_concurrent_batches_on_one_gate_keep_their_verdicts():
    """Threads racing on one backend's gate: every verdict is its batch's
    own, and each batch is counted once."""
    backend = Scripted()
    before = _counted()
    errors = []
    per_thread, threads = 200, 16

    def work(k: int) -> None:
        for i in range(per_thread):
            rows = [1, 1, 1] if (i + k) % 3 else [1, 0, 2]
            out = BatchVerifier(backend=backend).run_prepared(
                PreparedBatch(n=3, rows=rows))
            got = [r is None for r in out]
            if got != [r == 1 for r in rows]:
                errors.append((rows, got))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work, args=(k,)) for k in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(old)
    assert errors == []
    assert sum(_delta(before).values()) == per_thread * threads
