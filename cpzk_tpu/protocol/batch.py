"""Batch verification — re-designed from reference ``src/verifier/batch.rs``.

API parity: ``BatchVerifier`` accumulates up to ``MAX_BATCH_SIZE`` entries of
(params, statement, proof, context), validating statements on ``add``
(batch.rs:139-168); ``verify`` returns per-proof results, short-circuiting a
single-entry batch to individual verification (batch.rs:171-183) and falling
back to per-proof verification when the combined check fails
(batch.rs:314-318) — so the *accept set* is always per-proof ground truth.
After a batch that held a reject, the next batch on the same backend goes
to the per-proof checks directly (:class:`CombinedGate`).

Math fix (normative deviation, SURVEY.md §3.2): the reference's combined
equation drops the random coefficient on the ``y^c`` term
(batch.rs:297-299), which makes its fast path fail for every n ≥ 2 batch and
silently degrade to per-proof verification. We implement the correct
random-linear-combination check

    Σ αᵢ·(sᵢ·G − r1ᵢ − cᵢ·y1ᵢ)  +  β·Σ αᵢ·(sᵢ·H − r2ᵢ − cᵢ·y2ᵢ)  ==  O

with per-entry random αᵢ and one extra random weight β merging the two
equations (soundness: Schwartz-Zippel over ℓ; per-equation failure
probability ≤ 2/ℓ). Observable accept/reject semantics are identical to the
reference because its fallback already defines acceptance per-proof.

The heavy lifting is delegated to a pluggable ``VerifierBackend``:
``CpuBackend`` (host oracle, default) or the TPU/JAX backend in
:mod:`cpzk_tpu.ops.backend` (one big vectorized pass; see BASELINE.json
north star).
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, field

from ..errors import Error, InvalidParams, InvalidProofEncoding
from ..core import edwards
from ..core.ristretto import Element, Ristretto255, Scalar
from ..core.rng import SecureRng
from ..core.scalars import L, sc_mul
from ..core.transcript import Transcript
from .gadgets import Parameters, Proof, Statement
from .verifier import Verifier

MAX_BATCH_SIZE = 1000


class _NullStages:
    """Inert stage recorder: ``BatchVerifier.verify`` always runs under a
    stage scope, instrumented or not (the real recorder lives in
    :mod:`cpzk_tpu.observability.tracing` — this layer stays import-free
    of it)."""

    def stage(self, name: str):
        del name
        return contextlib.nullcontext()


_NULL_STAGES = _NullStages()


@dataclass
class BatchEntry:
    params: Parameters
    statement: Statement
    proof: Proof
    transcript_context: bytes | None
    #: absolute ``time.monotonic()`` point after which nobody is waiting for
    #: this entry's result (the RPC deadline, threaded through the serving
    #: layer); ``None`` = wait forever.  The dynamic batcher sheds expired
    #: entries before device dispatch instead of verifying them.
    deadline: float | None = None
    #: trace id of the RPC that queued this entry (observability subsystem);
    #: the batcher fans per-stage spans out to every member trace.
    trace_id: str | None = None
    #: ``time.monotonic()`` at enqueue, stamped by the batcher — the
    #: ``queue_wait`` span/histogram measures from here to dispatch.
    enqueued_at: float | None = None


@dataclass
class BatchRow:
    """Flattened, challenge-resolved entry handed to a backend."""

    g: Element
    h: Element
    y1: Element
    y2: Element
    r1: Element
    r2: Element
    s: Scalar
    c: Scalar
    alpha: Scalar


@dataclass
class PreparedBatch:
    """Host-phase output of :meth:`BatchVerifier.prepare_batch` — the
    challenge-resolved rows (or the n == 1 verifier, or the deferred-parse
    splice plan) ready for backend dispatch via
    :meth:`BatchVerifier.run_prepared`.  Built on one thread, consumable
    on another: nothing here touches the backend or the RNG."""

    n: int
    # n == 1 individual-verification path
    entry: BatchEntry | None = None
    verifier: object | None = None      # protocol.verifier.Verifier
    transcript: Transcript | None = None
    # n >= 2 batch path
    rows: list[BatchRow] | None = None
    beta: Scalar | None = None
    same_generators: bool = True
    # deferred-parse splice path: undecodable wires mapped to their parse
    # errors; survivors prepared as a sub-batch
    pre_errors: dict[int, Error] | None = None
    sub: "BatchVerifier | None" = None
    sub_prepared: "PreparedBatch | None" = field(default=None, repr=False)


class CombinedGate:
    """Check order for one backend: whether the next batch runs the
    combined RLC check before the per-row checks.

    Rule, with no parameters: run it first iff the previous batch
    verified on this backend came out all-valid.  A passed combined
    check leaves the gate open.  Otherwise ``verify_each`` runs (after a
    failed combined check, or in place of a skipped one) and its
    statuses decide: any invalid row (status 0 or 2) closes the gate,
    an all-valid result opens it, since that batch's combined check
    would have passed.  The gate starts open, so the first batch and an
    all-valid stream run combined-first.  Verdicts never depend on it:
    whenever the combined check does not pass, the per-row statuses
    decide acceptance (batch.rs:314-318); a closed gate only drops a
    check that a reject-bearing stream keeps failing.

    Concurrent ``run_prepared`` calls on one backend (the pipelined
    batcher) may race on ``open``.  The race is benign: the worst case
    is one extra or one missing combined check, never another verdict,
    so no lock is taken."""

    __slots__ = ("open",)

    def __init__(self) -> None:
        self.open = True

    def settle(self, accepted: bool | None, statuses) -> None:
        """After a batch: ``accepted`` is the combined check's outcome
        (None: not run), ``statuses`` the per-row ones when they ran."""
        self.open = bool(accepted) or all(s == 1 for s in statuses)


def _count_combined(accepted: bool | None) -> None:
    """``batch.combined{outcome=accepted|rejected|skipped}``: one per
    multi-row batch that could take the combined check.  Metrics live in
    the server layer; this layer stays importable without it."""
    try:
        from ..server import metrics
    except ImportError:  # pragma: no cover - server layer unavailable
        return
    outcome = ("skipped" if accepted is None
               else "accepted" if accepted else "rejected")
    metrics.counter("batch.combined", labelnames=("outcome",)).labels(
        outcome=outcome).inc()


class VerifierBackend:
    """Backend interface for the batch-verification compute plane.

    Thread-safety contract: the serving layer's pipelined batcher
    (``DynamicBatcher(pipeline_depth>1)``) calls ``verify_combined`` /
    ``verify_each`` for DIFFERENT batches concurrently from worker
    threads.  Implementations must tolerate that — keep per-call state on
    the stack and guard any shared caches (see ``TpuBackend._gh``)."""

    #: Whether the combined RLC fast path is actually faster than per-proof
    #: checks on this backend. False for the scalar CPU oracle (4n+2 muls vs
    #: 4n, and a failed combined check pays both passes); True for vectorized
    #: backends where the combined check amortizes.
    prefers_combined: bool = True

    #: Whether ``verify_each`` reports a deferred-parse proof's commitment
    #: decode failure tri-state (row status 2) instead of crashing or
    #: conflating it with a verification failure.  When False, the
    #: dispatcher eagerly screens deferred proofs before involving the
    #: backend, so backends never see an undecodable wire.
    supports_deferred_decode: bool = False

    @property
    def combined_gate(self) -> CombinedGate:
        """This instance's :class:`CombinedGate`, made on first use; it
        outlives the per-batch ``BatchVerifier`` objects."""
        gate = self.__dict__.get("_combined_gate")
        if gate is None:
            gate = self.__dict__.setdefault("_combined_gate", CombinedGate())
        return gate

    def verify_combined(self, rows: list[BatchRow], beta: Scalar) -> bool:
        """Corrected-RLC combined check; True iff the whole batch passes."""
        raise NotImplementedError

    def verify_each(self, rows: list[BatchRow]) -> list[int]:
        """Per-proof ground-truth checks (the accept-set decider).
        Per-row status: 1/True = pass, 0/False = fail, 2 = commitment wire
        failed to decode (deferred-parse rows only)."""
        raise NotImplementedError


class CpuBackend(VerifierBackend):
    """Host-plane backend over the integer-exact core (the oracle)."""

    prefers_combined = False
    supports_deferred_decode = True  # native rows report status 2

    def verify_combined(self, rows: list[BatchRow], beta: Scalar) -> bool:
        acc = edwards.IDENTITY
        sum_as = 0  # Σ αᵢ·sᵢ mod ℓ
        for row in rows:
            a = row.alpha.value
            ac = sc_mul(a, row.c.value)
            sum_as = (sum_as + a * row.s.value) % L
            # subtract αᵢ·r1ᵢ + (αᵢcᵢ)·y1ᵢ + β·(αᵢ·r2ᵢ + (αᵢcᵢ)·y2ᵢ)
            term = edwards.pt_add(
                edwards.pt_scalar_mul(row.r1.point, a),
                edwards.pt_scalar_mul(row.y1.point, ac),
            )
            term2 = edwards.pt_add(
                edwards.pt_scalar_mul(row.r2.point, sc_mul(a, beta.value)),
                edwards.pt_scalar_mul(row.y2.point, sc_mul(ac, beta.value)),
            )
            acc = edwards.pt_add(acc, edwards.pt_add(term, term2))
        # add (Σαs)·G + β(Σαs)·H — valid only when all rows share generators;
        # the dispatcher (BatchVerifier.verify) only takes this fast path in
        # that case and sends mixed-generator batches to verify_each.
        g = rows[0].g.point
        h = rows[0].h.point
        lhs = edwards.pt_add(
            edwards.pt_scalar_mul(g, sum_as),
            edwards.pt_scalar_mul(h, sc_mul(sum_as, beta.value)),
        )
        return edwards.pt_eq(lhs, acc)

    def verify_each(self, rows: list[BatchRow]) -> list[int]:
        native = self._verify_each_native(rows)
        if native is not None:
            return native
        out: list[int] = []
        for row in rows:
            try:
                r1p, r2p = row.r1.point, row.r2.point
            except Error:
                # deferred-parse wire that fails to decode (tri-state twin
                # of the native path's status 2)
                out.append(2)
                continue
            lhs1 = edwards.pt_scalar_mul(row.g.point, row.s.value)
            rhs1 = edwards.pt_add(r1p, edwards.pt_scalar_mul(row.y1.point, row.c.value))
            lhs2 = edwards.pt_scalar_mul(row.h.point, row.s.value)
            rhs2 = edwards.pt_add(r2p, edwards.pt_scalar_mul(row.y2.point, row.c.value))
            out.append(int(edwards.pt_eq(lhs1, rhs1) and edwards.pt_eq(lhs2, rhs2)))
        return out

    @staticmethod
    def _verify_each_native(rows: list[BatchRow]) -> list[int] | None:
        """Threaded C++ row verification (native/ristretto.cpp) when the
        library is loadable and the batch shares one generator pair; None
        routes the caller to the pure-Python oracle.  Statuses per the
        ``verify_each`` contract: 1 pass, 0 fail, 2 commitment-decode
        failure (NOT truthy-pass — deferred rows only)."""
        if not rows:
            return []
        if not all(r.g == rows[0].g and r.h == rows[0].h for r in rows):
            return None
        from ..core import _native

        eb = Ristretto255.element_to_bytes
        sb = Ristretto255.scalar_to_bytes
        return _native.verify_rows(
            eb(rows[0].g),
            eb(rows[0].h),
            b"".join(eb(r.y1) for r in rows),
            b"".join(eb(r.y2) for r in rows),
            b"".join(eb(r.r1) for r in rows),
            b"".join(eb(r.r2) for r in rows),
            b"".join(sb(r.s) for r in rows),
            b"".join(sb(r.c) for r in rows),
        )


class FailoverBackend(VerifierBackend):
    """Self-healing TPU→CPU failover wrapper (SURVEY.md §5 failure
    detection + resilience subsystem circuit breaker).

    Routes to ``primary`` until it raises, then degrades to ``fallback``
    — a failed combined check simply reports False so the dispatcher's
    per-proof path decides, keeping accept/reject semantics byte-identical
    through a mid-batch backend loss.  Unlike the old one-way latch,
    degradation heals: after ``recovery_after_s`` the breaker grants a
    single *probe* — one batch is verified on BOTH planes, the fallback
    result stays authoritative, and the primary is re-armed only when its
    answers match ground truth exactly (a device that comes back *wrong*
    never regains traffic).  ``recovery_after_s=None`` restores the
    permanent-until-``reset()`` behavior.

    Observability: ``tpu.backend.failover`` counts CLOSED→OPEN trips,
    ``tpu.backend.state`` gauges the breaker (0 closed / 1 open / 2
    half-open), ``tpu.backend.degraded_seconds`` accumulates CPU-only
    wall time, and each transition logs WARNING exactly once.
    """

    def __init__(
        self,
        primary: VerifierBackend,
        fallback: VerifierBackend,
        recovery_after_s: float | None = 30.0,
        probe_batch_max: int = 64,
        clock=None,
    ):
        import time as _time

        from ..resilience.breaker import BreakerState, CircuitBreaker

        if probe_batch_max < 1:
            raise InvalidParams("probe_batch_max must be positive")
        self.primary = primary
        self.fallback = fallback
        self.probe_batch_max = probe_batch_max
        self._closed = BreakerState.CLOSED
        self.breaker = CircuitBreaker(
            recovery_after_s=recovery_after_s,
            clock=clock or _time.monotonic,
            on_transition=self._on_transition,
        )

    @property
    def degraded(self) -> bool:
        """True while traffic is (at least partly) on the fallback."""
        return self.breaker.state is not self._closed

    @property
    def state(self):
        """Breaker state, for the admin REPL ``/status`` line."""
        return self.breaker.state

    @property
    def prefers_combined(self) -> bool:  # type: ignore[override]
        backend = self.fallback if self.degraded else self.primary
        return backend.prefers_combined

    def reset(self) -> None:
        """Operator re-arm (bypasses the probe — trust the fix)."""
        self.breaker.reset()

    # -- transitions / observability --------------------------------------

    def _on_transition(self, old, new) -> None:
        import logging

        from ..resilience.breaker import BreakerState

        log = logging.getLogger("cpzk_tpu.protocol.batch")
        if new is BreakerState.OPEN and old is BreakerState.CLOSED:
            log.warning(
                "primary verifier backend failed; degrading to fallback "
                "(probe retry in %ss)", self.breaker.recovery_after_s,
            )
        elif new is BreakerState.OPEN:
            log.warning(
                "primary verifier probe failed or disagreed with fallback "
                "ground truth; staying degraded (next probe in %ss)",
                self.breaker.recovery_after_s,
            )
        elif new is BreakerState.HALF_OPEN:
            log.info("probing primary verifier backend with one batch")
        else:  # -> CLOSED
            log.warning(
                "primary verifier backend recovered after %.1fs degraded; "
                "traffic back on primary", self.breaker.degraded_seconds,
            )
        try:  # metrics live in the server layer; optional here
            from ..server import metrics

            if new is BreakerState.OPEN and old is BreakerState.CLOSED:
                metrics.counter("tpu.backend.failover").inc()
            metrics.gauge("tpu.backend.state").set(
                {"closed": 0, "open": 1, "half-open": 2}[new.value]
            )
        except Exception:
            pass
        try:  # transition also lands in the trace ring buffer, so degraded
            # periods share the /tracez timeline with the requests they hit
            from ..observability import get_tracer

            get_tracer().record_event(
                "breaker_transition", old=old.value, new=new.value,
            )
        except Exception:
            pass

    def _touch_degraded_gauge(self) -> None:
        try:
            from ..server import metrics

            metrics.gauge("tpu.backend.degraded_seconds").set(
                self.breaker.degraded_seconds
            )
        except Exception:
            pass

    def _note_failure(self, exc: Exception) -> None:
        # pipelined dispatches call backends from multiple threads; the
        # breaker hands the CLOSED->OPEN transition to exactly one of them
        # (transition logging/metrics live in _on_transition; the device
        # exception itself is only worth one traceback, not one per batch)
        if self.breaker.record_failure():
            import logging

            logging.getLogger("cpzk_tpu.protocol.batch").warning(
                "primary verifier backend raised", exc_info=exc
            )

    # -- verification routing ----------------------------------------------

    def verify_combined(self, rows: list[BatchRow], beta: Scalar) -> bool:
        self._touch_degraded_gauge()
        route = self.breaker.acquire()
        if route == "primary":
            try:
                return self.primary.verify_combined(rows, beta)
            except Exception as exc:
                self._note_failure(exc)
        elif route == "probe":
            # a combined check has no per-row ground truth to compare the
            # probe against; hand the token back so the dispatcher's
            # verify_each pass (or the next batch) runs the real probe
            self.breaker.release_probe()
        # a False combined check routes the dispatcher to verify_each,
        # which is the ground-truth path on the fallback backend
        if self.fallback.prefers_combined:
            return self.fallback.verify_combined(rows, beta)
        return False

    def verify_each(self, rows: list[BatchRow]) -> list[int]:
        self._touch_degraded_gauge()
        route = self.breaker.acquire()
        if route == "primary":
            try:
                return self.primary.verify_each(rows)
            except Exception as exc:
                self._note_failure(exc)
            return self.fallback.verify_each(rows)
        if route == "probe":
            return self._probe_each(rows)
        return self.fallback.verify_each(rows)

    def _probe_each(self, rows: list[BatchRow]) -> list[int]:
        """Half-open probe: fallback verifies the whole batch (its result
        is returned — authoritative no matter what the primary says); the
        primary re-verifies the first ``probe_batch_max`` rows and must
        reproduce ground truth exactly to re-close the breaker."""
        import logging

        truth = self.fallback.verify_each(rows)
        n = min(len(rows), self.probe_batch_max)
        if n == 0:
            self.breaker.release_probe()
            return truth
        try:
            probe = self.primary.verify_each(rows[:n])
            agreed = [int(v) for v in probe] == [int(v) for v in truth[:n]]
        except Exception as exc:
            logging.getLogger("cpzk_tpu.protocol.batch").warning(
                "primary verifier probe raised: %s", exc
            )
            agreed = False
        if agreed:
            self.breaker.probe_succeeded()
        else:
            self.breaker.probe_failed()
        self._touch_degraded_gauge()
        return truth


_DEFAULT_BACKEND: VerifierBackend | None = None


def default_backend() -> VerifierBackend:
    """Process-wide default backend (CPU oracle unless overridden)."""
    global _DEFAULT_BACKEND
    if _DEFAULT_BACKEND is None:
        _DEFAULT_BACKEND = CpuBackend()
    return _DEFAULT_BACKEND


def set_default_backend(backend: VerifierBackend | None) -> None:
    global _DEFAULT_BACKEND
    _DEFAULT_BACKEND = backend


class BatchVerifier:
    """Accumulate-and-verify batch API (reference ``BatchVerifier`` twin).

    ``max_size`` defaults to the reference's 1000-entry cap (parity for the
    gRPC per-request surface) but is configurable up to device scale — the
    TPU backend amortizes best at 64k+ rows (SURVEY.md §7.5), where the
    reference's O(n) host loop had no reason to go."""

    def __init__(
        self,
        backend: VerifierBackend | None = None,
        max_size: int = MAX_BATCH_SIZE,
    ):
        if max_size < 1:
            raise InvalidParams("Batch capacity must be positive")
        self.entries: list[BatchEntry] = []
        self.max_size = max_size
        self._backend = backend

    @staticmethod
    def with_capacity(capacity: int, backend: VerifierBackend | None = None) -> "BatchVerifier":
        """Capacity is clamped to MAX_BATCH_SIZE (batch.rs:107-117); Python
        lists need no preallocation, so this is a naming-parity constructor."""
        if capacity < 0:
            raise InvalidParams("Capacity cannot be negative")
        return BatchVerifier(backend)

    def __len__(self) -> int:
        return len(self.entries)

    def is_empty(self) -> bool:
        return not self.entries

    def remaining_capacity(self) -> int:
        return max(0, self.max_size - len(self.entries))

    def clear(self) -> None:
        """Empty the batch for reuse (reference BatchVerifier::clear)."""
        self.entries.clear()

    def add(self, params: Parameters, statement: Statement, proof: Proof) -> None:
        self.add_with_context(params, statement, proof, None)

    def add_with_context(
        self,
        params: Parameters,
        statement: Statement,
        proof: Proof,
        context: bytes | None,
    ) -> None:
        """Validates the statement on add (batch.rs:139-168)."""
        if len(self.entries) >= self.max_size:
            raise InvalidParams(f"Batch size limit exceeded (max {self.max_size})")
        statement.validate()
        self.entries.append(BatchEntry(params, statement, proof, context))

    # --- verification ---

    @property
    def backend(self) -> VerifierBackend:
        """The backend this batch will verify on (explicit or default)."""
        return self._backend or default_backend()

    def prepare_rows(self, rng: SecureRng) -> list[BatchRow]:
        """Derive the backend-facing rows: per-entry Fiat-Shamir challenge
        (batched transcript derivation) plus a fresh random RLC weight
        alpha per row.  Public seam for benchmarks and drivers that time
        or shard the backend stage directly (``verify`` composes this
        with the combined-check/fallback policy)."""
        from ..core.transcript import derive_challenges_batch

        challenges = derive_challenges_batch(
            [e.transcript_context for e in self.entries],
            [Ristretto255.element_to_bytes(e.params.generator_g) for e in self.entries],
            [Ristretto255.element_to_bytes(e.params.generator_h) for e in self.entries],
            [Ristretto255.element_to_bytes(e.statement.y1) for e in self.entries],
            [Ristretto255.element_to_bytes(e.statement.y2) for e in self.entries],
            [Ristretto255.element_to_bytes(e.proof.commitment.r1) for e in self.entries],
            [Ristretto255.element_to_bytes(e.proof.commitment.r2) for e in self.entries],
        )
        # RLC coefficients from one pooled CSPRNG draw: a per-row
        # random_scalar() is a getrandom(2) syscall each, which at device
        # batch sizes costs more host time than the wide reductions
        alphas = Ristretto255.random_scalars(rng, len(self.entries))
        rows = []
        for entry, c, alpha in zip(
            self.entries, challenges, alphas, strict=True
        ):
            rows.append(
                BatchRow(
                    g=entry.params.generator_g,
                    h=entry.params.generator_h,
                    y1=entry.statement.y1,
                    y2=entry.statement.y2,
                    r1=entry.proof.commitment.r1,
                    r2=entry.proof.commitment.r2,
                    s=entry.proof.response.s,
                    c=c,
                    alpha=alpha,
                )
            )
        return rows

    def verify(self, rng: SecureRng, stages=None) -> list[Error | None]:
        """Verify all entries; per-entry ``None`` (ok) or ``Error``.

        Mirrors batch.rs:171-183: empty batch is an error; n == 1 verifies
        individually; otherwise the combined check decides the fast path and
        failure falls back to per-proof results.

        ``stages`` is an optional stage recorder (duck-typed like
        :class:`cpzk_tpu.observability.BatchStages`): host prep is timed
        under ``pad_and_pack``, the backend call(s) under
        ``device_dispatch``, and result assembly under ``unpack`` — the
        latency-breakdown seam the serving layer's traces report through.

        Deferred-parse proofs (see :meth:`Proof.from_bytes_batch`) settle
        their postponed commitment decodes here: backends that report
        decode failures tri-state handle them in the same pass as
        verification; otherwise (and always for n == 1 or the combined
        fast path) they are screened eagerly first, so every path yields
        the exact eager-parse error for an undecodable wire.

        Composes :meth:`prepare_batch` (host phase) with
        :meth:`run_prepared` (device phase) — the two-phase seam the
        serving layer's dispatch lane uses to overlap batch N+1's host
        prep with batch N's device compute.  Calling ``verify`` runs both
        phases back-to-back on the current thread.
        """
        st = stages if stages is not None else _NULL_STAGES
        return self.run_prepared(self.prepare_batch(rng, st), st)

    def prepare_batch(self, rng: SecureRng, stages=None) -> "PreparedBatch":
        """Host phase: deferred-parse screening, Fiat-Shamir challenge
        derivation, RLC coefficient draws, and (n == 1) verifier/transcript
        construction — everything that does not touch the backend.  Timed
        under the ``pad_and_pack`` stage.  The returned
        :class:`PreparedBatch` is consumed by :meth:`run_prepared`, on the
        same thread or another one (the dispatch lane's device thread)."""
        if not self.entries:
            raise InvalidParams("Cannot verify empty batch")
        st = stages if stages is not None else _NULL_STAGES
        n = len(self.entries)
        backend = self.backend
        # one pad_and_pack bracket covers the WHOLE host phase — the
        # generator-equality / deferred scans, screening, and row build —
        # so the flight record's stage sum tiles its wall on every path
        with st.stage("pad_and_pack"):
            same_generators = all(
                e.params.generator_g == self.entries[0].params.generator_g
                and e.params.generator_h == self.entries[0].params.generator_h
                for e in self.entries
            )
            has_deferred = any(e.proof.deferred for e in self.entries)
            if has_deferred and (
                n == 1
                or not same_generators
                or not backend.supports_deferred_decode
                or backend.prefers_combined
            ):
                pre_errors = self._screen_deferred()
                if pre_errors:
                    # keep undecodable wires away from the backend:
                    # prepare the survivors as their own batch (null
                    # recorder — this bracket covers their host phase;
                    # run_prepared brackets their device phase);
                    # run_prepared splices results around the errors
                    sub = BatchVerifier(backend=self._backend,
                                        max_size=max(self.max_size, 1))
                    sub.entries = [e for i, e in enumerate(self.entries)
                                   if i not in pre_errors]
                    sub_prepared = (
                        sub.prepare_batch(rng) if sub.entries else None
                    )
                    return PreparedBatch(
                        n=n, pre_errors=pre_errors, sub=sub,
                        sub_prepared=sub_prepared,
                    )

            if n == 1:
                # single-entry batches keep the same stage decomposition
                # so a trace through a lightly-loaded batcher still
                # breaks down
                entry = self.entries[0]
                transcript = Transcript()
                if entry.transcript_context is not None:
                    transcript.append_context(entry.transcript_context)
                verifier = Verifier(entry.params, entry.statement)
                return PreparedBatch(
                    n=1, entry=entry, verifier=verifier,
                    transcript=transcript,
                )

            rows = self.prepare_rows(rng)
            beta = Ristretto255.random_scalar(rng)
        return PreparedBatch(
            n=n, rows=rows, beta=beta, same_generators=same_generators,
        )

    def run_prepared(
        self, prepared: "PreparedBatch", stages=None
    ) -> list[Error | None]:
        """Device phase: backend dispatch (``device_dispatch`` stage) and
        result assembly (``unpack``) for a :meth:`prepare_batch` output.
        Accept/reject semantics are identical to :meth:`verify` — the
        split changes WHERE the phases run, never what they compute.
        The backend's :class:`CombinedGate` decides whether a multi-row
        batch tries the combined check before the per-row checks."""
        st = stages if stages is not None else _NULL_STAGES
        backend = self.backend

        if prepared.pre_errors is not None:
            # the sub-batch's device phase records into THIS batch's
            # stage recorder, so the splice path keeps the full
            # decomposition (and the stage-sum≈wall invariant)
            sub_results = (
                prepared.sub.run_prepared(prepared.sub_prepared, st)
                if prepared.sub is not None and prepared.sub_prepared is not None
                else []
            )
            results: list[Error | None] = []
            k = 0
            for i in range(prepared.n):
                if i in prepared.pre_errors:
                    results.append(prepared.pre_errors[i])
                else:
                    results.append(sub_results[k])
                    k += 1
            return results

        if prepared.n == 1:
            entry = prepared.entry
            with st.stage("device_dispatch"):
                try:
                    prepared.verifier.verify_with_transcript(
                        entry.proof, prepared.transcript
                    )
                    result: Error | None = None
                except Error as exc:
                    result = exc
            with st.stage("unpack"):
                return [result]

        rows, beta = prepared.rows, prepared.beta
        gate = backend.combined_gate
        with st.stage("device_dispatch"):
            accepted = None  # the combined check's outcome; None: not run
            if prepared.same_generators and backend.prefers_combined:
                if gate.open:
                    accepted = backend.verify_combined(rows, beta)
                _count_combined(accepted)
            # Fallback: per-proof ground truth (batch.rs:314-318)
            statuses = None if accepted else backend.verify_each(rows)
            gate.settle(accepted, statuses)
        with st.stage("unpack"):
            if statuses is None:
                return [None] * len(rows)
            results = []
            for ok in statuses:
                if ok == 2:  # deferred commitment wire failed to decode
                    results.append(InvalidProofEncoding(
                        "Bytes do not represent a valid Ristretto point"))
                elif ok:
                    results.append(None)
                else:
                    results.append(InvalidParams("Proof verification failed"))
            return results

    def _screen_deferred(self) -> dict[int, Error]:
        """Settle deferred proofs' postponed point decodes eagerly: one
        native deep parse over just the deferred wires.  Survivors are
        promoted to fully-validated (``deferred`` cleared, elements marked
        canonical); failures map to the exact eager-parse error."""
        idxs = [i for i, e in enumerate(self.entries) if e.proof.deferred]
        out: dict[int, Error] = {}
        if not idxs:
            return out
        from ..core import _native

        packed = b"".join(self.entries[i].proof.to_bytes() for i in idxs)
        flags = _native.parse_proofs(packed)  # deep: includes the decodes
        for j, i in enumerate(idxs):
            proof = self.entries[i].proof
            if flags is not None:
                ok = bool(flags[j])
            else:  # no native core: settle through the Python decoder
                try:
                    _ = proof.commitment.r1.point
                    _ = proof.commitment.r2.point
                    ok = True
                except Error:
                    ok = False
            if ok:
                proof.deferred = False
                proof.commitment.r1._validated = True
                proof.commitment.r2._validated = True
            else:
                out[i] = InvalidProofEncoding(
                    "Bytes do not represent a valid Ristretto point")
        return out
