"""Bulk offline audit: replay a proof log through the batch engine.

``python -m cpzk_tpu.audit run`` turns the serving plane's proof log
(:mod:`cpzk_tpu.audit.log`) back into TPU-sized work: records stream
through :class:`~cpzk_tpu.protocol.batch.BatchVerifier` split into the
SAME two phases the serving plane's
:class:`~cpzk_tpu.server.dispatch.DispatchLane` runs on its two threads
(``prepare_batch`` on the host, ``run_prepared`` on the device) at a full
batch quantum per dispatch — and through the
:mod:`~cpzk_tpu.parallel.mesh`-sharded TPU backend when more than one
device is visible — then emits a Schnorr-signed report
(:mod:`cpzk_tpu.audit.sign`) stating what it found.

Overlap (the single engine, ``lanes == 1``): one worker thread per
:func:`run_audit` call runs a quantum's device phase (marshal, the
combined check, the per-row fallback, unpack) while the calling thread
decodes, parses and ``prepare_batch``-es the next quantum; it then waits
for the worker, submits the prepared quantum, and only then folds,
checkpoints and reports ``progress`` for the finished one.  At most one
quantum is on the worker and at most one more is prepared.  The native
core and JAX's device waits release the GIL, which is what overlaps.
``max_batches=k`` never prepares quantum k+1; the worker is shut down
before :func:`run_audit` returns or raises.  The ``lanes != 1`` router
path has its own concurrency and runs its quanta serially.

Resumability contract (the SIGKILL test pins it exactly):

- After every quantum the pipeline atomically checkpoints a **cursor**
  (byte offset, last sequence number, running totals, running transcript
  digest) via write-to-temp + rename — a crash leaves either the old or
  the new cursor, never a torn one.
- The running digest is a SHA-256 chain folded over every record IN
  ORDER (canonical record JSON + the audit outcome byte), so a resumed
  run recomputes the identical digest — and because report signing is
  deterministic (:func:`cpzk_tpu.audit.sign._nonce`), a run that is
  SIGKILLed at ANY point and resumed produces a byte-exact-identical
  signed report to an uninterrupted run.
- The fold runs in record order on the calling thread; the overlap only
  moves work in time.  A failure in quantum k — in its host prep or its
  device phase — is raised with its own type and leaves the cursor after
  quantum k-1, as a serial loop would.

Audit semantics per record:

- frame fails CRC/parse/sequence rules -> the scan stops (WAL prefix
  contract); everything before the violation is still audited and the
  report carries the valid byte count.
- record parses but is not a well-formed ``proof`` record (unknown type,
  missing/oversized/non-hex fields, bad statement encoding) ->
  **skipped**, never handed to the backend.
- proof wire malformed -> **rejected** (an invalid proof is a
  verification outcome, exactly as the serving path answers it).
- otherwise the batch engine decides: **verified** or **rejected**; a
  computed verdict that contradicts the recorded one increments
  **mismatched** (the number an auditor actually cares about).

Tracing: each :func:`run_audit` call is one ``audit.run`` trace in the
process's tracer (``/tracez``), finished ``complete``, ``checkpointed``
(``max_batches`` stopped it) or ``failure``.  Its spans: ``audit.open``
(cursor, log read, scan, backend build), per quantum ``audit.decode``,
``audit.parse``, the dispatch seam's ``BatchStages`` spans
(``pad_and_pack`` on the caller; ``device_wait``, the prepared quantum's
dwell until the worker takes it, then ``device_dispatch`` with
``marshal``/``compile``/``execute`` — under a mesh also the ``mesh.*``
spans of :mod:`~cpzk_tpu.parallel.mesh` — and ``unpack`` on the worker),
``audit.wait`` (the caller blocked on the quantum's device phase after
the next quantum is prepared: long when the worker's chain paces the
replay, near 0 when the host prep does), ``audit.fold`` and
``audit.checkpoint``, then ``audit.report``.  The ``audit.*`` spans
carry ``quantum``/``records`` attrs.  One ``audit.quantum`` parent per
quantum carries ``quantum``, ``records`` and ``settled``; it runs from
the end of the previous quantum's ``progress`` call (the first: from the
loop's start) to the end of its own, so the parents tile the caller's
timeline and each is the wall time one quantum's settling took.  Under
overlap quantum N's parent holds quantum N+1's decode, parse and
``pad_and_pack``, then N's wait, fold, checkpoint and ``progress``; the
worker's spans run beside the caller's, so the stage spans sum to more
than the wall, by the overlap.  Every
stage span is also a ``cpzk.<name>`` profiler annotation; the parents,
``audit.wait`` and the ``mesh.*`` spans are not, so no annotation
encloses another on a thread and idle gaps are labelled by the stage
holding the device back.
Each single-engine dispatch books one flight record (``/flightrec``;
``lanes != 1`` replays through the router, whose dispatch is neither
spanned nor recorded), and ``audit.records{outcome}`` counts records
once per quantum.  None of it touches a verdict, the fold order or the
report.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import time
from collections.abc import Callable
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from functools import partial

from .. import errors
from ..core.ristretto import Ristretto255
from ..core.rng import SecureRng
from ..observability.context import RequestContext, new_trace_id
from ..observability.tracing import BatchStages, get_tracer
from ..protocol.batch import BatchEntry, BatchVerifier, PreparedBatch
from ..protocol.gadgets import Parameters, Proof, Statement
from ..server import metrics
from ..server.dispatch import _run_instrumented
from .log import scan_records, validate_proof_record
from .sign import load_or_create_key, sign_report

SCHEMA = "cpzk-audit-report/1"
CURSOR_SCHEMA = "cpzk-audit-cursor/1"
DEFAULT_QUANTUM = 4096

#: Audit outcome bytes folded into the digest chain (one per record, in
#: record order) — part of the signed transcript, so a tampered log that
#: still parses but audits differently changes the digest.
OUTCOME_VERIFIED = b"V"
OUTCOME_REJECTED = b"R"
OUTCOME_SKIPPED = b"S"

_ZERO_CHAIN = "0" * 64


def _fold(chain_hex: str, rec: dict, outcome: bytes) -> str:
    h = hashlib.sha256()
    h.update(bytes.fromhex(chain_hex))
    h.update(json.dumps(rec, separators=(",", ":"), sort_keys=True).encode())
    h.update(outcome)
    return h.hexdigest()


class AuditState:
    """Running totals + digest chain — everything the cursor persists.

    Pure fold state: :meth:`note` consumes records in order with their
    audit outcomes; the fuzz harness drives it directly (no crypto) to
    hold the monotonicity/consistency invariants."""

    def __init__(self):
        self.offset = 0
        self.prev_seq: int | None = None
        self.first_seq: int | None = None
        self.records = 0
        self.verified = 0
        self.rejected = 0
        self.mismatched = 0
        self.skipped = 0
        self.chain = _ZERO_CHAIN

    @property
    def audited(self) -> int:
        return self.verified + self.rejected

    def note(self, rec: dict, outcome: bytes, mismatch: bool = False) -> None:
        self.records += 1
        seq = rec.get("seq")
        if isinstance(seq, int) and not isinstance(seq, bool):
            if self.first_seq is None:
                self.first_seq = seq
            self.prev_seq = seq
        if outcome == OUTCOME_VERIFIED:
            self.verified += 1
        elif outcome == OUTCOME_REJECTED:
            self.rejected += 1
        else:
            self.skipped += 1
        if mismatch:
            self.mismatched += 1
        self.chain = _fold(self.chain, rec, outcome)

    # -- cursor (de)serialization -------------------------------------------

    def to_cursor(self, log_path: str) -> dict:
        return {
            "schema": CURSOR_SCHEMA,
            "log_path": os.path.basename(log_path),
            "offset": self.offset,
            "prev_seq": self.prev_seq,
            "first_seq": self.first_seq,
            "records": self.records,
            "verified": self.verified,
            "rejected": self.rejected,
            "mismatched": self.mismatched,
            "skipped": self.skipped,
            "chain": self.chain,
        }

    @classmethod
    def from_cursor(cls, cur: dict, log_path: str) -> "AuditState":
        if cur.get("schema") != CURSOR_SCHEMA:
            raise ValueError(f"unknown cursor schema: {cur.get('schema')!r}")
        if cur.get("log_path") != os.path.basename(log_path):
            raise ValueError(
                f"cursor belongs to {cur.get('log_path')!r}, "
                f"not {os.path.basename(log_path)!r}"
            )
        st = cls()
        st.offset = int(cur["offset"])
        st.prev_seq = cur["prev_seq"]
        st.first_seq = cur["first_seq"]
        st.records = int(cur["records"])
        st.verified = int(cur["verified"])
        st.rejected = int(cur["rejected"])
        st.mismatched = int(cur["mismatched"])
        st.skipped = int(cur["skipped"])
        chain = str(cur["chain"])
        bytes.fromhex(chain)  # ValueError on a tampered cursor
        if len(chain) != 64:
            raise ValueError("cursor chain must be 32 hex bytes")
        st.chain = chain
        return st


def _atomic_write_json(path: str, obj: dict) -> None:
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(prefix="." + os.path.basename(path) + ".", dir=d)
    try:
        payload = json.dumps(obj, separators=(",", ":"), sort_keys=True)
        os.write(fd, payload.encode() + b"\n")
        os.fsync(fd)
        os.close(fd)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.close(fd)
        except OSError:
            pass
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def log_files(log_path: str) -> list[str]:
    """The ordered file list of one logical proof log: the path itself,
    or — when it is a rotated-segment **directory** — every sealed
    ``*.seg`` file in name order (zero-padded names sort in sequence
    order) followed by the active log file(s) they rotated out of.
    Sequence numbers strictly increase across that concatenation, so the
    WAL prefix scan treats it as one log."""
    if not os.path.isdir(log_path):
        return [log_path]
    from .log import _SEG_RE

    names = sorted(os.listdir(log_path))
    segs = [n for n in names if _SEG_RE.search(n)]
    bases: list[str] = []
    for n in segs:
        base = _SEG_RE.sub("", n)
        if base not in bases:
            bases.append(base)
    files = [os.path.join(log_path, n) for n in segs]
    files += [
        os.path.join(log_path, b) for b in sorted(bases)
        if os.path.isfile(os.path.join(log_path, b))
    ]
    if not files:
        raise ValueError(
            f"{log_path} is a directory with no proof-log segments "
            "(*.seg) in it"
        )
    return files


def _read_log_bytes(log_path: str) -> bytes:
    parts = []
    for path in log_files(log_path):
        with open(path, "rb") as f:
            parts.append(f.read())
    return b"".join(parts)


def build_backend(backend_name: str, mesh_devices: int = 0):
    """The audit compute plane: the CPU oracle, or the mesh-sharded TPU
    backend (``mesh_devices`` semantics shared with serving: 0 = all
    visible devices — :func:`cpzk_tpu.parallel.mesh.resolve_mesh_devices`
    decides whether a real mesh is built).  A mesh's compiled programs
    are the process's, so the backend each call builds finds what an
    earlier call or ``TpuBackend.prewarm`` compiled."""
    if backend_name == "tpu":
        from ..ops.backend import TpuBackend

        return TpuBackend(mesh_devices=mesh_devices)
    from ..protocol.batch import CpuBackend

    return CpuBackend()


def build_router(backend_name: str, lanes: int, quantum: int):
    """The audit pipeline's multi-lane compute plane — the SAME
    :class:`~cpzk_tpu.server.router.LaneRouter` the serving daemon
    places batches on, attached via its synchronous seam
    (``verify_blocking``): each quantum fans out across every lane, so
    a bulk replay is the first consumer that can saturate all chips.

    ``lanes`` semantics match ``[tpu] lanes``: 1 = no router (the
    single-engine path), -1 = one lane per local device (tpu backend) or
    per host core (cpu backend), k = exactly k lanes.  Returns None when
    one lane resolves — the caller keeps the direct ``verify_once``
    path.  The per-lane prewarm runs here (tpu backend) so the replay's
    first quantum per lane books jit HITs like serving traffic."""
    if lanes == 1:
        return None
    from ..server.router import LaneRouter

    if backend_name == "tpu":
        from ..ops.backend import TpuBackend, prewarm_executables
        from ..parallel import resolve_lane_devices

        devices = resolve_lane_devices(lanes)
        if devices is None:
            return None
        prewarm_executables([quantum], devices=devices)
        return LaneRouter(
            [TpuBackend(device=d) for d in devices], devices=devices,
        )
    from ..protocol.batch import CpuBackend

    n = lanes if lanes > 0 else (os.cpu_count() or 1)
    if n <= 1:
        return None
    # CPU lanes: the native verify releases the GIL, so N lanes = real
    # host-core parallelism through the identical router seam
    return LaneRouter([CpuBackend() for _ in range(n)])


def _record_entry(rec: dict) -> tuple[BatchEntry | None, str | None]:
    """(entry, skip_reason): decode one validated proof record into a
    batch entry, or say why it cannot be audited.  A proof wire that
    parses as *malformed proof* is NOT a skip — the caller maps it to a
    rejected outcome via the entry-less ``(None, None)`` convention plus
    ``rec['_parse_error']``."""
    reason = validate_proof_record(rec)
    if reason is not None:
        return None, reason
    try:
        y1 = Ristretto255.element_from_bytes(bytes.fromhex(rec["y1"]))
        y2 = Ristretto255.element_from_bytes(bytes.fromhex(rec["y2"]))
        statement = Statement(y1, y2)
        statement.validate()
        if Ristretto255.is_identity(y1) or Ristretto255.is_identity(y2):
            return None, "bad-statement"
    except errors.Error:
        return None, "bad-statement"
    return (
        BatchEntry(
            Parameters.new(), statement,
            None,  # type: ignore[arg-type]  # proof attached after bulk parse
            bytes.fromhex(rec["ctx"]),
        ),
        None,
    )


def run_audit(
    log_path: str,
    report_path: str,
    cursor_path: str | None = None,
    key_path: str | None = None,
    quantum: int = DEFAULT_QUANTUM,
    backend: str = "cpu",
    mesh_devices: int = 0,
    lanes: int = 1,
    resume: bool = True,
    max_batches: int | None = None,
    progress=None,
) -> dict | None:
    """Replay ``log_path`` through the batch engine and write a signed
    report to ``report_path``.  Returns the report dict, or ``None`` when
    ``max_batches`` stopped the run early (checkpoint saved — rerun with
    ``resume=True`` to continue; the test harness uses this to model a
    SIGKILL between checkpoints).

    ``cursor_path`` defaults to ``<report_path>.cursor``; ``key_path``
    defaults to ``<report_path>.key`` (minted 0600 when absent).

    ``log_path`` may be a **rotated-segment directory** (a log written
    with ``[audit] segment_bytes`` — or a standby's shipped copy): the
    sealed ``*.seg`` files plus the active tail replay as one logical
    log, cursor offsets indexing into their concatenation (stable:
    sealing only renames bytes in place within the order).

    ``lanes != 1`` replays through the serving plane's
    :class:`~cpzk_tpu.server.router.LaneRouter` — each quantum fans out
    across every per-device lane concurrently.  Outcomes fold into the
    digest chain in record order regardless of which lane computed them,
    so the signed report is byte-identical to a single-lane run
    (test-pinned).
    """
    if quantum < 1:
        raise ValueError("audit quantum must be positive")
    cursor_path = cursor_path or report_path + ".cursor"
    key_path = key_path or report_path + ".key"
    tracer = get_tracer()
    trace_id = new_trace_id()
    tracer.start(RequestContext(trace_id=trace_id), "audit.run")
    status = "failure"
    try:
        with tracer.span(trace_id, "audit.open") as attrs:
            state = AuditState()
            if resume and os.path.exists(cursor_path):
                with open(cursor_path, encoding="utf-8") as f:
                    state = AuditState.from_cursor(json.load(f), log_path)

            buf = _read_log_bytes(log_path)
            if state.offset > len(buf):
                raise ValueError(
                    f"cursor offset {state.offset} is beyond the log "
                    f"({len(buf)} bytes) — wrong log file?"
                )

            router = build_router(backend, lanes, quantum)
            engine = None if router is not None else build_backend(
                backend, mesh_devices=mesh_devices
            )
            # ONE scan of the remaining suffix (the parse cost is linear in
            # what is left, not quadratic in batch count); quanta then slice
            # the parsed records, with the cursor offset advanced frame-wise
            records, valid = scan_records(
                buf, offset=state.offset, prev_seq=state.prev_seq
            )
            attrs["records"] = len(records)
            if router is not None:
                router.start_in_thread()
        rng = SecureRng()
        quanta = [records[lo:lo + quantum]
                  for lo in range(0, len(records), quantum)]
        stop = len(quanta)
        if max_batches is not None:
            stop = min(stop, max(max_batches, 1))

        def prepare(i: int) -> _Quantum:
            return _prepare_quantum(quanta[i], i, engine, rng, router,
                                    trace_id, backend)

        settled_at = time.monotonic()

        def finish(q: _Quantum, results: list) -> None:
            nonlocal settled_at
            audited = state.audited
            _fold_quantum(q, results, state, trace_id)
            with tracer.span(trace_id, "audit.checkpoint",
                             quantum=q.index, records=len(q.records)):
                state.offset = _advance(buf, state.offset, len(q.records))
                _atomic_write_json(cursor_path, state.to_cursor(log_path))
            if progress is not None:
                progress(state)
            now = time.monotonic()
            tracer.add_span(trace_id, "audit.quantum", settled_at,
                            now - settled_at, quantum=q.index,
                            records=len(q.records),
                            settled=state.audited - audited)
            settled_at = now

        # the single engine's device phase runs on one worker thread, so
        # the caller prepares quantum N+1 while quantum N is on the device;
        # the router has its own concurrency, and its quanta run serially
        worker = None if router is not None else ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="cpzk-audit-device")
        try:
            if worker is None:
                for i in range(stop):
                    q = prepare(i)
                    finish(q, q.device_phase())
            elif stop:
                _overlapped(prepare, finish, stop, worker, trace_id)
        finally:
            if router is not None:
                router.stop_thread()
            if worker is not None:
                worker.shutdown(wait=True)
        if stop < len(quanta):
            status = "checkpointed"
            return None

        with tracer.span(trace_id, "audit.report", records=state.records):
            state.offset = max(state.offset, valid)
            report = _build_report(
                log_path, state, valid_bytes=state.offset,
                file_bytes=len(buf), backend=backend, quantum=quantum,
            )
            sign_report(report, load_or_create_key(key_path))
            _atomic_write_json(report_path, report)
            # the run is complete: the cursor has served its purpose
            # (keeping it would make a LATER run against an appended-to
            # log resume silently)
            try:
                os.unlink(cursor_path)
            except OSError:
                pass
        status = "complete"
        return report
    finally:
        tracer.finish(trace_id, status)


def _advance(buf: bytes, offset: int, n_frames: int) -> int:
    """Byte offset after ``n_frames`` well-formed frames from ``offset``
    (frame sizes only — the frames were already validated this scan)."""
    from ..durability.wal import _HEADER, HEADER_BYTES

    off = offset
    for _ in range(n_frames):
        length, _crc = _HEADER.unpack_from(buf, off)
        off += HEADER_BYTES + length
    return off


@dataclass
class _Quantum:
    """One quantum between its host prep and its fold."""

    index: int
    records: list[dict]
    plan: list[tuple[dict, str | None, bool]]  # (rec, skip, parse_fail)
    #: the device phase: per-entry results of the live entries, in order
    device_phase: Callable[[], list] | None

    def hand_over(self) -> Callable[[], list]:
        """The device phase, taken out of the quantum: the worker that runs
        it holds the batch's last reference and frees the batch on its own
        thread as the phase ends, not on the caller's between two
        quanta's spans."""
        phase, self.device_phase = self.device_phase, None
        return phase


def _overlapped(prepare, finish, stop: int, worker, trace_id: str) -> None:
    """Quanta ``0 .. stop-1`` with one device phase in flight on
    ``worker``: submit N, prepare N+1, wait for N, submit N+1, then fold
    and checkpoint N.  A failure in N+1's host prep is raised once N is
    checkpointed, a failure in N's device phase before N folds: either
    way the cursor stands where the serial loop would leave it."""
    tracer = get_tracer()
    q = prepare(0)
    running = worker.submit(q.hand_over())
    for i in range(stop):
        nxt = failed = None
        if i + 1 < stop:
            try:
                nxt = prepare(i + 1)
            except Exception as exc:  # re-raised below, after N's checkpoint
                failed = exc
        with tracer.span(trace_id, "audit.wait", annotate=False,
                         quantum=i, records=len(q.records)):
            results = running.result()
        if nxt is not None:
            running = worker.submit(nxt.hand_over())
        finish(q, results)
        if failed is not None:
            raise failed
        q = nxt


def _device_phase(
    bv: BatchVerifier, prepared: PreparedBatch, stages: BatchStages,
    t0: float,
) -> list:
    """A quantum's dispatch after its ``prepare_batch``: marshal, the
    combined check, the per-row fallback and unpack, then its flight
    record (``t0``: when its ``pad_and_pack`` began)."""
    stages.mark_device_start()
    results = _run_instrumented(bv, prepared, stages)
    stages.finalize(time.monotonic() - t0)
    return results


def _prepare_quantum(
    records: list[dict], index: int, engine, rng, router,
    trace_id: str, backend: str,
) -> _Quantum:
    """The host prep of one quantum on the calling thread: record decode,
    bulk proof parse and — for the single engine — ``prepare_batch``, the
    only use of ``rng``.  Its device phase is the engine's
    ``run_prepared`` or the lane router's synchronous fan-out
    (``verify_blocking``).  The stages are spans of quantum ``index`` on
    the ``trace_id`` trace."""
    tracer = get_tracer()

    def span(name: str):
        return tracer.span(trace_id, name, quantum=index, records=len(records))

    with span("audit.decode"):
        entries: list[BatchEntry] = []
        plan: list[tuple[dict, str | None, bool]] = []  # (rec, skip, parse_fail)
        wires: list[bytes] = []
        for rec in records:
            entry, skip = _record_entry(rec)
            if skip is not None:
                plan.append((rec, skip, False))
                continue
            wires.append(bytes.fromhex(rec["p"]))
            entries.append(entry)
            plan.append((rec, None, False))
    with span("audit.parse"):
        # bulk proof parse (deferred point decodes settle inside the batch
        # engine with exact eager-parse semantics, like the serving path)
        parsed = Proof.from_bytes_batch(wires, defer_point_validation=True)
        live: list[BatchEntry] = []
        k = 0
        for i, (rec, skip, _) in enumerate(plan):
            if skip is not None:
                continue
            proof = parsed[k]
            entry = entries[k]
            k += 1
            if isinstance(proof, errors.Error):
                plan[i] = (rec, None, True)  # malformed proof -> rejected
                continue
            entry.proof = proof
            live.append(entry)
    if not live:
        phase = list  # nothing to dispatch: no results
    elif router is not None:
        phase = partial(router.verify_blocking, live)
    else:
        stages = BatchStages(tracer, [trace_id], batch_size=len(live),
                             backend_label=backend)
        t0 = time.monotonic()
        bv = BatchVerifier(backend=engine, max_size=len(live))
        bv.entries.extend(live)
        prepared = bv.prepare_batch(rng, stages)
        stages.mark_staged()
        phase = partial(_device_phase, bv, prepared, stages, t0)
    return _Quantum(index, records, plan, phase)


def _fold_quantum(
    q: _Quantum, results: list, state: AuditState, trace_id: str,
) -> None:
    """Fold a quantum's outcomes into ``state`` IN RECORD ORDER (lane
    placement and the worker never reorder the fold)."""
    before = (state.verified, state.rejected, state.skipped)
    with get_tracer().span(trace_id, "audit.fold", quantum=q.index,
                           records=len(q.records)):
        it = iter(results)
        for rec, skip, parse_fail in q.plan:
            if skip is not None:
                state.note(rec, OUTCOME_SKIPPED)
                continue
            if parse_fail:
                computed = False
            else:
                computed = next(it) is None
            outcome = OUTCOME_VERIFIED if computed else OUTCOME_REJECTED
            mismatch = bool(rec.get("v", 0)) != computed
            state.note(rec, outcome, mismatch=mismatch)
        counted = metrics.counter("audit.records", labelnames=("outcome",))
        after = (state.verified, state.rejected, state.skipped)
        for outcome, b, a in zip(("verified", "rejected", "skipped"),
                                 before, after):
            if a > b:
                counted.labels(outcome=outcome).inc(a - b)


def _build_report(
    log_path: str,
    state: AuditState,
    valid_bytes: int,
    file_bytes: int,
    backend: str,
    quantum: int,
) -> dict:
    """The deterministic (pre-signature) report body: no wall-clock
    timestamps, no absolute paths — two runs over the same log bytes
    produce the same bytes here, which is what makes SIGKILL-resume
    equivalence byte-exact."""
    return {
        "schema": SCHEMA,
        "log": {
            "name": os.path.basename(log_path),
            "valid_bytes": valid_bytes,
            "file_bytes": file_bytes,
            "first_seq": state.first_seq,
            "last_seq": state.prev_seq,
        },
        "engine": {"backend": backend, "quantum": quantum},
        "totals": {
            "records": state.records,
            "audited": state.audited,
            "verified": state.verified,
            "rejected": state.rejected,
            "mismatched": state.mismatched,
            "skipped": state.skipped,
        },
        "digest": state.chain,
    }


def verify_report_file(report_path: str) -> tuple[bool, str, dict | None]:
    """Offline ``--verify-report``: ``(ok, reason, report)``.  Total over
    arbitrary files — a tampered report answers False, never raises."""
    from .sign import verify_report

    try:
        with open(report_path, encoding="utf-8") as f:
            report = json.load(f)
    except (OSError, ValueError) as e:
        return False, f"unreadable report: {e}", None
    if not isinstance(report, dict):
        return False, "report is not a JSON object", None
    if report.get("schema") != SCHEMA:
        return False, f"unknown report schema: {report.get('schema')!r}", report
    ok, reason = verify_report(report)
    return ok, reason, report
