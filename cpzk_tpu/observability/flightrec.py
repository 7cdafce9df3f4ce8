"""Device-plane flight recorder: per-batch dispatch accounting.

PR-2's four spans (``queue_wait``/``pad_and_pack``/``device_dispatch``/
``unpack``) tell an operator *that* the serving path starves the device,
not *why* — the 46x device-serving collapse (PROFILE.md §7c) hides
inside ``device_dispatch``, which conflates the ``asyncio.to_thread``
hop, host limb marshalling, first-sight XLA compiles, and actual device
execution.  This module is the always-on instrument that splits them:

- a :class:`DeviceSink` contextvar the backend reports into from the
  worker thread (``marshal`` seconds, jit cache hits/misses per padded
  shape, lane counts) without the backend ever importing the tracer;
- a :class:`FlightRecorder` ring of per-batch :class:`FlightRecord` rows
  — batch size, padded lanes, occupancy, pad waste, jit hit/miss, the
  widened stage breakdown, and **dispatch gap**: device idle time
  between consecutive dispatches, the direct measure of "serving
  starves the silicon";
- gauges/histograms on top (``tpu.device.busy_fraction``,
  ``tpu.batch.occupancy``, ``tpu.dispatch.gap``, ``tpu.jit.*``, a
  rolling proofs/s EWMA) plus a compile-storm WARNING when first-sight
  compiles exceed a threshold per window — the signature of a
  misconfigured padding schedule recompiling per batch size;
- an on-demand deep capture (``/profile``) wrapping
  ``jax.profiler.start_trace``/``stop_trace``, guarded against
  concurrent captures, whose timeline carries the same ``cpzk.<stage>``
  annotation names as the software spans.

Everything here is batch-shape metadata — no statement bytes, proofs,
or secrets ever enter a record, so dumps are safe to attach to bugs.

Thread-safety: records are built by batcher worker threads while the
REPL/SIGUSR2 read the ring from the event-loop thread; every ring and
window mutation is lock-guarded.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import threading
import time
from collections import deque
from dataclasses import dataclass, field

from ..server import metrics

log = logging.getLogger("cpzk_tpu.observability.flightrec")

#: JSON dump schema tag (bump on incompatible record changes).
SCHEMA = "cpzk-flightrec/1"

#: Stage vocabulary widening (the split of PR-2's ``device_dispatch``).
STAGE_THREAD_HOP = "thread_hop"
STAGE_DEVICE_WAIT = "device_wait"
STAGE_MARSHAL = "marshal"
STAGE_COMPILE = "compile"
STAGE_EXECUTE = "execute"

#: Stage keys of one flight record, dispatch order.  ``queue_wait`` is
#: carried separately (per-entry mean) — these tile the submit->resolve
#: wall time, which is the sum invariant the tests pin.  ``device_wait``
#: is the dispatch lane's staging-slot dwell: a host-prepared batch
#: waiting for the device thread to finish the previous batch (near the
#: previous batch's device time under double-buffered overlap, ~0 when
#: the device is the idle side).
RECORD_STAGES = (
    STAGE_THREAD_HOP,
    "pad_and_pack",
    STAGE_DEVICE_WAIT,
    STAGE_MARSHAL,
    STAGE_COMPILE,
    STAGE_EXECUTE,
    "unpack",
)


# -- device sink (backend -> recorder seam) -----------------------------------


@dataclass
class DeviceSink:
    """Per-batch accumulator the backend reports device-plane facts into.

    Installed (contextvar) by the stage recorder around the
    ``device_dispatch`` stage in the worker thread; the backend calls the
    module-level ``note_*`` helpers, which no-op when no sink is active
    (benches and direct ``BatchVerifier`` use stay zero-overhead)."""

    marshal_s: float = 0.0
    jit_hits: int = 0
    jit_misses: int = 0
    compiled: list[str] = field(default_factory=list)
    rows: int = 0
    lanes: int = 0
    combined: bool | None = None
    #: (name, start, seconds) of the backend's own sub-spans
    #: (:func:`device_span`), in the order they ended
    spans: list[tuple[str, float, float]] = field(default_factory=list)


_SINK: contextvars.ContextVar[DeviceSink | None] = contextvars.ContextVar(
    "cpzk_device_sink", default=None
)


def install_sink() -> tuple[DeviceSink, contextvars.Token]:
    sink = DeviceSink()
    return sink, _SINK.set(sink)


def uninstall_sink(token: contextvars.Token) -> None:
    _SINK.reset(token)


def note_marshal(duration_s: float) -> None:
    """Host SoA limb-marshal seconds within the current device dispatch."""
    sink = _SINK.get()
    if sink is not None:
        sink.marshal_s += max(0.0, duration_s)


@contextlib.contextmanager
def device_span(name: str):
    """Time the body as a sub-span of the current device dispatch (the
    stage recorder attaches it to the batch's traces beside ``marshal``
    and ``execute``).  No profiler annotation: ``device_dispatch`` holds
    one already.  Outside an instrumented dispatch it records nothing."""
    t0 = time.monotonic()
    try:
        yield
    finally:
        sink = _SINK.get()
        if sink is not None:
            sink.spans.append((name, t0, time.monotonic() - t0))


def note_jit(shape: str, first_sight: bool) -> None:
    """One jitted-program cache check: ``first_sight`` means this padded
    shape has never been dispatched by this process, so the call pays an
    XLA trace+compile (its cost is attributed to the ``compile`` stage)."""
    metrics.counter("tpu.jit.cache", labelnames=("outcome",)).labels(
        outcome="miss" if first_sight else "hit"
    ).inc()
    if first_sight:
        metrics.counter("tpu.jit.compiles", labelnames=("shape",)).labels(
            shape=shape
        ).inc()
        get_flight_recorder().note_compile_event(shape)
    sink = _SINK.get()
    if sink is not None:
        if first_sight:
            sink.jit_misses += 1
            sink.compiled.append(shape)
        else:
            sink.jit_hits += 1


def note_combined(accepted: bool) -> None:
    """Outcome of the batch's combined RLC check on the device: a reject
    sends the batch to per-row ``verify_each`` (twice the device work), so
    a combined check that rejects valid batches is invisible in verdicts
    and visible only here."""
    sink = _SINK.get()
    if sink is not None:
        sink.combined = accepted


def note_lanes(rows: int, lanes: int) -> None:
    """Padded device-lane accounting for the current dispatch: occupancy
    = true rows / padded lanes (the complement of ``tpu.batch.pad_waste``)."""
    if lanes > 0:
        metrics.gauge("tpu.batch.occupancy").set(rows / lanes)
    sink = _SINK.get()
    if sink is not None:
        sink.rows = rows
        sink.lanes = lanes


# -- flight records -----------------------------------------------------------


@dataclass
class FlightRecord:
    """One device batch through the batcher->backend seam."""

    seq: int = 0
    ts: float = 0.0            # wall clock at record time
    batch: int = 0             # true rows in the batch
    lane: int | str | None = None  # dispatch lane index ("mesh" for the
                               # big-batch mesh path; None = single-lane)
    lanes: int = 0             # padded device lanes (0 = no device padding)
    occupancy: float = 1.0     # batch / lanes (1.0 without device padding)
    pad_waste: float = 0.0     # 1 - occupancy
    backend: str = "cpu"
    queue_wait_s: float = 0.0  # mean over member entries
    stages_s: dict[str, float] = field(default_factory=dict)
    wall_s: float = 0.0        # dispatch commit -> results returned
    dispatch_gap_s: float = 0.0  # device idle before this dispatch
    jit_hits: int = 0
    jit_misses: int = 0
    compiled: list[str] = field(default_factory=list)
    combined: bool | None = None  # combined check accepted (None: not run)

    def stage_sum_s(self) -> float:
        """Sum of the widened stage spans — the tests pin this against
        ``wall_s`` (within 10%): the decomposition must tile the wall."""
        return sum(self.stages_s.get(name, 0.0) for name in RECORD_STAGES)

    def to_dict(self) -> dict:
        return {
            "seq": self.seq,
            "ts": self.ts,
            "batch": self.batch,
            "lane": self.lane,
            "lanes": self.lanes,
            "occupancy": round(self.occupancy, 6),
            "pad_waste": round(self.pad_waste, 6),
            "backend": self.backend,
            "queue_wait_s": self.queue_wait_s,
            "stages_s": {k: v for k, v in sorted(self.stages_s.items())},
            "wall_s": self.wall_s,
            "dispatch_gap_s": self.dispatch_gap_s,
            "jit_hits": self.jit_hits,
            "jit_misses": self.jit_misses,
            "combined": self.combined,
            "compiled": list(self.compiled),
        }


class FlightRecorder:
    """Fixed-size ring of :class:`FlightRecord` rows + the derived
    device-plane gauges.  Always on; the per-batch cost is a lock, a
    deque append, and a handful of float ops (<2% of even the CPU
    serving path — pinned by the bench overhead test)."""

    def __init__(
        self,
        capacity: int = 512,
        storm_threshold: int = 8,
        storm_window_s: float = 60.0,
        clock=time.monotonic,
    ):
        self._lock = threading.Lock()
        self._ring: deque[FlightRecord] = deque(maxlen=max(1, capacity))
        self._clock = clock
        self._seq = 0
        # device-idle accounting between consecutive dispatches
        self._last_device_end: float | None = None
        self._busy_ewma = 0.0
        # rolling serving throughput
        self._last_record_at: float | None = None
        self._pps_ewma = 0.0
        # compile-storm window
        self.storm_threshold = max(1, storm_threshold)
        self.storm_window_s = storm_window_s
        self._compile_times: deque[float] = deque()
        self._storm_warned_at: float | None = None

    # -- configuration ------------------------------------------------------

    def configure(
        self,
        capacity: int | None = None,
        storm_threshold: int | None = None,
        storm_window_s: float | None = None,
    ) -> None:
        with self._lock:
            if capacity is not None and capacity != self._ring.maxlen:
                self._ring = deque(self._ring, maxlen=max(1, capacity))
            if storm_threshold is not None:
                self.storm_threshold = max(1, storm_threshold)
            if storm_window_s is not None:
                self.storm_window_s = storm_window_s

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._seq = 0
            self._last_device_end = None
            self._busy_ewma = 0.0
            self._last_record_at = None
            self._pps_ewma = 0.0
            self._compile_times.clear()
            self._storm_warned_at = None

    # -- device-idle / compile-storm signals --------------------------------

    def note_device_interval(self, start: float, end: float) -> float:
        """Account one device-busy interval [start, end] (monotonic
        seconds); returns the **dispatch gap** — device idle time since
        the previous dispatch ended (0 for the first dispatch, and 0
        under pipelined overlap, where the device never went idle)."""
        with self._lock:
            if self._last_device_end is None:
                gap = 0.0
            else:
                gap = max(0.0, start - self._last_device_end)
            self._last_device_end = max(self._last_device_end or end, end)
            busy = max(0.0, end - start)
            frac = busy / (busy + gap) if busy + gap > 0 else 0.0
            self._busy_ewma = (
                frac if self._busy_ewma == 0.0
                else 0.8 * self._busy_ewma + 0.2 * frac
            )
            busy_ewma = self._busy_ewma
        metrics.histogram("tpu.dispatch.gap").observe(gap)
        metrics.gauge("tpu.device.busy_fraction").set(busy_ewma)
        return gap

    def note_compile_event(self, shape: str) -> None:
        """One first-sight compile; WARNING when the rolling window
        exceeds the storm threshold (at most once per window)."""
        now = self._clock()
        with self._lock:
            self._compile_times.append(now)
            horizon = now - self.storm_window_s
            while self._compile_times and self._compile_times[0] < horizon:
                self._compile_times.popleft()
            storm = len(self._compile_times) > self.storm_threshold
            warned_recently = (
                self._storm_warned_at is not None
                and now - self._storm_warned_at < self.storm_window_s
            )
            count = len(self._compile_times)
            if storm and not warned_recently:
                self._storm_warned_at = now
            else:
                storm = False
        if storm:
            log.warning(
                "compile storm: %d first-sight jit compiles in the last "
                "%.0fs (threshold %d, latest shape %s) — the padding "
                "schedule is minting fresh device programs per batch; "
                "check CPZK_LANE_QUANTUM / batch sizing",
                count, self.storm_window_s, self.storm_threshold, shape,
            )

    # -- recording ----------------------------------------------------------

    def record(self, rec: FlightRecord) -> FlightRecord:
        now = self._clock()
        with self._lock:
            self._seq += 1
            rec.seq = self._seq
            if rec.ts == 0.0:
                rec.ts = time.time()
            if self._last_record_at is not None and rec.batch > 0:
                dt = now - self._last_record_at
                if dt > 0:
                    inst = rec.batch / dt
                    self._pps_ewma = (
                        inst if self._pps_ewma == 0.0
                        else 0.8 * self._pps_ewma + 0.2 * inst
                    )
            self._last_record_at = now
            pps = self._pps_ewma
            self._ring.append(rec)
        metrics.gauge("tpu.throughput.proofs_per_s").set(pps)
        metrics.gauge("tpu.batch.occupancy").set(rec.occupancy)
        return rec

    # -- inspection / dump --------------------------------------------------

    def snapshot(self, n: int | None = None) -> list[FlightRecord]:
        """Most-recent-last copy of the ring (last ``n``)."""
        with self._lock:
            out = list(self._ring)
        return out if n is None else out[-n:]

    def proofs_per_s(self) -> float:
        with self._lock:
            return self._pps_ewma

    def payload(self, n: int | None = None) -> dict:
        """THE ``cpzk-flightrec/1`` payload — the single serializer behind
        the REPL ``/flightrec`` rendering, the SIGUSR2 dump, and the ops
        plane's HTTP ``/flightrec`` (one schema, one code path: the three
        surfaces cannot drift)."""
        return {
            "schema": SCHEMA,
            "dumped_at": time.time(),
            "proofs_per_s_ewma": self.proofs_per_s(),
            "records": [r.to_dict() for r in self.snapshot(n)],
        }

    def to_json(self, n: int | None = None) -> str:
        return json.dumps(self.payload(n), indent=2, sort_keys=True)

    def dump(self, path: str, n: int | None = None) -> str:
        """Write the ring as JSON to ``path`` (the SIGUSR2 hook target).
        Serialization happens outside the lock via :meth:`snapshot`."""
        text = self.to_json(n)
        with open(path, "w", encoding="utf-8") as f:
            f.write(text)
        return path


_RECORDER = FlightRecorder()


def get_flight_recorder() -> FlightRecorder:
    """The process-wide flight recorder (configure via
    ``observability.configure``)."""
    return _RECORDER


# -- operator rendering -------------------------------------------------------


def format_record(rec: dict) -> str:
    """One ``/flightrec`` line: shape, occupancy, gap, stage breakdown.
    Consumes a serialized record dict (``FlightRecord.to_dict``) — the
    REPL renders the same payload the HTTP endpoint serves."""
    stages_s = rec.get("stages_s", {})
    stages = " ".join(
        f"{name}={stages_s.get(name, 0.0) * 1000:.2f}ms"
        for name in RECORD_STAGES
    )
    lane = rec.get("lane")
    lane_tag = "" if lane is None else f"lane={lane} "
    return (
        f"#{rec['seq']} {lane_tag}n={rec['batch']} lanes={rec['lanes']} "
        f"occ={rec['occupancy']:.2f} gap={rec['dispatch_gap_s'] * 1000:.2f}ms "
        f"wait={rec['queue_wait_s'] * 1000:.2f}ms {stages} "
        f"wall={rec['wall_s'] * 1000:.2f}ms "
        f"jit={rec['jit_hits']}h/{rec['jit_misses']}m {rec['backend']}"
    )


def format_flightrec(payload: dict, limit: int = 20) -> str:
    """The admin REPL ``/flightrec`` body: last ``limit`` batches, newest
    first, one line each, plus the rolling throughput header.  Takes the
    :meth:`FlightRecorder.payload` dict — the REPL is a text rendering
    of EXACTLY the JSON the HTTP endpoint and SIGUSR2 dump emit."""
    recent = payload.get("records", [])[-limit:][::-1]
    if not recent:
        return "no recorded batches yet"
    lines = [
        f"last {len(recent)} device batches (newest first), "
        f"~{payload.get('proofs_per_s_ewma', 0.0):.0f} proofs/s EWMA:"
    ]
    lines += ["  " + format_record(r) for r in recent]
    return "\n".join(lines)


# -- on-demand deep capture (xprof) -------------------------------------------

_PROFILE_LOCK = threading.Lock()
_PROFILE_DIR: str | None = None


def profile_active() -> str | None:
    """The capture directory of an in-flight profile, or None."""
    with _PROFILE_LOCK:
        return _PROFILE_DIR


def start_profile(logdir: str) -> bool:
    """Begin a ``jax.profiler`` trace into ``logdir``; False when a
    capture is already running (concurrent captures corrupt the trace)."""
    global _PROFILE_DIR
    import jax

    with _PROFILE_LOCK:
        if _PROFILE_DIR is not None:
            return False
        jax.profiler.start_trace(logdir)
        _PROFILE_DIR = logdir
        return True


def stop_profile() -> str | None:
    """End the in-flight capture; returns its directory (None when no
    capture was running)."""
    global _PROFILE_DIR
    import jax

    with _PROFILE_LOCK:
        if _PROFILE_DIR is None:
            return None
        logdir, _PROFILE_DIR = _PROFILE_DIR, None
        jax.profiler.stop_trace()
        return logdir
