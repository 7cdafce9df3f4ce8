"""TPU/JAX ``VerifierBackend`` — the device data plane behind
:class:`cpzk_tpu.protocol.batch.BatchVerifier`.

Host side: scalar arithmetic mod l (Python ints are exact and cheap relative
to group ops), window/digit decomposition, and SoA limb marshalling of the
row points.  Device side: the batched kernels in :mod:`cpzk_tpu.ops.verify`
and the windowed-Pippenger MSM in :mod:`cpzk_tpu.ops.msm`.  Batch shapes
follow the ``_pad_lanes`` schedule — powers of two up to ``LANE_QUANTUM``,
then quantum multiples — so ``jax.jit`` caches a bounded program set
without pow2's 2x padding waste at just-past-pow2 sizes.

The combined RLC check dispatches by topology: single-device batches use
the per-row shared-doubling kernel at EVERY size (calibrated winner on TPU
v5 lite — see ``PIPPENGER_MIN_ROWS``), tiled into ``LANE_CHUNK``-lane
programs past the device's proven program size; mesh-sharded batches route
through the Pippenger MSM over all 4n+2 terms, whose per-device partial
points combine over ICI (``parallel/mesh.py``).

Semantics parity (reference ``src/verifier/batch.rs``): the combined check
is only an accelerator — on failure ``BatchVerifier`` falls back to
``verify_each``, whose per-row results are ground truth, so accept/reject
matches the reference bit-for-bit (SURVEY.md §3.2).
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import OrderedDict
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..core import edwards
from ..core.ristretto import Ristretto255, Scalar
from ..core.scalars import L
from ..protocol.batch import BatchRow, VerifierBackend
from . import curve, msm, verify

#: Row count at or above which the combined check uses the Pippenger MSM
#: instead of per-row windowed chains.  Calibrated on TPU v5 lite
#: (.hw/ sweep, round 5): the per-row kernel wins EVERY measured A/B —
#: 11,991 vs 7,844 proofs/s at n=1024, 24,714 vs 19,028 at n=4096 — so
#: the single-device default is "never" (the Pippenger path remains the
#: multi-chip sharded-MSM story and stays selectable via the constructor's
#: ``pippenger_min`` for re-calibration on other silicon).
PIPPENGER_MIN_ROWS = 1 << 62

#: Maximum lane count for one monolithic device program.  Measured on TPU
#: v5 lite (round-5 sweep, PROFILE.md §7a): the MSM kernel is
#: bit-correct through 32,770 lanes and deterministically WRONG at 40,962+
#: (internal XLA error at 49,154; all-zero output at 57,346), and the
#: per-row combined kernel fails its in-kernel check at 65,538 rows while
#: passing at 16,386 — an XLA codegen defect on large-lane programs, not
#: a math bug (the identical code passes every CPU differential at every
#: size).  Batches above this are tiled into full chunks of this many
#: lanes plus one quantum-aligned remainder chunk (one compile per chunk
#: shape, partial points added at the end), which also cuts the 64k
#: monolith's >18-minute compile.
LANE_CHUNK = int(os.environ.get("CPZK_LANE_CHUNK", "16384"))

#: Lane-pad granularity past the pow2 range.  Pure pow2 padding doubles
#: the device work for just-past-pow2 batches (the ubiquitous N+1
#: correction-row case: 16,385 -> 32,768); quantum padding caps the waste
#: at <= QUANTUM-1 lanes (~3% at 64k) while keeping the jit cache bounded
#: (one shared full-chunk program + at most LANE_CHUNK/QUANTUM remainder
#: shapes).
LANE_QUANTUM = int(os.environ.get("CPZK_LANE_QUANTUM", "2048"))
if LANE_CHUNK % min(LANE_QUANTUM, LANE_CHUNK):
    # a chunk that is not a quantum multiple makes every remainder shape
    # batch-size-dependent — one fresh minutes-long XLA compile each,
    # defeating the bounded-cache design; round down once, loudly
    import warnings

    _rounded = LANE_CHUNK - LANE_CHUNK % LANE_QUANTUM
    warnings.warn(
        f"CPZK_LANE_CHUNK={LANE_CHUNK} is not a multiple of "
        f"LANE_QUANTUM={LANE_QUANTUM}; rounding down to {_rounded} to keep "
        "remainder-chunk shapes bounded", stacklevel=1)
    LANE_CHUNK = _rounded


#: LRU bound on ``TpuBackend._gh_cache`` — device-resident generator-pair
#: points keyed by statement bytes.  Real deployments share one generator
#: pair, so 128 is generous; the bound exists because an adversarial (or
#: merely huge) registered-statement population must not leak device/host
#: memory one [20, 1] coordinate set at a time.
GH_CACHE_MAX = int(os.environ.get("CPZK_GH_CACHE_MAX", "128"))


def _note_gh_cache(size: int, evicted: int) -> None:
    """Generator-pair cache telemetry (``tpu.gh_cache.size`` gauge,
    ``tpu.gh_cache.evictions`` counter); optional like all server-layer
    metrics from this module."""
    try:
        from ..server import metrics

        metrics.gauge("tpu.gh_cache.size").set(size)
        if evicted:
            metrics.counter("tpu.gh_cache.evictions").inc(evicted)
    except Exception:  # pragma: no cover - server layer unavailable
        pass


def _note_pad_waste(n: int, pad: int) -> None:
    """Batch-shape telemetry: fraction of device lanes burned on padding
    for the most recent batch (``tpu.batch.pad_waste`` gauge) plus the
    flight recorder's occupancy accounting (``tpu.batch.occupancy``).
    Metrics live in the server layer; this module stays importable
    without it."""
    try:
        from ..server import metrics

        metrics.gauge("tpu.batch.pad_waste").set(
            (pad - n) / pad if pad > 0 else 0.0
        )
    except Exception:  # pragma: no cover - server layer unavailable
        pass
    try:
        from ..observability import flightrec

        flightrec.note_lanes(n, pad)
    except Exception:  # pragma: no cover - observability unavailable
        pass


def _note_marshal(t0: float) -> None:
    """Report elapsed host limb-marshal seconds since ``t0`` into the
    flight recorder's device sink (no-op outside an instrumented batch)."""
    try:
        from ..observability import flightrec

        flightrec.note_marshal(time.perf_counter() - t0)
    except Exception:  # pragma: no cover - observability unavailable
        pass


def _note_combined(ok: bool) -> None:
    """Combined-check outcome into the flight recorder's device sink."""
    try:
        from ..observability import flightrec

        flightrec.note_combined(ok)
    except Exception:  # pragma: no cover - observability unavailable
        pass


#: Per-thread device pin.  A per-device dispatch lane's backend enters
#: :func:`device_scope` around every verify call, which (a) makes
#: ``jax.default_device`` target that chip for the thread (staging
#: transfers AND jit executions land there) and (b) stamps the thread's
#: device key into every jit/AOT cache key below — XLA compiles one
#: executable PER device, so a cache that ignored the device would book
#: phantom hits on lanes 1..N-1 and the prewarm would warm only lane 0
#: (the ISSUE 12 prewarm bug).
_ACTIVE_DEVICE = threading.local()


def _device_key() -> str | None:
    """The jit/AOT cache-key suffix of the thread's pinned device (None
    outside :func:`device_scope` — the default-device fast path keeps its
    historical unsuffixed keys)."""
    return getattr(_ACTIVE_DEVICE, "key", None)


@contextlib.contextmanager
def device_scope(device):
    """Pin this thread's dispatches (staging, jit, AOT lookup) to one jax
    device.  ``None`` is a no-op, so single-device callers pay nothing."""
    if device is None:
        yield
        return
    prev = getattr(_ACTIVE_DEVICE, "key", None)
    _ACTIVE_DEVICE.key = f"dev{device.id}"
    try:
        with jax.default_device(device):
            yield
    finally:
        _ACTIVE_DEVICE.key = prev


def _scoped_key(key: tuple) -> tuple:
    dk = _device_key()
    return key if dk is None else key + (dk,)


#: First-sight registry of jitted device programs, keyed by (kernel name,
#: static args, padded shape[, device]) — the cache key the flight
#: recorder uses to attribute a dispatch's cost to ``compile`` (first
#: sight of a padded shape pays an XLA trace+compile) vs ``execute``.
#: The device component appears only under :func:`device_scope` (per-lane
#: dispatch): XLA compiles per device, so first-sights are per-device
#: facts.  Guarded: pipelined batches call the backend from multiple
#: worker threads.
_JIT_SEEN: set[tuple] = set()
_JIT_LOCK = threading.Lock()


def _jit_first_sight(*key) -> bool:
    """Register one jitted-program dispatch; True when this process has
    never dispatched this (kernel, shape) on this thread's device before."""
    key = _scoped_key(key)
    with _JIT_LOCK:
        first = key not in _JIT_SEEN
        if first:
            _JIT_SEEN.add(key)
    try:
        from ..observability import flightrec

        flightrec.note_jit("/".join(str(k) for k in key), first)
    except Exception:  # pragma: no cover - observability unavailable
        pass
    return first


def _mark_seen(key: tuple) -> None:
    """Book a program compiled before ready (prewarm): its first serving
    dispatch is a jit HIT."""
    with _JIT_LOCK:
        _JIT_SEEN.add(_scoped_key(key))


#: Pre-lowered executables per (kernel, padded shape[, device]), keyed
#: like ``_JIT_SEEN``.  Populated by :func:`prewarm_executables` at server
#: startup (``[tpu] prewarm_quanta``) via ``jit(...).lower(...).compile()``;
#: the dispatch wrappers consult it FIRST, so a warmed shape never pays an
#: XLA trace at serving time and the flight recorder books its dispatches
#: as cache hits (zero steady-state ``compile`` spans).  Keys carry the
#: compiling thread's :func:`device_scope` pin, so a per-lane prewarm
#: yields one executable per chip and lane N's first dispatch finds ITS
#: executable, not lane 0's.
_AOT_CACHE: dict[tuple, object] = {}


def _aot_get(*key):
    key = _scoped_key(key)
    with _JIT_LOCK:
        return _AOT_CACHE.get(key)


def _aot_register(key: tuple, exe) -> None:
    with _JIT_LOCK:
        _AOT_CACHE[_scoped_key(key)] = exe
    _mark_seen(key)


def _point_aval(pad: int):
    return tuple(
        jax.ShapeDtypeStruct((curve.NLIMBS, pad), jnp.int32)
        for _ in range(4)
    )


def _windows_aval(pad: int):
    return jax.ShapeDtypeStruct((curve.NWINDOWS, pad), jnp.int32)


def _prewarm_plan(batch_sizes) -> list[tuple]:
    """The (key, lower-thunk) list a prewarm covers: exactly the program
    shapes the shipping single-device dispatch of each batch size hits —
    the per-row combined kernel (with its +1 correction row), the
    chunk/partial programs past LANE_CHUNK, and the ``verify_each``
    ground-truth kernel the combined check falls back to."""
    plan: list[tuple] = []
    seen: set[tuple] = set()

    def add(key, thunk):
        if key not in seen:
            seen.add(key)
            plan.append((key, thunk))

    for n in batch_sizes:
        n = int(n)
        if n < 1:
            continue
        # combined RLC check: n rows + 1 correction row
        pad = _pad_lanes(n + 1)
        if pad <= LANE_CHUNK:
            add(
                ("combined", pad),
                lambda p=pad: _kernel("combined").lower(
                    p,
                    _point_aval(p), _point_aval(p),
                    _point_aval(p), _point_aval(p),
                    _windows_aval(p), _windows_aval(p),
                    _windows_aval(p), _windows_aval(p),
                ),
            )
        else:
            bounds = list(_chunk_bounds(pad))
            for lo, hi in bounds:
                w = hi - lo
                add(
                    ("combined_partial", w),
                    lambda p=w: _kernel("combined_partial").lower(
                        p,
                        _point_aval(p), _point_aval(p),
                        _point_aval(p), _point_aval(p),
                        _windows_aval(p), _windows_aval(p),
                        _windows_aval(p), _windows_aval(p),
                    ),
                )
            add(
                ("partials", len(bounds)),
                lambda k=len(bounds): _partials_jit.lower(_point_aval(k)),
            )
        # verify_each fallback (shared generator pair, [20, 1] g/h)
        pad_e = _pad_lanes(n)
        chunks = (
            [(0, pad_e)] if pad_e <= LANE_CHUNK else list(_chunk_bounds(pad_e))
        )
        for lo, hi in chunks:
            w = hi - lo
            add(
                ("each", w, True),
                lambda p=w: _kernel("each").lower(
                    p,
                    _point_aval(1), _point_aval(1),
                    _point_aval(p), _point_aval(p),
                    _point_aval(p), _point_aval(p),
                    _windows_aval(p), _windows_aval(p),
                ),
            )
    return plan


def prewarm_executables(batch_sizes, devices=None) -> list[str]:
    """AOT-compile (``jit(...).lower(...).compile()``) the single-device
    verify kernels for every padded shape the given batch sizes dispatch,
    and register them in the AOT executable cache + ``_JIT_SEEN``.  Call
    before the server reports ready (``[tpu] prewarm_quanta``): steady-
    state dispatch then never pays an XLA trace/compile.

    ``devices`` targets the prewarm: ``None`` warms the default device
    with the historical unsuffixed cache keys; a device list compiles one
    executable PER device under :func:`device_scope`, so every per-device
    dispatch lane's first serving dispatch books a jit HIT.

    Returns the warmed shape keys (for the startup log).  Idempotent per
    (shape, device)."""
    warmed: list[str] = []
    for device in (devices if devices is not None else [None]):
        with device_scope(device):
            for key, lower in _prewarm_plan(batch_sizes):
                if _aot_get(*key) is None:
                    _aot_register(
                        key, _timed_compile(key, lambda: lower().compile()))
                    warmed.append("/".join(str(k) for k in _scoped_key(key)))
    return warmed


def _timed_compile(key: tuple, compile_):
    """``compile_()``, with a log line for a long compile."""
    t0 = time.perf_counter()
    exe = compile_()
    log_s = time.perf_counter() - t0
    if log_s > 1.0:  # long compiles are worth a line each
        import logging

        logging.getLogger("cpzk_tpu.ops.backend").info(
            "prewarmed %s in %.1fs",
            "/".join(str(k) for k in _scoped_key(key)), log_s,
        )
    return exe


def _pad_pow2(n: int) -> int:
    size = 1
    while size < n:
        size *= 2
    return size


def _pad_lanes(n: int) -> int:
    """Lane padding schedule: powers of two while small (compile-cache
    friendly), then multiples of LANE_QUANTUM.  Chunking slices the
    result into LANE_CHUNK-lane programs plus one quantum-aligned
    remainder program (see ``_chunk_bounds``)."""
    q = min(LANE_QUANTUM, LANE_CHUNK)
    if n <= q:
        return _pad_pow2(n)
    return -(-n // q) * q


def _msm_shape(n: int) -> tuple[int, int]:
    """(c, m_pad) of the combined Pippenger MSM over ``n`` rows: 4n+2
    terms (four per row, plus the G and H correction terms) in
    4 * pow2(n) slots, or 4 * pow2(n) + 2 when n is itself a power of
    two.  Whole power-of-two term counts tile into whole chunks and mesh
    slices: the old 4 * pow2(n) + 2 spilled the two correction terms
    into one more, near-empty remainder program — a second sharded MSM
    compile for every batch past one mesh step."""
    m = 4 * _pad_pow2(n)
    if 4 * n + 2 > m:
        m += 2
    # window size is per-PROGRAM: past the chunk cap the MSM runs as
    # LANE_CHUNK-term tiles (chunked_msm_identity) and each device of
    # a mesh sees at most LANE_CHUNK lanes (_mesh_step), so the cost
    # model must see the chunk length, not the full term count —
    # sizing from m overshot c by 2 windows at 64k terms (ADVICE.md /
    # ROADMAP item 4 calibration-tail fix)
    c = msm.pick_window(min(m, LANE_CHUNK))
    # m is already shape-quantized, so below the chunk cap it is used
    # EXACTLY; above it, quantum padding keeps the waste to under one
    # LANE_QUANTUM of identity terms
    return c, (m if m <= LANE_CHUNK else _pad_lanes(m))


def _chunk_bounds(pad: int):
    """(lo, hi) slices of a padded lane axis: full LANE_CHUNK chunks plus
    one remainder chunk (a LANE_QUANTUM multiple by construction)."""
    lo = 0
    while lo < pad:
        hi = min(lo + LANE_CHUNK, pad)
        yield lo, hi
        lo = hi


def _points_soa(points: list[edwards.Point], pad: int) -> curve.Point:
    return curve.points_soa(points, pad)


def _elems_soa(elems: list, pad: int, device=None) -> curve.Point:
    """SoA limb marshal of Elements.  Serving-path elements are
    wire-validated with lazy coordinates, so the native batch decode
    (threaded, ~9 us/point) beats materializing ``.point`` per element
    (~340 us of Python big-int decode each) by ~40x; falls back to the
    Python path when the native core is absent — checked FIRST, so the
    fallback never pays O(n) wire encodes just to learn that.  ``device``
    targets the staging transfer at a pinned chip (per-lane dispatch);
    the Python fallback relies on the caller's :func:`device_scope`."""
    from ..core import _native

    if _native.load() is not None:
        dev = curve.wires_to_device(
            b"".join(e.wire() for e in elems), pad, device=device
        )
        if dev is not None:
            return dev
    return _points_soa([e.point for e in elems], pad)


def _windows(values: list[int], pad: int) -> jnp.ndarray:
    return curve.scalar_windows(values, pad)


def _each_shared_impl(n_pad, g, h, y1, y2, r1, r2, ws, wc):
    del n_pad  # static cache key only
    return verify.verify_each_kernel(g, h, y1, y2, r1, r2, ws, wc)


def _combined_impl(n_pad, r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac):
    del n_pad
    return verify.combined_kernel(r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac)


def _combined_partial_impl(n_pad, r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac):
    del n_pad
    return verify.combined_partial_kernel(
        r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac)


#: Jitted single-device kernels, built lazily so buffer donation can be
#: decided once the JAX backend is known (importing this module must not
#: initialize a backend).  Donation marks the per-batch input arrays as
#: reusable by XLA — steady-state serving then recycles the same device
#: buffers batch after batch instead of allocating per dispatch.  Gated
#: off on CPU (XLA CPU ignores donation and warns per call); the cached
#: generator-pair arrays of ``_each_shared`` (g, h) are NEVER donated —
#: the gh-cache hands the same buffers to every batch.
_KERNELS: dict[str, object] = {}
_KERNEL_SPECS = {
    # name -> (impl, donate_argnums when donation is on)
    "each": (_each_shared_impl, tuple(range(3, 9))),
    "combined": (_combined_impl, tuple(range(1, 9))),
    "combined_partial": (_combined_partial_impl, tuple(range(1, 9))),
}


_DONATE_OVERRIDE: bool | None = None


def enable_donation(on: bool = True) -> None:
    """Serving-daemon switch: donate per-batch kernel inputs so XLA
    recycles their device buffers across batches.  Deliberately NOT the
    default — benches and direct callers may re-dispatch the same arrays
    (a donated array is dead after its call), so only the serving path,
    which rebuilds every input per batch, turns this on (build_backend,
    off-CPU).  Call before the first kernel dispatch; already-jitted
    kernels are rebuilt under the new policy, already-AOT-compiled
    executables are not."""
    global _DONATE_OVERRIDE
    _DONATE_OVERRIDE = on
    _KERNELS.clear()


def _donation_enabled() -> bool:
    """Donate device input buffers?  CPZK_DONATE_BUFFERS=1/0 forces;
    otherwise the :func:`enable_donation` switch decides (default off)."""
    forced = os.environ.get("CPZK_DONATE_BUFFERS")
    if forced in ("0", "1"):
        return forced == "1"
    return bool(_DONATE_OVERRIDE)


def _kernel(name: str):
    fn = _KERNELS.get(name)
    if fn is None:
        impl, donate = _KERNEL_SPECS[name]
        fn = _KERNELS[name] = jax.jit(
            impl,
            static_argnums=(0,),
            donate_argnums=donate if _donation_enabled() else (),
        )
    return fn


def _each_shared(n_pad, g, h, y1, y2, r1, r2, ws, wc):
    # the AOT executable is lowered for a SHARED [20, 1] generator pair;
    # mixed-generator batches (full-width g/h) must take the jit path
    if g[0].shape[-1] == 1:
        exe = _aot_get("each", n_pad, True)
        if exe is not None:
            return exe(g, h, y1, y2, r1, r2, ws, wc)
    return _kernel("each")(n_pad, g, h, y1, y2, r1, r2, ws, wc)


def _combined(n_pad, r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac):
    exe = _aot_get("combined", n_pad)
    if exe is not None:
        return exe(r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac)
    return _kernel("combined")(
        n_pad, r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac)


def _combined_partial(n_pad, r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac):
    exe = _aot_get("combined_partial", n_pad)
    if exe is not None:
        return exe(r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac)
    return _kernel("combined_partial")(
        n_pad, r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac)


@partial(jax.jit, static_argnums=(0,))
def _msm_identity(c, points, digits):
    return msm.msm_is_identity_kernel(points, digits, c)


@partial(jax.jit, static_argnums=(0,))
def _msm_partial(c, points, digits):
    return msm.msm_kernel(points, digits, c)


def _partials_impl(parts: curve.Point) -> jnp.ndarray:
    return curve.is_identity(curve.tree_sum(parts, axis=-1))


_partials_jit = jax.jit(_partials_impl)


def _partials_are_identity(parts: curve.Point) -> jnp.ndarray:
    """[20, k] partial points -> does their sum hit the identity coset."""
    exe = _aot_get("partials", parts[0].shape[-1])
    if exe is not None:
        return exe(parts)
    return _partials_jit(parts)


def _chunk_point(pt: curve.Point, lo: int, hi: int) -> curve.Point:
    """Lane-slice every coordinate array of a SoA point."""
    return tuple(c[..., lo:hi] for c in pt)


def _stack_partials(parts: list[curve.Point]) -> curve.Point:
    """[20, 1] chunk partials -> one [20, k] point batch for the final
    tree-sum + identity test."""
    return tuple(
        jnp.concatenate([p[k] for p in parts], axis=-1) for k in range(4)
    )


def chunked_combined_identity(pad, r1, y1, r2, y2,
                              w_a, w_ac, w_ba, w_bac) -> bool:
    """The full chunked per-row combined check: LANE_CHUNK-lane partial
    programs (identity-padded lanes contribute identity partials), then
    one tree-sum + identity test.  The SINGLE implementation of the
    chunk schedule — TpuBackend serves it and bench.py times it, so the
    bench cannot drift from the shipped dispatch."""
    if pad <= LANE_CHUNK:
        _jit_first_sight("combined", pad)
        return bool(_combined(pad, r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac))
    parts = []
    for lo, hi in _chunk_bounds(pad):
        _jit_first_sight("combined_partial", hi - lo)
        parts.append(_combined_partial(
            hi - lo,
            _chunk_point(r1, lo, hi), _chunk_point(y1, lo, hi),
            _chunk_point(r2, lo, hi), _chunk_point(y2, lo, hi),
            w_a[:, lo:hi], w_ac[:, lo:hi],
            w_ba[:, lo:hi], w_bac[:, lo:hi]))
    _jit_first_sight("partials", len(parts))
    return bool(_partials_are_identity(_stack_partials(parts)))


def chunked_msm_identity(c: int, pts: curve.Point,
                         digits: jnp.ndarray) -> bool:
    """The full chunked MSM == identity check (term axis tiled; zero-digit
    padded terms contribute identity).  Shared by TpuBackend and bench.py
    for the same no-drift reason as :func:`chunked_combined_identity`."""
    m_pad = digits.shape[-1]
    if m_pad <= LANE_CHUNK:
        _jit_first_sight("msm", c, m_pad)
        return bool(_msm_identity(c, pts, digits))
    parts = []
    for lo, hi in _chunk_bounds(m_pad):
        _jit_first_sight("msm_partial", c, hi - lo)
        parts.append(_msm_partial(
            c, _chunk_point(pts, lo, hi), digits[:, lo:hi]))
    _jit_first_sight("partials", len(parts))
    return bool(_partials_are_identity(_stack_partials(parts)))


class TpuBackend(VerifierBackend):
    """Vectorized device backend (TPU when available, any JAX backend).

    ``mesh_devices``: ``None`` pins single-device execution; ``0`` shards
    the batch axis over all visible devices (production default via the
    ``tpu.mesh_devices`` config knob); ``k > 1`` uses the first k.  The
    sharded paths ride ICI collectives via ``shard_map``
    (:mod:`cpzk_tpu.parallel.mesh`).

    ``device`` pins every dispatch of THIS instance to one jax device
    (staging transfers via ``jax.device_put``-targeted
    ``wires_to_device``, jit/AOT execution via :func:`device_scope`) —
    the per-device serving lanes each hold one pinned instance, so eight
    chips serve eight independent batch streams.  Mutually exclusive
    with a mesh.
    """

    prefers_combined = True

    def __init__(self, mesh_devices: int | None = None,
                 pippenger_min: int | None = None,
                 gh_cache_max: int | None = None,
                 device=None):
        """``pippenger_min`` overrides the rowcombined->Pippenger crossover
        for this instance (None = the ``PIPPENGER_MIN_ROWS`` default);
        callers (drivers, calibration sweeps, tests) use it to drive the
        single-device MSM.  ``gh_cache_max``
        bounds the per-generator-pair device-point cache (None = the
        GH_CACHE_MAX module default / CPZK_GH_CACHE_MAX).  ``device``
        pins the instance to one jax device (see class docstring)."""
        if device is not None and mesh_devices is not None:
            raise ValueError(
                "TpuBackend(device=...) pins one chip; it cannot also "
                "shard over a mesh (mesh_devices must be None)"
            )
        self._device = device
        self._pippenger_min = (
            PIPPENGER_MIN_ROWS if pippenger_min is None else pippenger_min
        )

        # LRU-bounded generator-pair cache: keyed by statement generator
        # bytes, so millions of distinct registered statements must not
        # grow it without bound (the KeyedTokenBuckets containment story
        # applied to device memory) — least-recently-verified pair evicts
        self._gh_cache: OrderedDict[
            tuple[bytes, bytes], tuple[curve.Point, curve.Point]
        ] = OrderedDict()
        self._gh_cache_max = max(
            1, GH_CACHE_MAX if gh_cache_max is None else gh_cache_max
        )
        # the pipelined batcher calls verify_* from multiple worker
        # threads; guard the check-then-insert so a cold generator pair
        # is marshalled once, not once per concurrent batch
        self._gh_lock = threading.Lock()
        self._mesh = None
        self._sharded_each = None
        self._sharded_msm = None
        if mesh_devices is not None:
            from ..parallel import (
                batch_mesh,
                make_sharded_msm_check,
                make_sharded_verify_each,
                resolve_mesh_devices,
            )

            devices = resolve_mesh_devices(mesh_devices)
            if devices is not None:
                self._mesh = batch_mesh(devices)
                self._sharded_each = make_sharded_verify_each(self._mesh)
                self._sharded_msm = make_sharded_msm_check(self._mesh)

    def _gh(self, row: BatchRow) -> tuple[curve.Point, curve.Point]:
        key = (
            Ristretto255.element_to_bytes(row.g),
            Ristretto255.element_to_bytes(row.h),
        )
        evicted = 0
        with self._gh_lock:
            pair = self._gh_cache.pop(key, None)
            if pair is None:
                # single shared points keep a size-1 batch axis ([20, 1]
                # coords) and broadcast against the [20, n] row arrays
                pair = (
                    curve.points_to_device([row.g.point]),
                    curve.points_to_device([row.h.point]),
                )
            self._gh_cache[key] = pair  # (re)insert most-recently-used
            while len(self._gh_cache) > self._gh_cache_max:
                self._gh_cache.popitem(last=False)
                evicted += 1
            size = len(self._gh_cache)
        _note_gh_cache(size, evicted)
        return pair

    def prewarm(self, batch_sizes) -> list[str]:
        """Compile every program this instance dispatches for the given
        batch sizes before serving: the sharded MSM (with its partials
        reduction) and sharded ``verify_each`` under a mesh, else the
        single-device kernels (:func:`prewarm_executables`).  Returns
        the warmed program names; their first dispatch books a jit HIT.
        The sharded programs are the process's (``parallel.mesh``): a
        later instance over the same chips finds them and returns none."""
        if self._mesh is None:
            return prewarm_executables(
                batch_sizes,
                devices=None if self._device is None else [self._device])
        warmed: list[str] = []
        for n in map(int, batch_sizes):
            c, m_pad = _msm_shape(n)
            warmed += _timed_compile(
                ("mesh_msm", c, m_pad),
                lambda: self._sharded_msm.warm(m_pad, c))
            warmed += _timed_compile(
                ("mesh_each", n),
                lambda: self._sharded_each.warm(_pad_lanes(n)))
        return warmed

    # -- VerifierBackend interface ------------------------------------------

    def verify_combined(self, rows: list[BatchRow], beta: Scalar) -> bool:
        with device_scope(self._device):
            ok = self._verify_combined(rows, beta)
        _note_combined(ok)
        return ok

    def _verify_combined(self, rows: list[BatchRow], beta: Scalar) -> bool:
        n = len(rows)

        if self._sharded_msm is not None or n >= self._pippenger_min:
            # a mesh always routes through the Pippenger MSM: the sharded
            # combined check is the partial-bucket-psum path (SURVEY §2.3)
            return self._combined_pippenger(rows, beta)

        # correction row: G in slot r1 with -sum(a s), H in slot y1 with
        # -b sum(a s); identity in the other two slots.
        t0 = time.perf_counter()
        pad = _pad_lanes(n + 1)
        _note_pad_waste(n + 1, pad)
        dev = self._device
        r1 = _elems_soa([r.r1 for r in rows] + [rows[0].g], pad, device=dev)
        y1 = _elems_soa([r.y1 for r in rows] + [rows[0].h], pad, device=dev)
        r2 = _elems_soa([r.r2 for r in rows], pad, device=dev)
        y2 = _elems_soa([r.y2 for r in rows], pad, device=dev)
        b = beta.value
        a = [r.alpha.value for r in rows]
        c = [r.c.value for r in rows]
        s = [r.s.value for r in rows]
        ac = [x * y % L for x, y in zip(a, c)]
        ba = [b * x % L for x in a]
        bac = [b * x % L for x in ac]
        sum_as = sum(x * y for x, y in zip(a, s)) % L
        w_a = _windows(a + [(L - sum_as) % L], pad)
        w_ac = _windows(ac + [(L - b * sum_as % L) % L], pad)
        w_ba = _windows(ba, pad)
        w_bac = _windows(bac, pad)
        _note_marshal(t0)
        return chunked_combined_identity(
            pad, r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac)

    def _combined_pippenger(self, rows: list[BatchRow], beta: Scalar) -> bool:
        """One MSM over all 4n+2 (point, scalar) terms == identity.

        The row count (not the term count) is padded to a power of two, so
        the jit cache stays small while padding waste stays ~0% — padding
        the 4n+2 terms directly would double device work at power-of-two
        batch sizes, the common full-batch serving case.  Term order:
        a(n) | ac(n) | ba(n) | bac(n) | corr_G | corr_H | zeros(pad).
        """
        t0 = time.perf_counter()
        elems = (
            [r.r1 for r in rows]
            + [r.y1 for r in rows]
            + [r.r2 for r in rows]
            + [r.y2 for r in rows]
            + [rows[0].g, rows[0].h]
        )
        terms = 4 * len(rows) + 2
        c, m_pad = _msm_shape(len(rows))
        _note_pad_waste(terms, m_pad)
        pts = _elems_soa(elems, m_pad, device=self._device)
        mesh = self._sharded_msm is not None
        from ..observability import flightrec

        # the scalar products and their signed-digit recode
        digits_span = (flightrec.device_span("mesh.digits") if mesh
                       else contextlib.nullcontext())
        with digits_span:
            b = beta.value
            a = [r.alpha.value for r in rows]
            ch = [r.c.value for r in rows]
            s = [r.s.value for r in rows]
            ac = [x * y % L for x, y in zip(a, ch)]
            ba = [b * x % L for x in a]
            bac = [b * x % L for x in ac]
            sum_as = sum(x * y for x, y in zip(a, s)) % L
            scalars = a + ac + ba + bac + [
                (L - sum_as) % L, (L - b * sum_as % L) % L,
            ]
            digits = jnp.asarray(
                msm.scalars_to_signed_digits(
                    scalars + [0] * (m_pad - len(scalars)), c)
            )
        _note_marshal(t0)
        if mesh:
            return self._sharded_msm(pts, digits, c, real=terms)
        return chunked_msm_identity(c, pts, digits)

    def verify_each(self, rows: list[BatchRow]) -> list[bool]:
        with device_scope(self._device):
            return self._verify_each(rows)

    def _verify_each(self, rows: list[BatchRow]) -> list[bool]:
        n = len(rows)
        dev = self._device
        t0 = time.perf_counter()
        pad = _pad_lanes(n)
        _note_pad_waste(n, pad)
        shared = all(r.g == rows[0].g and r.h == rows[0].h for r in rows)
        if shared:
            g, h = self._gh(rows[0])
        else:
            g = _elems_soa([r.g for r in rows], pad, device=dev)
            h = _elems_soa([r.h for r in rows], pad, device=dev)
        y1 = _elems_soa([r.y1 for r in rows], pad, device=dev)
        y2 = _elems_soa([r.y2 for r in rows], pad, device=dev)
        r1 = _elems_soa([r.r1 for r in rows], pad, device=dev)
        r2 = _elems_soa([r.r2 for r in rows], pad, device=dev)
        ws = _windows([r.s.value for r in rows], pad)
        wc = _windows([r.c.value for r in rows], pad)
        _note_marshal(t0)

        if self._sharded_each is not None and shared:
            mask = self._sharded_each(g, h, y1, y2, r1, r2, ws, wc, real=n)
        elif pad > LANE_CHUNK:
            # per-row checks are lane-independent: tile and concatenate
            chunks = []
            for lo, hi in _chunk_bounds(pad):
                cg = g if shared else _chunk_point(g, lo, hi)
                ch_ = h if shared else _chunk_point(h, lo, hi)
                _jit_first_sight("each", hi - lo, shared)
                chunks.append(_each_shared(
                    hi - lo, cg, ch_,
                    _chunk_point(y1, lo, hi), _chunk_point(y2, lo, hi),
                    _chunk_point(r1, lo, hi), _chunk_point(r2, lo, hi),
                    ws[:, lo:hi], wc[:, lo:hi]))
            mask = jnp.concatenate(chunks, axis=-1)
        else:
            _jit_first_sight("each", pad, shared)
            mask = _each_shared(pad, g, h, y1, y2, r1, r2, ws, wc)
        return [bool(v) for v in np.asarray(mask)[:n]]
