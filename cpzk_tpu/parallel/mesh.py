"""Mesh-sharded batch verification (shard_map + ICI collectives).

Design (SURVEY.md §2.3, §5 long-context entry): proofs are embarrassingly
parallel along the batch axis, so every row array ([20, n] limb-major point
coords and [64, n] scalar windows — batch rides the minor axis / vector
lanes) is sharded over a 1-D device mesh along that batch axis.  The
per-proof kernel needs no communication at all; the combined RLC check
reduces each device's shard to one partial point locally, then combines the
``D`` partial points with one tiny cross-device gather — the multi-chip
analog of the reference's accumulation loop at
``src/verifier/batch.rs:271-312``.

``pad_to_multiple`` handles ragged batches here (instead of at every call
site): identity points with zero windows are verified-true rows in the
per-proof kernel and contribute the identity to the combined sum.
"""

from __future__ import annotations

import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import curve, msm, verify

AXIS = "batch"


def batch_mesh(devices=None) -> Mesh:
    """1-D data-parallel mesh over all (or the given) devices."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (AXIS,))


def resolve_mesh_devices(mesh_devices: int | None):
    """The shared ``mesh_devices`` convention: ``None`` -> no mesh
    (single-device), ``0`` -> all visible devices, ``k`` -> the first k.
    Returns a device list when a real (>1) mesh should be built, else
    None — one policy for every mesh-capable component (TpuBackend,
    BatchProver, the serving lane router).

    Asking for more devices than exist is a deployment error, not a
    preference: it used to clamp silently, so a config written for an
    8-chip host "worked" on a 1-chip box at 1/8 the capacity with no
    signal.  Rejected loudly instead."""
    if mesh_devices is None:
        return None
    n_avail = jax.device_count()
    if mesh_devices > n_avail:
        raise ValueError(
            f"mesh_devices={mesh_devices} exceeds the {n_avail} visible "
            f"jax device(s) on this host — fix the topology knob or the "
            "deployment (a silent clamp would serve at a fraction of the "
            "configured capacity)"
        )
    want = n_avail if mesh_devices == 0 else mesh_devices
    if want <= 1:
        return None
    return jax.devices()[:want]


def resolve_lane_devices(lanes: int):
    """Lane-count discovery for the per-device serving plane (``[tpu]
    lanes``): ``1`` -> None (the single-lane fast path, today's
    behavior), ``-1`` -> one lane per local device, ``k > 1`` -> the
    first k local devices (rejected when k exceeds the local count, same
    policy as :func:`resolve_mesh_devices`).  Returns a device list only
    when a real multi-lane router should be built."""
    if lanes == 1:
        return None
    if lanes == -1:
        devices = jax.local_devices()
        return devices if len(devices) > 1 else None
    n_local = jax.local_device_count()
    if lanes > n_local:
        raise ValueError(
            f"lanes={lanes} exceeds the {n_local} local jax device(s) on "
            "this host — one dispatch lane pins one local chip"
        )
    return jax.local_devices()[:lanes]


def pad_to_multiple(pt: curve.Point, n_to: int) -> curve.Point:
    """Pad a [20, n] point SoA with identity rows up to n_to lanes."""
    n = pt[0].shape[-1]
    if n == n_to:
        return pt
    pad = curve.identity((n_to - n,))
    return tuple(jnp.concatenate([c, pc], axis=-1) for c, pc in zip(pt, pad))


def pad_windows(w: jnp.ndarray, n_to: int) -> jnp.ndarray:
    """Pad a [64, n] window array with zero-scalar lanes up to n_to."""
    n = w.shape[-1]
    if n == n_to:
        return w
    return jnp.concatenate(
        [w, jnp.zeros(w.shape[:-1] + (n_to - n,), dtype=w.dtype)], axis=-1
    )


def _mesh_pad(d: int, n: int) -> tuple[int, int]:
    """(step, n_to): the per-slice lane count d*LANE_CHUNK that keeps every
    per-device program at or under the TPU large-lane miscompile bound
    (ops/backend.py LANE_CHUNK), and the padded total.  Single source for
    all three sharded wrappers.

    Padding is a d-multiple in BOTH regimes (ROADMAP item 2 fix): below
    one step, the next d-multiple; above, each device's lane count is
    rounded up to a LANE_QUANTUM multiple instead of a full LANE_CHUNK —
    the old full-step rounding burned up to d*LANE_CHUNK-1 identity lanes
    (2x device work at one-past-a-step sizes, e.g. 140k rows on 8 chips
    padded 262,144 instead of 147,456).  The remainder slice is shorter
    than ``step`` but stays a d-multiple with quantum-aligned per-device
    programs, so the jit cache stays bounded exactly like the
    single-device remainder-chunk schedule."""
    from ..ops import backend as _backend  # lazy: no import cycle

    step = d * _backend.LANE_CHUNK
    if n <= step:
        n_to = -(-n // d) * d
    else:
        q = min(_backend.LANE_QUANTUM, _backend.LANE_CHUNK)
        per_device = -(-n // d)               # ceil lanes per device
        per_device = -(-per_device // q) * q  # quantum-align its program
        n_to = per_device * d
    return step, n_to


def _mesh_step(d: int, n: int) -> tuple[int, int]:
    """:func:`_mesh_pad` for a dispatch: also books the lane occupancy."""
    step, n_to = _mesh_pad(d, n)
    _note_occupancy(n, n_to)
    return step, n_to


def _slices(step: int, n_to: int) -> list[tuple[int, int]]:
    """(lo, hi) mesh slices of a padded lane axis: full steps plus one
    shorter (d-multiple) remainder."""
    return [(lo, min(lo + step, n_to)) for lo in range(0, n_to, step)]


def _note_occupancy(n: int, n_to: int) -> None:
    """Mesh lane-occupancy telemetry (``tpu.batch.occupancy``): true rows
    over padded mesh lanes.  Metrics live in the server layer; this
    module stays importable without it."""
    try:
        from ..server import metrics

        metrics.gauge("tpu.batch.occupancy").set(n / n_to if n_to else 1.0)
    except Exception:  # pragma: no cover - server layer unavailable
        pass


def _span(name: str):
    """A ``mesh.*`` sub-span of the current device dispatch."""
    from ..observability import flightrec  # lazy: no import cycle

    return flightrec.device_span(name)


def _note_lanes(real: int, n_to: int) -> None:
    """``mesh.lanes{kind}``: real terms or rows, and identity pad lanes,
    sent to the mesh programs."""
    try:
        from ..server import metrics

        lanes = metrics.counter("mesh.lanes", labelnames=("kind",))
        lanes.labels(kind="term").inc(real)
        lanes.labels(kind="pad").inc(n_to - real)
    except Exception:  # pragma: no cover - server layer unavailable
        pass


def _note_compile(when: str) -> None:
    """``mesh.compiles{when}``: sharded programs compiled, at ``prewarm``
    or while ``serving``."""
    try:
        from ..server import metrics

        metrics.counter("mesh.compiles", labelnames=("when",)).labels(
            when=when).inc()
    except Exception:  # pragma: no cover - server layer unavailable
        pass


#: The process's compiled sharded programs, keyed by (the mesh's device
#: ids, program name, input shape): every wrapper over the same devices
#: finds what any other compiled or prewarmed, so a backend built per
#: audit run (``run_audit``) or per daemon boot compiles nothing twice.
_EXES: dict[tuple, object] = {}
#: Guards ``_EXES``; held through a compile, so a program is compiled
#: once even when pipelined batches dispatch from worker threads.
_EXES_LOCK = threading.Lock()


class _Programs:
    """The AOT-compiled programs of one sharded wrapper, one per input
    shape, each compiled at most once per process
    (``jit(...).lower(...).compile()``, the single-device AOT cache's
    scheme, into ``_EXES``).  Every dispatch is booked with the flight
    recorder's jit counters under ``(name, d, shape...)``: a program
    :meth:`warm` compiled before ready is a HIT, one compiled on first
    sight while serving is a MISS."""

    def __init__(self, name: str, mesh: Mesh, fn, in_specs):
        self._name = name
        self._d = mesh.devices.size
        self._ids = tuple(int(dev.id) for dev in mesh.devices.flat)
        self._fn = fn
        self._shardings = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec), in_specs,
            is_leaf=lambda x: isinstance(x, P))

    def _exe(self, key: tuple, avals, when: str):
        """(executable, compiled now) for ``avals``."""
        full = (self._ids, self._name) + key
        with _EXES_LOCK:
            exe = _EXES.get(full)
            if exe is not None:
                return exe, False
            avals = jax.tree.map(
                lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=sh),
                avals, self._shardings)
            exe = _EXES[full] = self._fn.lower(*avals).compile()
        _note_compile(when)
        return exe, True

    def _key(self, key: tuple) -> tuple:
        return (self._name, self._d) + key

    def warm(self, key: tuple, avals) -> str | None:
        """Compile the program for ``avals`` before serving; returns its
        name, or None when the process already holds it."""
        from ..ops import backend as _backend  # lazy: no import cycle

        _backend._mark_seen(self._key(key))
        if not self._exe(key, avals, "prewarm")[1]:
            return None
        return "/".join(str(k) for k in self._key(key))

    def __call__(self, key: tuple, *args):
        from ..ops import backend as _backend  # lazy: no import cycle

        _backend._jit_first_sight(*self._key(key))
        args = jax.device_put(args, self._shardings)
        return self._exe(key, args, "serving")[0](*args)


def _fetch(x) -> np.ndarray:
    """A result on the host; a multi-host job's [n]-sharded result spans
    devices other processes own, so it is gathered everywhere first."""
    if not x.is_fully_addressable:
        from jax.experimental import multihost_utils

        x = multihost_utils.process_allgather(x, tiled=True)
    return np.asarray(x)


def _aval(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _point_aval(n: int):
    return tuple(_aval((curve.NLIMBS, n)) for _ in range(4))


def _point_specs(spec):
    return (spec, spec, spec, spec)


def _row_spec():
    # [20, n] coords / [64, n] windows: shard the minor (batch) axis
    return P(None, AXIS)


def make_sharded_verify_each(mesh: Mesh):
    """Reusable (AOT-cached) sharded per-proof checker for ``mesh``.

    Returns ``call(g, h, y1, y2, r1, r2, ws, wc, real=None) -> [n] bool``
    (a host array); ``g``/``h`` [20, 1] (replicated), row arrays sharded
    on the batch axis.  Ragged batches are padded to a mesh-size multiple
    (identity rows with zero windows verify True and are sliced off the
    result); ``real`` counts the rows that are not such padding
    (``mesh.lanes``; default all ``n``).  Span: ``mesh.each`` (the rows
    placed on the mesh, the programs and the mask's fetch).
    ``call.warm(n)`` compiles the programs an ``n``-lane call dispatches.
    """
    rows = _row_spec()
    rep = P()
    in_specs = (
        _point_specs(rep),
        _point_specs(rep),
        _point_specs(rows),
        _point_specs(rows),
        _point_specs(rows),
        _point_specs(rows),
        rows,
        rows,
    )

    def mesh_each(*args):  # the name is the device trace's module name
        return verify.verify_each_kernel(*args)

    programs = _Programs("mesh_each", mesh, jax.jit(shard_map(
        mesh_each,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(AXIS),
        check_vma=False,
    )), in_specs)
    d = mesh.devices.size

    def call(g, h, y1, y2, r1, r2, ws, wc, real: int | None = None):
        n = ws.shape[-1]
        step, n_to = _mesh_step(d, n)
        _note_lanes(n if real is None else real, n_to)
        y1, y2, r1, r2 = (pad_to_multiple(p, n_to) for p in (y1, y2, r1, r2))
        ws, wc = pad_windows(ws, n_to), pad_windows(wc, n_to)
        # the last slice may be a short (but d-multiple) remainder
        with _span("mesh.each"):
            chunks = [
                programs(
                    (hi - lo,), g, h,
                    *(tuple(c[..., lo:hi] for c in p)
                      for p in (y1, y2, r1, r2)),
                    ws[:, lo:hi], wc[:, lo:hi])
                for lo, hi in _slices(step, n_to)
            ]
            mask = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks)
            return _fetch(mask)[:n]

    def warm(n: int) -> list[str]:
        step, n_to = _mesh_pad(d, n)
        names = []
        for lo, hi in _slices(step, n_to):
            w = hi - lo
            names.append(programs.warm((w,), (
                _point_aval(1), _point_aval(1),
                *(_point_aval(w) for _ in range(4)),
                _aval((curve.NWINDOWS, w)), _aval((curve.NWINDOWS, w)))))
        return [x for x in names if x]

    call.warm = warm
    return call


def sharded_verify_each(mesh: Mesh, g, h, y1, y2, r1, r2, ws, wc):
    """One-shot convenience wrapper over :func:`make_sharded_verify_each`."""
    return make_sharded_verify_each(mesh)(g, h, y1, y2, r1, r2, ws, wc)


def make_sharded_prove(mesh: Mesh):
    """Sharded bulk commitment generation — the proving-side DP shard
    (BASELINE config 3 at mesh scale; reference analog
    ``prover/mod.rs:115-121``).  Comb tables are replicated, the digit
    batch axis is sharded, and because proofs are independent there are
    NO collectives: pure data parallelism over the mesh.

    Returns ``call(tables_g, tables_h, digits) -> (r1_bytes, r2_bytes)``
    with digits [64, n] (LSB window first) and [32, n] wire-byte outputs.
    Ragged batches pad with zero-digit lanes (identity commitments,
    sliced off)."""
    from ..ops import prove as prove_mod

    rows = _row_spec()
    fn = jax.jit(
        shard_map(
            prove_mod._commitments_kernel.__wrapped__,
            mesh=mesh,
            in_specs=(_point_specs(P()), _point_specs(P()), rows),
            out_specs=(rows, rows),
            check_vma=False,
        )
    )
    d = mesh.devices.size

    def call(tg, th, digits):
        n = digits.shape[-1]
        # proofs are independent, so over-cap batches run as mesh slices
        step, n_to = _mesh_step(d, n)
        digits = pad_windows(digits, n_to)
        if n_to <= step:
            b1, b2 = fn(tg, th, digits)
            return b1[:, :n], b2[:, :n]
        parts = [fn(tg, th, digits[:, lo:min(lo + step, n_to)])
                 for lo in range(0, n_to, step)]
        b1 = jnp.concatenate([p[0] for p in parts], axis=-1)
        b2 = jnp.concatenate([p[1] for p in parts], axis=-1)
        return b1[:, :n], b2[:, :n]

    return call


def sharded_prove(mesh: Mesh, tg, th, digits):
    """One-shot convenience wrapper over :func:`make_sharded_prove`."""
    return make_sharded_prove(mesh)(tg, th, digits)


def _combined_partial(r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac):
    rows = verify._msm_rows(
        [
            verify.build_table(r1),
            verify.build_table(y1),
            verify.build_table(r2),
            verify.build_table(y2),
        ],
        [w_a, w_ac, w_ba, w_bac],
    )
    partial = curve.tree_sum(rows, axis=-1)
    return tuple(c[:, None] for c in partial)  # [20, 1] per device


def make_sharded_combined_check(mesh: Mesh):
    """Reusable (jit-cached) sharded combined-RLC checker for ``mesh``.

    Each device reduces its shard to one partial point (local tree-sum);
    the ``D`` partials are then combined and tested against the identity.
    The caller has already appended the ``(-sum a s) G + (-b sum a s) H``
    correction row (see :meth:`cpzk_tpu.ops.backend.TpuBackend.verify_combined`);
    ragged batches are padded to a mesh-size multiple (identity rows with
    zero windows contribute the identity to the sum).
    """
    rows = _row_spec()
    partial_fn = shard_map(
        _combined_partial,
        mesh=mesh,
        in_specs=(
            _point_specs(rows),
            _point_specs(rows),
            _point_specs(rows),
            _point_specs(rows),
            rows,
            rows,
            rows,
            rows,
        ),
        out_specs=_point_specs(P(None, AXIS)),
        check_vma=False,
    )

    def check(*args):
        partials = partial_fn(*args)  # [20, D] coords, one lane per device
        total = curve.tree_sum(partials, axis=-1)
        return curve.is_identity(total)

    jcheck = jax.jit(check)
    d = mesh.devices.size

    def call(r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac):
        n = w_a.shape[-1]
        n_to = -(-n // d) * d
        r1, y1, r2, y2 = (pad_to_multiple(p, n_to) for p in (r1, y1, r2, y2))
        w_a, w_ac, w_ba, w_bac = (
            pad_windows(w, n_to) for w in (w_a, w_ac, w_ba, w_bac)
        )
        return jcheck(r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac)

    return call


def sharded_combined_check(mesh: Mesh, r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac):
    """One-shot convenience wrapper over :func:`make_sharded_combined_check`."""
    return make_sharded_combined_check(mesh)(r1, y1, r2, y2, w_a, w_ac, w_ba, w_bac)


def _reduce_partials(parts):
    """Mesh-slice [20, D] partial points -> does their sum hit the
    identity coset (one program: concatenate, tree-sum, test)."""
    from ..ops import backend as _backend  # lazy: no import cycle

    return _backend._partials_impl(_backend._stack_partials(list(parts)))


def make_sharded_msm_check(mesh: Mesh):
    """Reusable (AOT-cached) sharded Pippenger-MSM == identity checker.

    An MSM is a sum over (point, scalar) terms, so lane-sharding is exact:
    each device runs the full windowed-Pippenger kernel on its shard of the
    terms ([20, m/D] coords + [K, m/D] digits), producing one partial point;
    the ``D`` partials combine with one tiny cross-device gather — the ICI
    traffic is 4 coords x 20 limbs per device per batch, nothing else.

    Returns ``call(points, digits, c, real=None) -> bool``; ``real``
    counts the terms that are not identity padding (``mesh.lanes``;
    default all ``m``).  Span: ``mesh.msm`` (the terms placed on the
    mesh, the slice programs, the partials reduction and the verdict's
    fetch).  ``call.warm(m, c)`` compiles the programs an ``m``-term
    call dispatches.
    """
    rows = _row_spec()
    d = mesh.devices.size
    slice_programs: dict[int, _Programs] = {}  # by window size c
    reduce_programs: dict[int, _Programs] = {}  # by slice count

    def slice_program(c: int) -> _Programs:
        if c not in slice_programs:
            # the name is the device trace's module name
            def mesh_msm_slice(points, digits):
                return msm.msm_kernel(points, digits, c)  # [20, 1] per device

            in_specs = (_point_specs(rows), rows)
            slice_programs[c] = _Programs(f"mesh_msm/{c}", mesh, jax.jit(
                shard_map(
                    mesh_msm_slice,
                    mesh=mesh,
                    in_specs=in_specs,
                    out_specs=_point_specs(P(None, AXIS)),
                    check_vma=False,
                )), in_specs)  # (points, digits) -> [20, D] partials
        return slice_programs[c]

    def reduce_program(k: int) -> _Programs:
        if k not in reduce_programs:
            def mesh_partials(*parts):
                return _reduce_partials(parts)

            in_specs = tuple(_point_specs(rows) for _ in range(k))
            reduce_programs[k] = _Programs(
                "mesh_partials", mesh, jax.jit(mesh_partials), in_specs)
        return reduce_programs[k]

    def call(points, digits, c: int, real: int | None = None) -> bool:
        m = digits.shape[-1]
        # over-cap MSMs run as mesh slices whose [20, D] partials
        # concatenate into one final tree-sum + identity test
        step, m_to = _mesh_step(d, m)
        _note_lanes(m if real is None else real, m_to)
        points = pad_to_multiple(points, m_to)
        digits = pad_windows(digits, m_to)
        with _span("mesh.msm"):
            parts = [
                slice_program(c)(
                    (hi - lo,), tuple(cd[..., lo:hi] for cd in points),
                    digits[:, lo:hi])
                for lo, hi in _slices(step, m_to)
            ]
            return bool(reduce_program(len(parts))((d * len(parts),), *parts))

    def warm(m: int, c: int) -> list[str]:
        step, m_to = _mesh_pad(d, m)
        bounds = _slices(step, m_to)
        k = msm.num_windows(c)
        names = [
            slice_program(c).warm(
                (hi - lo,), (_point_aval(hi - lo), _aval((k, hi - lo))))
            for lo, hi in bounds
        ]
        names.append(reduce_program(len(bounds)).warm(
            (d * len(bounds),), tuple(_point_aval(d) for _ in bounds)))
        return [x for x in names if x]

    call.warm = warm
    return call


def sharded_msm_check(mesh: Mesh, points, digits, c: int):
    """One-shot convenience wrapper over :func:`make_sharded_msm_check`."""
    return make_sharded_msm_check(mesh)(points, digits, c)
