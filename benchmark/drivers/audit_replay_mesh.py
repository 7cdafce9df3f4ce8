"""The offline proof-log audit sharded over a mesh of the cell's chips:
``audit_replay`` (``run_audit`` in this process over the seeded log in
whole passes, and the plain reference's checks, unchanged) with the mesh's
own prewarm.

The prewarm is ``pipeline.build_backend("tpu", chips).prewarm([quantum])``:
the sharded slice MSM, its partials reduction and the sharded
``verify_each`` a quantum dispatches, which every ``run_audit`` call's
backend then finds in the process.  The single-device kernels are left
cold: a mesh dispatches none of them.  After the window a ``#`` note gives
the jit misses booked and the XLA compiles (or loads from the persistent
cache) made inside it; both should be 0.

A program that keeps its sharded programs per backend (no process-wide
``parallel.mesh._EXES``) cannot run the cell: nothing can prewarm what
each ``run_audit`` call's new backend dispatches, so every pass would
compile inside the window.  The run then exits non-zero before it
touches a chip.
"""

from __future__ import annotations

import contextlib
import functools
import time

import harness

audit_replay = harness.load_module("drivers", "audit_replay")


def run(r: harness.Run) -> harness.Outcome:
    with _mesh():
        return audit_replay.run(r)


def readings(r: harness.Run, seeds: list[int]) -> list[dict]:
    """control.py: as ``audit_replay.readings``, on the mesh."""
    with _mesh():
        return audit_replay.readings(r, seeds)


# a traced run traces the window's first quanta up to this long: four
# device planes of the sharded MSM's per-op events.  With the one-chip
# cell's 5 s, a traced run on the 4-chip v5e host gave no result 209 s
# after its log was made; 1 s holds two or three ~0.5 s quanta
TRACE_S = 1.0


@contextlib.contextmanager
def _mesh():
    """``audit_replay`` with this module's set-up and trace length, its
    window counted."""
    setup, replay, trace_s = (audit_replay._setup, audit_replay._replay,
                              audit_replay.TRACE_S)
    audit_replay._setup = _setup
    audit_replay._replay = functools.partial(_counted, replay)
    audit_replay.TRACE_S = TRACE_S
    try:
        yield
    finally:
        audit_replay._setup, audit_replay._replay = setup, replay
        audit_replay.TRACE_S = trace_s


def _setup(r: harness.Run):
    """The device check, the native core, the compile cache and the mesh
    prewarm: (device, run_audit keywords)."""
    import jax

    from cpzk_tpu import jaxrt
    from cpzk_tpu.audit import pipeline
    from cpzk_tpu.parallel import mesh

    if not hasattr(mesh, "_EXES"):
        raise SystemExit(
            "benchmark: this program keeps its sharded programs per backend, "
            "so every run_audit call would compile them inside the window; "
            "it cannot run this cell")

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    harness.check_device(r, device)
    cfg = r.config["audit"]
    chips = int(r.workload["chips"])
    if int(cfg["mesh_devices"]) != chips:
        # 0 would mean every visible chip: the cell runs the mesh it names
        raise ValueError(f"audit mesh_devices {cfg['mesh_devices']} is not "
                         f"the cell's {chips} chips")
    built = harness.build_native()
    jaxrt.enable_compile_cache()
    quantum = int(r.sizes.get("quantum", cfg["quantum"]))
    t = time.monotonic()
    warmed = pipeline.build_backend(cfg["backend"], chips).prewarm([quantum])
    r.note(f"prewarm: {len(warmed)} sharded programs compiled in "
           f"{time.monotonic() - t:.1f}s ({', '.join(warmed) or 'all cached'}); "
           f"native core make {built:.1f}s")
    return device, dict(quantum=quantum, backend=cfg["backend"],
                        mesh_devices=chips, lanes=int(cfg["lanes"]),
                        resume=False)


# every compile request that misses JAX's in-memory caches, whether XLA
# compiles it or loads it from the persistent cache
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _counted(replay, r: harness.Run, log: str, kw: dict, seconds: float,
             trace: bool):
    """``replay``, then a note of the jit misses the program booked and
    the programs JAX compiled while it ran."""
    import jax

    from cpzk_tpu.server import metrics

    compiles = []

    def listener(event: str, secs: float, **_) -> None:
        if event == _COMPILE_EVENT:
            compiles.append(secs)

    def misses() -> float:
        return metrics.read("tpu.jit.cache", labels={"outcome": "miss"})

    before = misses()
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        return replay(r, log, kw, seconds, trace)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
        r.note(f"window: {misses() - before:.0f} jit misses booked, "
               f"{len(compiles)} programs compiled or loaded "
               f"({sum(compiles):.1f}s)")
