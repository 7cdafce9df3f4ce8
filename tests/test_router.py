"""Multi-chip serving plane tests (ISSUE 12): deadline-aware placement
over per-device dispatch lanes, per-lane breaker isolation (one sick
chip degrades only its lane), the ``lanes = 1`` structural fast path,
drain-then-join shutdown across all lanes, the big-batch mesh path, the
audit pipeline's router fan-out (digest byte-identical to single-lane),
per-device AOT prewarm cache keys, the mesh-devices validation fix, and
the ``[tpu] lanes`` / ``mesh_threshold`` knob plumbing + drift guard.
"""

import asyncio
import json
import pathlib
import re
import time

import pytest

from cpzk_tpu.observability import get_flight_recorder
from cpzk_tpu.protocol.batch import CpuBackend, VerifierBackend
from cpzk_tpu.server.batching import DynamicBatcher
from cpzk_tpu.server.dispatch import LaneStopped
from cpzk_tpu.server.router import LaneRouter

from test_dispatch_lane import ExplodingBackend, RecordingBackend, make_entries

ROOT = pathlib.Path(__file__).resolve().parent.parent


def run(coro):
    return asyncio.run(coro)


@pytest.fixture(autouse=True)
def _fresh_recorder():
    rec = get_flight_recorder()
    rec.clear()
    yield
    rec.clear()


class SlowBackend(VerifierBackend):
    """CPU oracle with a fixed per-call delay (a slow chip)."""

    prefers_combined = False

    def __init__(self, delay_s: float = 0.0):
        self.calls = 0
        self.delay_s = delay_s
        self._inner = CpuBackend()

    def verify_combined(self, rows, beta):  # pragma: no cover - unused
        raise AssertionError("prefers_combined is False")

    def verify_each(self, rows):
        self.calls += 1
        if self.delay_s:
            time.sleep(self.delay_s)
        return self._inner.verify_each(rows)


# --- placement ---------------------------------------------------------------


def test_placement_prefers_least_backlogged_lane():
    """Skewed lane depths: a new batch lands on the lane with the
    shortest predicted completion (least pending entries at equal drain
    rates)."""
    router = LaneRouter([CpuBackend(), CpuBackend(), CpuBackend()])
    for slot, pending in zip(router._slots, (500, 3, 900), strict=True):
        slot.pending = pending
        slot.drain_rate = 100.0
    slot, probe = router._pick(4)
    assert not probe
    assert slot is router._slots[1]


def test_placement_is_drain_rate_aware():
    """Equal depths, unequal drain rates: the faster lane wins — depth
    alone would tie, but predicted completion = depth / drain rate."""
    router = LaneRouter([CpuBackend(), CpuBackend()])
    for slot, rate in zip(router._slots, (10.0, 1000.0), strict=True):
        slot.pending = 200
        slot.drain_rate = rate
    for _ in range(4):  # stable across the rotating tie-break
        slot, _ = router._pick(4)
        assert slot is router._slots[1]


def test_placement_spreads_cold_lanes():
    """Cold start (no drain history anywhere): the rotating tie-break
    spreads batches instead of piling them on lane 0."""
    router = LaneRouter([CpuBackend() for _ in range(4)])
    picked = set()
    for _ in range(8):
        slot, _ = router._pick(1)
        slot.pending += 1  # as submit() would
        picked.add(slot.label)
    assert len(picked) >= 3, picked


def test_router_serves_across_all_lanes():
    """Sustained load through the batcher lands dispatches on every lane
    (the acceptance shape: per-lane dispatch counters all nonzero), and
    every flight record carries its lane index."""
    router = LaneRouter([CpuBackend() for _ in range(3)])

    async def main():
        batcher = DynamicBatcher(
            CpuBackend(), max_batch=4, window_ms=1.0, max_queue=10_000,
            router=router,
        )
        batcher.start()
        waves = [make_entries(4) for _ in range(9)]
        results = await asyncio.gather(
            *[batcher.submit_many(w) for w in waves]
        )
        status = router.status()
        await batcher.stop()
        return results, status

    results, status = run(main())
    assert all(r == [None] * 4 for r in results)
    assert [row["dispatches"] > 0 for row in status["lanes"]] == [True] * 3
    assert sum(row["dispatches"] for row in status["lanes"]) == 9
    lanes_seen = {rec.lane for rec in get_flight_recorder().snapshot()}
    assert lanes_seen == {0, 1, 2}


def test_pipeline_depth_is_per_lane():
    """``pipeline_depth`` bounds in-flight batches PER lane: with depth 1
    and 3 lanes, three batches run at once (a global bound of 1 kept two
    of three chips idle)."""
    import threading
    import time

    lock = threading.Lock()
    active = [0, 0]  # now, peak

    class Slow(CpuBackend):
        def _busy(self):
            with lock:
                active[0] += 1
                active[1] = max(active[1], active[0])
            time.sleep(0.2)
            with lock:
                active[0] -= 1

        def verify_combined(self, rows, beta):
            self._busy()
            return super().verify_combined(rows, beta)

        def verify_each(self, rows):
            self._busy()
            return super().verify_each(rows)

    router = LaneRouter([Slow() for _ in range(3)])

    async def main():
        batcher = DynamicBatcher(
            CpuBackend(), max_batch=4, window_ms=1.0, max_queue=10_000,
            pipeline_depth=1, router=router,
        )
        batcher.start()
        results = await asyncio.gather(
            *[batcher.submit_many(make_entries(4)) for _ in range(3)]
        )
        await batcher.stop()
        return results

    assert run(main()) == [[None] * 4] * 3
    assert active[1] == 3


# --- per-lane breaker --------------------------------------------------------


def test_sick_lane_degrades_only_itself_and_readmits():
    """Per-lane breaker isolation: a raising backend in lane 2 errors
    only the batches placed on it before its breaker opens; lanes 0/1/3
    keep settling with zero errors; after the cooldown the next batch
    probes lane 2 and (backend healed) re-admits it."""
    sick = ExplodingBackend(explode_times=1)  # heals after one raise
    backends = [CpuBackend(), CpuBackend(), sick, CpuBackend()]
    router = LaneRouter(backends, recovery_after_s=0.05)

    async def main():
        router.start()
        errors = 0
        # drive until lane 2 has taken (and failed) its batch
        for _ in range(12):
            try:
                res = await router.submit(make_entries(2), None)
                assert res == [None, None]
            except RuntimeError:
                errors += 1
            if errors:
                break
        assert errors == 1, "lane 2 never drew a batch"
        assert router.status()["lanes"][2]["breaker"] == "open"
        # while OPEN, lane 2 is skipped: everything settles cleanly
        for _ in range(8):
            assert await router.submit(make_entries(2), None) == [None, None]
        assert sick.calls == 1  # no traffic reached the sick chip
        healthy_errors = [
            router.status()["lanes"][i]["errors"] for i in (0, 1, 3)
        ]
        assert healthy_errors == [0, 0, 0]
        # past the cooldown the next batch is the probe; backend healed,
        # so the lane re-admits
        await asyncio.sleep(0.06)
        for _ in range(8):
            assert await router.submit(make_entries(2), None) == [None, None]
            if router.status()["lanes"][2]["breaker"] == "closed":
                break
        status = router.status()["lanes"][2]
        assert status["breaker"] == "closed"
        assert status["probes"] == 1
        assert sick.calls >= 2  # the probe ran on the sick lane
        await router.stop()

    run(main())


def test_all_lanes_open_still_routes():
    """Every breaker OPEN is not a dead server: the router places the
    batch anyway (least-loaded) rather than refusing all work."""
    sick = ExplodingBackend()  # never heals
    router = LaneRouter([sick], recovery_after_s=1000.0)

    async def main():
        router.start()
        with pytest.raises(RuntimeError):
            await router.submit(make_entries(2), None)
        assert router.status()["lanes"][0]["breaker"] == "open"
        with pytest.raises(RuntimeError):  # routed anyway, still sick
            await router.submit(make_entries(2), None)
        await router.stop()

    run(main())


# --- lanes = 1 structural fast path ------------------------------------------


def test_single_lane_config_never_constructs_a_router(monkeypatch, tmp_path):
    """``[tpu] lanes = 1`` (the default) must keep the single-lane path
    structurally unchanged: ``build_backend`` never constructs a
    LaneRouter (spy raises), the batcher has no router, and batches
    verify exactly as before."""
    from cpzk_tpu.server import router as router_mod
    from cpzk_tpu.server.__main__ import build_backend
    from cpzk_tpu.server.config import ServerConfig

    def boom(*a, **k):  # noqa: ARG001
        raise AssertionError("LaneRouter constructed on the lanes=1 path")

    monkeypatch.setattr(router_mod.LaneRouter, "__init__", boom)
    cfg = ServerConfig()
    cfg.tpu.backend = "tpu"
    cfg.tpu.lanes = 1
    backend, batcher = build_backend(cfg)
    assert batcher is not None and batcher.router is None

    async def main():
        batcher.start()
        results = await batcher.submit_many(make_entries(2))
        await batcher.stop()
        return results

    assert run(main()) == [None, None]
    # single-lane flight records carry no lane index (nothing changed)
    assert {r.lane for r in get_flight_recorder().snapshot()} == {None}


# --- shutdown ----------------------------------------------------------------


def test_router_stop_resolves_every_future_across_lanes():
    """Drain-then-join fanned over N lanes: stop() resolves every
    accepted future exactly once with real results, refuses new work."""
    backends = [SlowBackend(delay_s=0.03) for _ in range(3)]
    router = LaneRouter(backends)

    async def main():
        router.start()
        futs = [router.submit(make_entries(2), None) for _ in range(6)]
        stop_task = asyncio.ensure_future(router.stop())
        await asyncio.sleep(0)
        with pytest.raises(LaneStopped):
            router.submit(make_entries(1), None)
        await stop_task
        assert all(f.done() for f in futs), "stop() returned before drain"
        return await asyncio.gather(*futs)

    results = run(main())
    assert results == [[None, None]] * 6
    assert sum(b.calls for b in backends) == 6


# --- mesh path ---------------------------------------------------------------


def test_mesh_threshold_routes_big_batches_to_the_mesh_lane():
    """Batches at/above ``mesh_threshold`` take the mesh lane (one
    sharded program); smaller ones stay on the per-device lanes."""
    mesh = RecordingBackend()
    lanes = [RecordingBackend(), RecordingBackend()]
    router = LaneRouter(lanes, mesh_backend=mesh, mesh_threshold=8)

    async def main():
        router.start()
        big = await router.submit(make_entries(8), None)
        small = await router.submit(make_entries(2), None)
        status = router.status()
        await router.stop()
        return big, small, status

    big, small, status = run(main())
    assert big == [None] * 8 and small == [None] * 2
    assert mesh.sizes == [8]
    assert sum(len(b.sizes) for b in lanes) == 1
    assert status["mesh"]["dispatches"] == 1
    assert status["mesh_threshold"] == 8
    lanes_seen = {rec.lane for rec in get_flight_recorder().snapshot()}
    assert lanes_seen == set()  # router.submit(None stages): no records


def test_mesh_lane_breaker_falls_back_to_per_device_lanes():
    """A mesh blow-up opens the mesh breaker: the next big batch routes
    per-device instead of dying on the mesh again."""
    mesh = ExplodingBackend()  # never heals
    lanes = [RecordingBackend(), RecordingBackend()]
    router = LaneRouter(
        lanes, mesh_backend=mesh, mesh_threshold=4, recovery_after_s=1000.0,
    )

    async def main():
        router.start()
        with pytest.raises(RuntimeError):
            await router.submit(make_entries(4), None)
        ok = await router.submit(make_entries(4), None)
        status = router.status()
        await router.stop()
        return ok, status

    ok, status = run(main())
    assert ok == [None] * 4
    assert status["mesh"]["breaker"] == "open"
    assert sum(len(b.sizes) for b in lanes) == 1


# --- audit through the router ------------------------------------------------


def test_audit_router_digest_identical_to_single_lane(tmp_path):
    """The audit pipeline replaying through the LaneRouter (each quantum
    fanned across lanes) produces a BYTE-identical signed report to the
    single-engine replay — placement never reorders the fold."""
    from cpzk_tpu.audit.__main__ import main as audit_main
    from cpzk_tpu.audit.pipeline import run_audit

    log = str(tmp_path / "p.log")
    rc = audit_main(["generate", "--n", "60", "--out", log,
                     "--users", "4", "--reject-frac", "0.1",
                     "--mismatch-frac", "0.05"])
    assert rc == 0
    key = str(tmp_path / "shared.key")
    single = str(tmp_path / "single.json")
    routed = str(tmp_path / "routed.json")
    rep1 = run_audit(log, single, key_path=key, quantum=16, lanes=1)
    rep2 = run_audit(log, routed, key_path=key, quantum=16, lanes=3)
    assert rep1["totals"]["mismatched"] > 0  # the audit found the lies
    assert rep1["digest"] == rep2["digest"]
    assert rep1["totals"] == rep2["totals"]
    b1 = pathlib.Path(single).read_bytes()
    b2 = pathlib.Path(routed).read_bytes()
    assert b1 == b2, "routed replay report differs from single-lane"


def test_audit_cli_accepts_lanes(tmp_path):
    from cpzk_tpu.audit.__main__ import main as audit_main

    log = str(tmp_path / "c.log")
    assert audit_main(["generate", "--n", "12", "--out", log]) == 0
    report = str(tmp_path / "c.json")
    rc = audit_main(["run", "--log", log, "--report", report,
                     "--quantum", "5", "--lanes", "2", "--quiet"])
    assert rc == 0
    assert audit_main(["verify-report", "--report", report]) == 0


# --- per-device prewarm / AOT cache keys -------------------------------------


def test_prewarm_keys_are_device_scoped(monkeypatch):
    """The prewarm-bug fix: prewarm with an explicit device registers
    device-suffixed jit/AOT keys, a pinned backend's dispatch finds
    THEM (zero compile spans — jit HITs booked), and the unpinned
    default-device keys stay untouched (no phantom hits)."""
    import jax

    from cpzk_tpu.ops import backend as backend_mod
    from cpzk_tpu.ops.backend import TpuBackend, prewarm_executables

    monkeypatch.setattr(backend_mod, "_JIT_SEEN", set())
    monkeypatch.setattr(backend_mod, "_AOT_CACHE", {})
    dev = jax.local_devices()[0]
    warmed = prewarm_executables([6], devices=[dev])
    suffix = f"dev{dev.id}"
    assert set(warmed) == {f"combined/8/{suffix}", f"each/8/True/{suffix}"}
    # idempotent per (shape, device); the default device is NOT warmed
    assert prewarm_executables([6], devices=[dev]) == []
    assert all(key[-1] == suffix for key in backend_mod._AOT_CACHE)
    assert backend_mod._aot_get("combined", 8) is None  # unpinned miss

    async def main():
        batcher = DynamicBatcher(
            TpuBackend(device=dev), max_batch=16, window_ms=1.0,
        )
        batcher.start()
        results = await batcher.submit_many(make_entries(6))
        await batcher.stop()
        return results

    assert run(main()) == [None] * 6
    rec = get_flight_recorder().snapshot()[-1]
    assert rec.jit_misses == 0, rec.to_dict()
    assert rec.jit_hits > 0
    assert rec.stages_s.get("compile", 0.0) == 0.0
    assert rec.stages_s.get("execute", 0.0) > 0.0


def test_prewarm_zero_compiles_on_lane_n_gt_0(monkeypatch):
    """ISSUE 12 satellite acceptance on a real multi-device host (the CI
    mesh-smoke job forces 8 host devices; self-skips on 1): after a
    per-device prewarm, lane N>0's FIRST dispatch books jit HITs only —
    zero ``compile`` spans, mirroring the existing lane-0 pin."""
    import jax

    from cpzk_tpu.ops import backend as backend_mod
    from cpzk_tpu.ops.backend import TpuBackend, prewarm_executables

    devices = jax.local_devices()
    if len(devices) < 2:
        pytest.skip("needs >1 local device (XLA_FLAGS host device count)")
    monkeypatch.setattr(backend_mod, "_JIT_SEEN", set())
    monkeypatch.setattr(backend_mod, "_AOT_CACHE", {})
    prewarm_executables([6], devices=devices[:2])

    async def main():
        # pinned to lane 1's device — the lane that used to eat the
        # first-dispatch compile while the recorder booked a phantom HIT
        batcher = DynamicBatcher(
            TpuBackend(device=devices[1]), max_batch=16, window_ms=1.0,
        )
        batcher.start()
        results = await batcher.submit_many(make_entries(6))
        await batcher.stop()
        return results

    assert run(main()) == [None] * 6
    rec = get_flight_recorder().snapshot()[-1]
    assert rec.jit_misses == 0, rec.to_dict()
    assert rec.jit_hits > 0
    assert rec.stages_s.get("compile", 0.0) == 0.0
    assert rec.stages_s.get("execute", 0.0) > 0.0


def test_device_scope_suffixes_jit_keys(monkeypatch):
    """``_jit_first_sight`` keys are per-device facts under
    ``device_scope``: the same shape on another 'device' is a fresh
    first sight (compile attribution per lane), while the unpinned path
    keeps its historical unsuffixed keys."""
    import jax

    from cpzk_tpu.ops import backend as backend_mod

    monkeypatch.setattr(backend_mod, "_JIT_SEEN", set())
    dev = jax.local_devices()[0]
    assert backend_mod._jit_first_sight("combined", 64) is True
    assert backend_mod._jit_first_sight("combined", 64) is False
    with backend_mod.device_scope(dev):
        # same shape, pinned device: a separate first sight
        assert backend_mod._jit_first_sight("combined", 64) is True
        assert backend_mod._jit_first_sight("combined", 64) is False
    assert backend_mod._jit_first_sight("combined", 64) is False
    assert ("combined", 64) in backend_mod._JIT_SEEN
    assert ("combined", 64, f"dev{dev.id}") in backend_mod._JIT_SEEN


def test_tpu_backend_rejects_device_plus_mesh():
    from cpzk_tpu.ops.backend import TpuBackend

    with pytest.raises(ValueError, match="mesh"):
        TpuBackend(mesh_devices=0, device=object())


# --- mesh validation fix -----------------------------------------------------


def test_resolve_mesh_devices_rejects_overcommit():
    """The satellite fix: asking for more devices than exist raises a
    ValueError naming both numbers instead of clamping silently."""
    import jax

    from cpzk_tpu.parallel import resolve_lane_devices, resolve_mesh_devices

    n = jax.device_count()
    with pytest.raises(ValueError, match=rf"mesh_devices={n + 7}.*{n} visible"):
        resolve_mesh_devices(n + 7)
    with pytest.raises(ValueError, match=rf"lanes={n + 7}"):
        resolve_lane_devices(n + 7)
    # unchanged semantics inside bounds
    assert resolve_mesh_devices(None) is None
    assert resolve_mesh_devices(1) is None
    if n == 1:
        assert resolve_mesh_devices(0) is None
        assert resolve_lane_devices(-1) is None
    assert resolve_lane_devices(1) is None


# --- statusz rows ------------------------------------------------------------


def test_statusz_carries_per_lane_rows():
    from cpzk_tpu.observability.opsplane import OpsSources

    router = LaneRouter([CpuBackend(), CpuBackend()])

    async def main():
        batcher = DynamicBatcher(
            CpuBackend(), max_batch=4, window_ms=1.0, router=router,
        )
        batcher.start()
        await batcher.submit_many(make_entries(3))
        doc = OpsSources(batcher=batcher).statusz()
        await batcher.stop()
        return doc

    doc = run(main())
    rows = doc["lanes"]["lanes"]
    assert len(rows) == 2
    assert {row["lane"] for row in rows} == {"0", "1"}
    assert all(row["breaker"] == "closed" for row in rows)
    assert sum(row["dispatches"] for row in rows) == 1
    # single-lane batcher: the block is null, not an empty list
    async def single():
        batcher = DynamicBatcher(CpuBackend(), max_batch=4, window_ms=1.0)
        doc = OpsSources(batcher=batcher).statusz()
        return doc

    assert run(single())["lanes"] is None


# --- config knobs ------------------------------------------------------------


def test_lanes_config_env_layering_and_validation(monkeypatch):
    from cpzk_tpu.server.config import ServerConfig

    monkeypatch.setenv("SERVER_TPU_LANES", "-1")
    monkeypatch.setenv("SERVER_TPU_MESH_THRESHOLD", "32768")
    cfg = ServerConfig()
    cfg._merge_env()
    assert cfg.tpu.lanes == -1
    assert cfg.tpu.mesh_threshold == 32768
    cfg.validate()

    cfg = ServerConfig()
    cfg.tpu.lanes = 0
    with pytest.raises(ValueError, match="lanes"):
        cfg.validate()
    cfg = ServerConfig()
    cfg.tpu.lanes = -2
    with pytest.raises(ValueError, match="lanes"):
        cfg.validate()
    cfg = ServerConfig()
    cfg.tpu.mesh_threshold = -1
    with pytest.raises(ValueError, match="mesh_threshold"):
        cfg.validate()
    # a mesh crossover without multi-lane serving is a misconfiguration
    cfg = ServerConfig()
    cfg.tpu.mesh_threshold = 1000
    cfg.tpu.lanes = 1
    with pytest.raises(ValueError, match="mesh_threshold"):
        cfg.validate()
    cfg.tpu.lanes = -1
    cfg.validate()


def test_lanes_config_keys_documented():
    """CI drift guard (pattern from test_audit.py): the multi-chip
    serving knobs ship in the TOML example, the .env example, and the
    operations-doc knob inventory."""
    toml_text = (ROOT / "config" / "server.toml.example").read_text()
    m = re.search(r"^\[tpu\]$", toml_text, re.M)
    assert m, "[tpu] section missing from config/server.toml.example"
    section = toml_text[m.end():].split("\n[", 1)[0]
    env_text = (ROOT / ".env.example").read_text()
    docs = (ROOT / "docs" / "operations.md").read_text()
    for key in ("lanes", "mesh_threshold"):
        assert re.search(rf"^{key}\s*=", section, re.M), (
            f"[tpu] key {key!r} missing from config/server.toml.example"
        )
        assert f"SERVER_TPU_{key.upper()}" in env_text, (
            f"SERVER_TPU_{key.upper()} missing from .env.example"
        )
        assert f"`tpu.{key}`" in docs, (
            f"`tpu.{key}` missing from the docs/operations.md knob "
            "inventory"
        )


def test_perf_entry_lanes_is_a_config_key(tmp_path):
    """The perf gate treats the lane count as a config-key component:
    same name/n at a different lane count never gates against the
    1-lane baseline (added configs seed their own trajectory), and old
    baselines load as lanes=1."""
    from cpzk_tpu.observability.perf import (
        PerfEntry,
        compare_entries,
        load_snapshot,
        write_snapshot,
    )

    old = [PerfEntry("e2e_curve.grpc", "cpu", 256, 1000.0, "proofs/s")]
    new = [
        PerfEntry("e2e_curve.grpc", "cpu", 256, 990.0, "proofs/s"),
        PerfEntry("e2e_curve.grpc", "cpu", 256, 10.0, "proofs/s", lanes=8),
    ]
    report = compare_entries(old, new, threshold=0.35)
    assert report["passed"], report  # the 8-lane entry is only_new
    assert report["only_new"] == [
        # the key carries every config component: lanes (this test's
        # subject) and the transport wire mode (defaults to "python" —
        # exactly what pre-wire baselines measured)
        ("e2e_curve.grpc", "cpu", 256, "proofs/s", 8, "python")
    ]
    # round-trips: lanes serialized only when != 1, parsed back into key
    path = str(tmp_path / "snap.json")
    write_snapshot(path, new)
    loaded = load_snapshot(path)
    assert sorted(e.key() for e in loaded) == sorted(e.key() for e in new)
    raw = json.loads(pathlib.Path(path).read_text())
    lanes_fields = [e.get("lanes") for e in raw["entries"]]
    assert sorted(lanes_fields, key=str) == [8, None]
