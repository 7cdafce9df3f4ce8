"""Flight-recorder subsystem tests: ring semantics (bounded, dump-stable,
thread-safe), the widened stage vocabulary through the real gRPC serving
path (stage sum ≈ wall within 10%), compile-vs-execute attribution via
the jit cache-key registry, dispatch-gap/occupancy metrics, the
``/flightrec`` + ``/profile`` REPL commands, the PerfSnapshot regression
comparator (identical passes, degraded flags), and the PR's satellite
fixes: chunk-aware Pippenger window sizing, mesh d-multiple padding, and
the LRU-bounded generator-pair cache.
"""

import asyncio
import json
import logging
import threading

import pytest

from cpzk_tpu import Parameters, Prover, SecureRng, Transcript, Witness
from cpzk_tpu.client import AuthClient
from cpzk_tpu.core.ristretto import Ristretto255
from cpzk_tpu.observability import get_flight_recorder
from cpzk_tpu.observability.flightrec import (
    RECORD_STAGES,
    SCHEMA,
    FlightRecord,
    FlightRecorder,
    format_flightrec,
)
from cpzk_tpu.observability.perf import (
    PerfEntry,
    compare_entries,
    load_snapshot,
    stage_percentiles,
    write_snapshot,
)
from cpzk_tpu.ops import backend as backend_mod
from cpzk_tpu.ops import msm
from cpzk_tpu.ops.backend import TpuBackend
from cpzk_tpu.protocol.batch import BatchVerifier, CpuBackend
from cpzk_tpu.server import RateLimiter, ServerState, metrics
from cpzk_tpu.server.__main__ import handle_command
from cpzk_tpu.server.batching import DynamicBatcher
from cpzk_tpu.server.service import serve


def run(coro):
    return asyncio.run(coro)


@pytest.fixture(autouse=True)
def _fresh_recorder():
    rec = get_flight_recorder()
    rec.clear()
    yield
    rec.clear()


def _make_proofs(n, rng, params):
    out = []
    for i in range(n):
        prover = Prover(params, Witness(Ristretto255.random_scalar(rng)))
        ctx = b"fr-%04d" % i
        t = Transcript()
        t.append_context(ctx)
        out.append((prover.statement, prover.prove_with_transcript(rng, t), ctx))
    return out


# --- acceptance: stage sum ≈ wall on a CPU-backend gRPC e2e run -------------


def test_grpc_e2e_stage_sum_matches_wall():
    """The PR acceptance criterion: through the real gRPC serving path on
    the CPU backend, each flight record decomposes the dispatch into
    thread_hop/pad_and_pack/marshal/compile|execute/unpack spans whose
    sum is within 10% of the measured wall, and the dispatch-gap +
    occupancy metrics are populated."""
    rng = SecureRng()
    params = Parameters.new()

    async def main():
        state = ServerState()
        batcher = DynamicBatcher(CpuBackend(), max_batch=512, window_ms=5.0)
        server, port = await serve(
            state, RateLimiter(10**9, 10**9),
            host="127.0.0.1", port=0, batcher=batcher,
        )
        eb = Ristretto255.element_to_bytes
        try:
            async with AuthClient(f"127.0.0.1:{port}") as client:
                n = 256
                provers = [
                    Prover(params, Witness(Ristretto255.random_scalar(rng)))
                    for _ in range(n)
                ]
                resp = await client.register_batch(
                    [f"fr{i}" for i in range(n)],
                    [eb(p.statement.y1) for p in provers],
                    [eb(p.statement.y2) for p in provers],
                )
                assert all(r.success for r in resp.results)
                # two waves so the second dispatch has a measurable gap
                for _wave in range(2):
                    ids, cids, proofs = [], [], []
                    for i, p in enumerate(provers):
                        ch = await client.create_challenge(f"fr{i}")
                        cid = bytes(ch.challenge_id)
                        t = Transcript()
                        t.append_context(cid)
                        ids.append(f"fr{i}")
                        cids.append(cid)
                        proofs.append(
                            p.prove_with_transcript(rng, t).to_bytes()
                        )
                    resp = await client.verify_proof_batch(ids, cids, proofs)
                    assert all(r.success for r in resp.results)
                    for s in list(state._sessions):
                        await state.revoke_session(s)
        finally:
            await batcher.stop()
            await server.stop(None)

    run(main())

    records = get_flight_recorder().snapshot()
    assert len(records) >= 2
    big = [r for r in records if r.batch >= 64]
    assert big, [r.batch for r in records]
    for rec in big:
        assert rec.backend == "cpu"
        assert rec.wall_s > 0
        # the widened decomposition tiles the dispatch wall
        assert rec.stage_sum_s() == pytest.approx(rec.wall_s, rel=0.10), (
            rec.to_dict()
        )
        # CPU oracle: no marshal/compile attribution, pure execute
        assert rec.stages_s.get("execute", 0.0) > 0.0
        assert rec.stages_s.get("compile", 0.0) == 0.0
        assert rec.stages_s.get("thread_hop", 0.0) >= 0.0
        assert rec.occupancy == 1.0  # no device padding on the oracle
    # dispatch gap + occupancy + throughput populated
    gap_count, gap_sum = metrics.read_histogram("tpu.dispatch.gap")
    assert gap_count >= 2.0 and gap_sum >= 0.0
    assert metrics.read("tpu.device.busy_fraction", "g") > 0.0
    assert metrics.read("tpu.batch.occupancy", "g") == 1.0
    assert metrics.read("tpu.throughput.proofs_per_s", "g") >= 0.0
    assert metrics.read_histogram("tpu.batch.thread_hop")[0] >= 2.0


# --- compile vs execute attribution -----------------------------------------


def test_compile_then_cache_hit_attribution(monkeypatch):
    """First dispatch at a padded shape books a jit miss (compile
    attribution); a second batch at the same shape books hits and books
    its device time as execute."""
    monkeypatch.setattr(backend_mod, "_JIT_SEEN", set())
    rng = SecureRng()
    params = Parameters.new()
    proofs = _make_proofs(6, rng, params)

    async def submit_wave(batcher):
        from cpzk_tpu.protocol.batch import BatchEntry

        entries = [
            BatchEntry(params, st, pr, ctx) for st, pr, ctx in proofs
        ]
        res = await batcher.submit_many(entries)
        assert res == [None] * len(entries)

    async def main():
        batcher = DynamicBatcher(TpuBackend(), max_batch=16, window_ms=1.0)
        batcher.start()
        try:
            await submit_wave(batcher)
            await submit_wave(batcher)
        finally:
            await batcher.stop()

    run(main())
    records = get_flight_recorder().snapshot()
    assert len(records) == 2
    first, second = records
    assert first.jit_misses > 0
    assert first.compiled  # the first-sight shape keys are named
    assert first.stages_s.get("compile", 0.0) > 0.0
    assert first.stages_s.get("marshal", 0.0) > 0.0
    assert second.jit_misses == 0
    assert second.jit_hits > 0
    assert second.stages_s.get("compile", 0.0) == 0.0
    assert second.stages_s.get("execute", 0.0) > 0.0
    # device padding is visible: 6+1 correction rows pad to 8 lanes
    assert first.lanes == 8
    assert first.occupancy == pytest.approx(7 / 8)
    assert metrics.read("tpu.jit.cache", labels={"outcome": "miss"}) >= 1
    assert metrics.read("tpu.jit.cache", labels={"outcome": "hit"}) >= 1


def test_compile_storm_warning(caplog):
    rec = FlightRecorder(storm_threshold=3, storm_window_s=60.0)
    with caplog.at_level(
        logging.WARNING, logger="cpzk_tpu.observability.flightrec"
    ):
        for i in range(8):
            rec.note_compile_event(f"combined/{i}")
    storms = [r for r in caplog.records if "compile storm" in r.message]
    assert len(storms) == 1  # warned once per window, not once per compile


# --- ring semantics ----------------------------------------------------------


def test_ring_bounded_and_dump_stable(tmp_path):
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record(FlightRecord(batch=i + 1, stages_s={"execute": 0.001}))
    records = rec.snapshot()
    assert len(records) == 4
    assert [r.batch for r in records] == [7, 8, 9, 10]
    assert [r.seq for r in records] == [7, 8, 9, 10]

    payload = json.loads(rec.to_json())
    assert payload["schema"] == SCHEMA
    assert len(payload["records"]) == 4
    for row in payload["records"]:
        assert set(row) >= {
            "seq", "batch", "lanes", "occupancy", "pad_waste", "backend",
            "stages_s", "wall_s", "dispatch_gap_s", "jit_hits", "jit_misses",
        }
    path = tmp_path / "flightrec.json"
    rec.dump(str(path))
    assert json.loads(path.read_text())["records"] == payload["records"]


def test_ring_thread_safe():
    rec = FlightRecorder(capacity=64)
    errors = []

    def writer(k):
        try:
            for i in range(200):
                rec.record(FlightRecord(batch=k * 1000 + i))
                rec.note_device_interval(float(i), float(i) + 0.5)
        except Exception as exc:  # pragma: no cover - the assertion target
            errors.append(exc)

    def reader():
        try:
            for _ in range(100):
                rec.snapshot()
                rec.to_json()
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=writer, args=(k,)) for k in range(4)]
    threads += [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(rec.snapshot()) == 64


def test_dispatch_gap_accounting():
    rec = FlightRecorder()
    assert rec.note_device_interval(10.0, 10.5) == 0.0  # first dispatch
    assert rec.note_device_interval(11.0, 11.2) == pytest.approx(0.5)
    # pipelined overlap: the device never went idle
    assert rec.note_device_interval(11.1, 11.4) == 0.0


def test_recorder_configure_capacity():
    rec = FlightRecorder(capacity=8)
    for i in range(8):
        rec.record(FlightRecord(batch=i))
    rec.configure(capacity=2)
    assert len(rec.snapshot()) == 2


# --- REPL commands -----------------------------------------------------------


def test_flightrec_command_empty_and_populated():
    async def main():
        state = ServerState()
        out_empty, _ = await handle_command("/flightrec", state)
        get_flight_recorder().record(
            FlightRecord(batch=12, lanes=16, occupancy=0.75,
                         stages_s={"execute": 0.002}, wall_s=0.002)
        )
        out, quit_ = await handle_command("/flightrec 5", state)
        out_bad, _ = await handle_command("/flightrec banana", state)
        return out_empty, out, quit_, out_bad

    out_empty, out, quit_, out_bad = run(main())
    assert "no recorded batches" in out_empty
    assert not quit_
    assert "n=12" in out and "occ=0.75" in out and "gap=" in out
    assert "usage: /flightrec" in out_bad


def test_profile_command_capture_and_guard(tmp_path):
    from cpzk_tpu.observability import flightrec as fr

    logdir = str(tmp_path / "xprof")

    async def main():
        state = ServerState()
        usage, _ = await handle_command("/profile", state)
        bad, _ = await handle_command("/profile banana", state)
        out, _ = await handle_command(f"/profile 0.05 {logdir}", state)
        return usage, bad, out

    usage, bad, out = run(main())
    assert "usage: /profile" in usage
    assert "usage: /profile" in bad
    assert logdir in out and "tensorboard" in out
    assert fr.profile_active() is None  # capture closed

    # concurrent-capture guard: second start is refused, not corrupting
    assert fr.start_profile(str(tmp_path / "a"))
    try:
        assert not fr.start_profile(str(tmp_path / "b"))
        assert fr.profile_active() == str(tmp_path / "a")
    finally:
        assert fr.stop_profile() == str(tmp_path / "a")
    assert fr.stop_profile() is None


# --- perf snapshot + regression gate ----------------------------------------


def _entry(name="batch_e2e", backend="cpu", n=50, value=10.0,
           unit="ms/batch", spread=0.0):
    return PerfEntry(name=name, backend=backend, n=n, value=value,
                     unit=unit, spread=spread)


def test_regress_identical_passes_and_degraded_flags():
    base = [_entry(value=10.0), _entry(name="other", value=5.0)]
    same = compare_entries(base, [_entry(value=10.0),
                                  _entry(name="other", value=5.0)])
    assert same["passed"] and same["compared"] == 2

    degraded = compare_entries(
        base,
        [_entry(value=20.0), _entry(name="other", value=5.0)],
    )
    assert not degraded["passed"]
    assert [d.key[0] for d in degraded["regressions"]] == ["batch_e2e"]


def test_regress_direction_per_unit():
    # throughput: DROP is a regression, rise is fine
    up = compare_entries([_entry(unit="proofs/s", value=100.0)],
                         [_entry(unit="proofs/s", value=300.0)])
    assert up["passed"]
    down = compare_entries([_entry(unit="proofs/s", value=100.0)],
                           [_entry(unit="proofs/s", value=50.0)])
    assert not down["passed"]
    # latency: the same 2x move flips polarity
    faster = compare_entries([_entry(value=100.0)], [_entry(value=50.0)])
    assert faster["passed"]


def test_regress_noise_widens_but_never_disables_gate():
    # 40% regression: over the base 35% gate...
    noisy_old = [_entry(value=10.0, spread=2.0)]  # 20% relative noise
    tight_old = [_entry(value=10.0, spread=0.0)]
    new = [_entry(value=14.0)]
    assert not compare_entries(tight_old, new, threshold=0.35)["passed"]
    # ...but within the noise-widened 55% gate
    assert compare_entries(noisy_old, new, threshold=0.35)["passed"]
    # the allowance caps at one extra threshold: a 3x regression still fails
    wild_old = [_entry(value=10.0, spread=100.0)]
    assert not compare_entries(
        wild_old, [_entry(value=30.0)], threshold=0.35
    )["passed"]


def test_regress_added_removed_configs_do_not_gate():
    report = compare_entries([_entry()], [_entry(name="brand-new")])
    assert report["passed"]
    assert report["compared"] == 0
    assert report["only_old"] and report["only_new"]


def test_regress_cli_exit_codes(tmp_path):
    from cpzk_tpu.observability.regress import main as regress_main

    old = tmp_path / "old.json"
    write_snapshot(str(old), [_entry(value=10.0)])
    new_same = tmp_path / "same.json"
    write_snapshot(str(new_same), [_entry(value=10.0)])
    new_bad = tmp_path / "bad.json"
    write_snapshot(str(new_bad), [_entry(value=99.0)])

    assert regress_main([str(old), str(new_same)]) == 0
    assert regress_main([str(old), str(new_bad)]) == 1
    assert regress_main([str(old), str(new_bad), "--json"]) == 1
    assert regress_main([str(tmp_path / "missing.json"), str(old)]) == 2
    assert regress_main([str(old), str(new_same), "--threshold", "99"]) == 2
    # schema tag is validated, not assumed
    junk = tmp_path / "junk.json"
    junk.write_text('{"schema": "something-else", "entries": []}')
    assert regress_main([str(junk), str(old)]) == 2
    assert load_snapshot(str(old))[0].value == 10.0


def test_stage_percentiles_from_records():
    records = [
        FlightRecord(stages_s={"execute": 0.001 * (i + 1), "marshal": 0.0005})
        for i in range(10)
    ]
    out = stage_percentiles(records)
    assert out["execute"]["p50"] == pytest.approx(5.0)
    assert out["execute"]["p90"] == pytest.approx(9.0)
    assert out["execute"]["p99"] == pytest.approx(10.0)
    assert out["marshal"]["p50"] == pytest.approx(0.5)
    assert stage_percentiles([]) == {}


# --- satellite: chunk-aware pick_window -------------------------------------


def test_pick_window_sized_from_chunk_not_total():
    """ADVICE.md / ROADMAP item 4: past LANE_CHUNK the MSM runs as
    <=16384-term tiles, so the window cost model must see the chunk
    length.  Pinned at the 4k/16k/64k term counts (LANE_CHUNK=16384):
    full-count sizing would pick c=13 at 64k — two windows too deep for
    the tiles that actually run."""
    chunk = 16384
    assert msm.pick_window(4098) == 10          # 4k terms: unchunked
    assert msm.pick_window(min(16386, chunk)) == 11   # 16k terms
    assert msm.pick_window(min(65538, chunk)) == 11   # 64k terms: chunked
    assert msm.pick_window(65538) == 13         # the old miscalibration


def test_backend_pippenger_windows_from_chunk(monkeypatch):
    """The backend actually sizes c from min(m, LANE_CHUNK): with a tiny
    chunk, _combined_pippenger must ask the cost model about the chunk
    length, and the chunked dispatch must stay correct."""
    monkeypatch.setattr(backend_mod, "LANE_CHUNK", 32)
    seen = []
    real_pick = msm.pick_window

    def spy(m):
        seen.append(m)
        return real_pick(m)

    monkeypatch.setattr(backend_mod.msm, "pick_window", spy)

    from test_tpu_backend import make_entries

    entries = make_entries(20)  # m = 4*pad_pow2(20)+2 = 130 > 32
    bv = BatchVerifier(backend=TpuBackend(pippenger_min=2))
    for p, st, pr in entries:
        bv.add(p, st, pr)
    assert bv.verify(SecureRng()) == [None] * 20
    assert seen and all(m == 32 for m in seen)


# --- satellite: mesh d-multiple padding -------------------------------------


def test_mesh_step_pads_to_d_multiple(monkeypatch):
    from cpzk_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(backend_mod, "LANE_CHUNK", 8)
    monkeypatch.setattr(backend_mod, "LANE_QUANTUM", 2)
    d = 8
    step, n_to = mesh_mod._mesh_step(d, 72)  # one past a step boundary
    assert step == 64
    # old behavior padded to 2 full steps (128); now: 10 quantum-aligned
    # lanes per device -> 80 total, a d-multiple
    assert n_to == 80
    assert metrics.read("tpu.batch.occupancy", "g") == pytest.approx(72 / 80)
    # below one step: plain d-multiple, unchanged
    assert mesh_mod._mesh_step(d, 40) == (64, 40)
    assert mesh_mod._mesh_step(d, 41) == (64, 48)


def test_mesh_remainder_slice_matches_oracle(monkeypatch):
    """Over-cap mesh verify with a short (d-multiple) remainder slice
    stays bit-identical to the host oracle, and the occupancy gauge
    reflects the reclaimed lanes (80 padded lanes, not 128)."""
    import jax

    if jax.device_count() < 2:
        pytest.skip("no multi-device mesh available")
    monkeypatch.setattr(backend_mod, "LANE_CHUNK", 8)
    monkeypatch.setattr(backend_mod, "LANE_QUANTUM", 2)

    from test_tpu_backend import make_entries

    entries = make_entries(72)
    be = TpuBackend(mesh_devices=0)
    if be._mesh is None:
        pytest.skip("no multi-device mesh available")
    rng = SecureRng()
    from cpzk_tpu import Statement

    params = entries[11][0]
    wrong = Statement.from_witness(
        params, Witness(Ristretto255.random_scalar(rng))
    )
    entries[11] = (params, wrong, entries[11][2])

    def _run(backend):
        bv = BatchVerifier(backend=backend)
        for p, st, pr in entries:
            bv.add(p, st, pr)
        return [e is None for e in bv.verify(SecureRng())]

    expect = _run(CpuBackend())
    assert expect == [i != 11 for i in range(72)]
    assert _run(be) == expect  # combined fails -> sharded verify_each
    assert metrics.read("tpu.batch.occupancy", "g") == pytest.approx(72 / 80)


def test_mesh_prewarm_books_hits(monkeypatch):
    """``TpuBackend.prewarm`` under a mesh compiles the sharded MSM, its
    partials reduction and the sharded verify_each before serving: a
    mixed batch at the warmed size runs only warmed programs (every jit
    check a HIT) and matches the host oracle."""
    import jax

    from cpzk_tpu import Statement
    from cpzk_tpu.observability import flightrec

    if jax.device_count() < 2:
        pytest.skip("no multi-device mesh available")
    from cpzk_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(backend_mod, "LANE_CHUNK", 8)
    monkeypatch.setattr(backend_mod, "LANE_QUANTUM", 2)
    # the sharded programs are the process's: start from none, as a fresh
    # process does (earlier tests compile some of these shapes)
    monkeypatch.setattr(mesh_mod, "_EXES", {})
    be = TpuBackend(mesh_devices=0)
    if be._mesh is None:
        pytest.skip("no multi-device mesh available")
    d = be._mesh.devices.size
    warmed = be.prewarm([12])
    c, m_pad = backend_mod._msm_shape(12)
    # whole d * LANE_CHUNK-lane MSM slices; 12 rows pad to a d-multiple
    assert warmed == [f"mesh_msm/{c}/{d}/{d * 8}",
                      f"mesh_partials/{d}/{m_pad // 8}",
                      f"mesh_each/{d}/{-(-12 // d) * d}"]
    assert be.prewarm([12]) == []  # idempotent

    seen = []
    monkeypatch.setattr(flightrec, "note_jit",
                        lambda shape, first: seen.append((shape, first)))
    from test_tpu_backend import make_entries

    entries = make_entries(12)
    params = entries[4][0]
    entries[4] = (params, Statement.from_witness(
        params, Witness(Ristretto255.random_scalar(SecureRng()))),
        entries[4][2])

    def _run(backend):
        bv = BatchVerifier(backend=backend)
        for p, st, pr in entries:
            bv.add(p, st, pr)
        return [e is None for e in bv.verify(SecureRng())]

    assert _run(be) == _run(CpuBackend()) == [i != 4 for i in range(12)]
    assert {shape for shape, _ in seen} == set(warmed)
    assert not any(first for _, first in seen)


# --- satellite: LRU-bounded generator-pair cache ----------------------------


def test_gh_cache_lru_bounded():
    from cpzk_tpu.protocol.batch import BatchRow

    rng = SecureRng()
    params = Parameters.new()
    backend = TpuBackend(gh_cache_max=2)

    def row_with_generators():
        # any two distinct valid group elements work as a generator pair
        st = Prover(
            params, Witness(Ristretto255.random_scalar(rng))
        ).statement
        g, h = st.y1, st.y2
        return BatchRow(g=g, h=h, y1=g, y2=h, r1=g, r2=h,
                        s=Ristretto255.random_scalar(rng),
                        c=Ristretto255.random_scalar(rng),
                        alpha=Ristretto255.random_scalar(rng))

    rows = [row_with_generators() for _ in range(4)]
    for row in rows:
        backend._gh(row)
    assert len(backend._gh_cache) == 2
    assert metrics.read("tpu.gh_cache.size", "g") == 2.0
    assert metrics.read("tpu.gh_cache.evictions") >= 2.0
    # most-recently-used pairs survive; re-touching promotes
    backend._gh(rows[2])
    backend._gh(rows[0])  # re-marshal (was evicted), evicts rows[3]'s pair
    keys = list(backend._gh_cache)
    eb = Ristretto255.element_to_bytes
    assert keys[-1] == (eb(rows[0].g), eb(rows[0].h))
    assert len(backend._gh_cache) == 2


# --- recorder is a no-op outside instrumented paths -------------------------


def test_direct_batchverifier_unrecorded():
    """bench_batch's direct BatchVerifier path (stages=None) must not
    touch the recorder — the <=2% overhead criterion is structural."""
    rng = SecureRng()
    params = Parameters.new()
    proofs = _make_proofs(3, rng, params)
    bv = BatchVerifier()
    for st, pr, ctx in proofs:
        bv.add_with_context(params, st, pr, ctx)
    assert bv.verify(rng) == [None] * 3
    assert get_flight_recorder().snapshot() == []


# --- config knobs ------------------------------------------------------------


def test_flightrec_config_env_and_validation(monkeypatch):
    from cpzk_tpu.server import ServerConfig

    monkeypatch.setenv("SERVER_OBSERVABILITY_FLIGHT_RING", "16")
    monkeypatch.setenv("SERVER_OBS_COMPILE_STORM_THRESHOLD", "3")
    cfg = ServerConfig()
    cfg._merge_env()
    assert cfg.observability.flight_ring == 16
    assert cfg.observability.compile_storm_threshold == 3
    cfg.validate()

    cfg = ServerConfig()
    cfg.observability.flight_ring = 0
    with pytest.raises(ValueError):
        cfg.validate()
    cfg = ServerConfig()
    cfg.observability.compile_storm_threshold = 0
    with pytest.raises(ValueError):
        cfg.validate()


def test_configure_applies_flight_ring():
    from cpzk_tpu.observability import configure
    from cpzk_tpu.server.config import ObservabilitySettings

    rec = get_flight_recorder()
    try:
        configure(ObservabilitySettings(flight_ring=3))
        for i in range(6):
            rec.record(FlightRecord(batch=i))
        assert len(rec.snapshot()) == 3
        assert rec.storm_threshold == 8
    finally:
        configure(ObservabilitySettings())


def test_format_flightrec_limit():
    records = [
        FlightRecord(seq=i, batch=i, stages_s={}, wall_s=0.001)
        for i in range(1, 6)
    ]
    # the REPL consumes the same serialized payload shape the HTTP
    # /flightrec endpoint and the SIGUSR2 dump emit
    payload = {"records": [r.to_dict() for r in records]}
    out = format_flightrec(payload, limit=2)
    assert "#5" in out and "#4" in out and "#3" not in out
    for name in RECORD_STAGES:
        assert f"{name}=" in out
