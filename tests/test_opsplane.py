"""Fleet ops plane tests: HTTP introspection endpoints, exposition
parity on both metric backings, /statusz e2e against a live serving
stack with replication + audit enabled, the SLO burn-rate engine under
a synthetic error storm, the shared REPL/HTTP/SIGUSR2 serializers, and
the [opsplane]/[slo] config surface."""

import asyncio
import json
import logging
import os
import pathlib
import re
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import dataclasses
import pytest

from cpzk_tpu import Parameters, Prover, SecureRng, Transcript, Witness
from cpzk_tpu.admission import AdmissionController
from cpzk_tpu.audit import ProofLogWriter
from cpzk_tpu.client import AuthClient
from cpzk_tpu.core.ristretto import Ristretto255
from cpzk_tpu.durability import DurabilityManager
from cpzk_tpu.observability import get_tracer
from cpzk_tpu.observability.flightrec import FlightRecord, get_flight_recorder
from cpzk_tpu.observability.opsplane import ENDPOINTS, OpsPlane, OpsSources
from cpzk_tpu.observability.slo import RPC_CLASSES, SloEngine
from cpzk_tpu.protocol.batch import CpuBackend
from cpzk_tpu.replication import SegmentShipper, StandbyReplica
from cpzk_tpu.server import RateLimiter, ServerState, metrics
from cpzk_tpu.server.batching import DynamicBatcher
from cpzk_tpu.server.config import (
    AdmissionSettings,
    DurabilitySettings,
    OpsplaneSettings,
    ReplicationSettings,
    ServerConfig,
    SloSettings,
)
from cpzk_tpu.server.service import serve
from cpzk_tpu.server.state import _LOCK_WAIT_STRIDE, StateShard

ROOT = pathlib.Path(__file__).resolve().parent.parent
EB = Ristretto255.element_to_bytes

rng = SecureRng()
params = Parameters.new()


def run(coro):
    return asyncio.run(coro)


def http_get(port: int, path: str, timeout: float = 10.0):
    """(status, content_type, body bytes) — raises on transport errors,
    returns the error status for HTTP-level failures."""
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        ) as resp:
            return resp.status, resp.headers.get("Content-Type"), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get("Content-Type"), e.read()


async def aget(port: int, path: str):
    return await asyncio.to_thread(http_get, port, path)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# --- exposition parity -------------------------------------------------------


def test_exposition_contains_every_registered_family():
    """Every (kind, name) in the facade registry renders into the
    exposition text on the prometheus backing (the in-process one)."""
    metrics.counter("opsx.count").inc(3)
    metrics.gauge("opsx.depth").set(7)
    metrics.histogram("opsx.dur").observe(0.5)
    metrics.counter("opsx.labeled", labelnames=("rpc",)).labels(rpc="A").inc()
    text = metrics.render_exposition()
    for _kind, name in metrics.registered():
        assert metrics._sanitize(name) in text, name
    assert text.rstrip().endswith("# EOF")
    # TYPE lines name the kinds
    assert "# TYPE opsx_count counter" in text
    assert "# TYPE opsx_depth gauge" in text
    assert "# TYPE opsx_dur histogram" in text
    assert re.search(r'opsx_labeled(?:_total)?\{rpc="A"\} 1\.0', text)


_NOOP_PARITY_SCRIPT = """
import importlib.abc, sys

class _Block(importlib.abc.MetaPathFinder):
    def find_spec(self, fullname, path, target=None):
        if fullname.split(".")[0] == "prometheus_client":
            raise ImportError("blocked")
        return None

sys.meta_path.insert(0, _Block())

from cpzk_tpu.server import metrics

assert metrics.HAVE_PROMETHEUS is False
# the same family kinds the prometheus-backed test creates
metrics.counter("opsx.count").inc(3)
metrics.gauge("opsx.depth").set(7)
metrics.histogram("opsx.dur").observe(0.5)
metrics.counter("opsx.labeled", labelnames=("rpc",)).labels(rpc="A").inc()
text = metrics.render_exposition()
for _kind, name in metrics.registered():
    assert metrics._sanitize(name) in text, name
assert "opsx_count_total 3.0" in text
assert "opsx_depth 7.0" in text
assert "opsx_dur_count 1.0" in text and "opsx_dur_sum 0.5" in text
assert 'opsx_labeled_total{rpc="A"} 1.0' in text
assert text.rstrip().endswith("# EOF")

# ...and over real HTTP through the ops plane
import asyncio, urllib.request
from cpzk_tpu.observability.opsplane import OpsPlane, OpsSources

async def main():
    plane = OpsPlane(OpsSources(), port=0)
    port = await plane.start()
    def get():
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            return r.status, r.read().decode()
    status, body = await asyncio.to_thread(get)
    assert status == 200
    for _kind, name in metrics.registered():
        assert metrics._sanitize(name) in body, name
    await plane.stop()

asyncio.run(main())
print("NOOP-EXPOSITION-OK")
"""


def test_exposition_parity_without_prometheus_subprocess():
    """The no-prometheus backing renders the identical family set —
    including over real HTTP through the ops plane."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    result = subprocess.run(
        [sys.executable, "-c", _NOOP_PARITY_SCRIPT],
        capture_output=True, text=True, cwd=str(ROOT), env=env, timeout=120,
    )
    assert result.returncode == 0, result.stderr
    assert "NOOP-EXPOSITION-OK" in result.stdout


# --- one serializer for REPL / HTTP / SIGUSR2 --------------------------------


def test_flightrec_dump_and_http_share_payload(tmp_path):
    """The SIGUSR2 dump file, ``payload()``, and the REPL rendering all
    come from one serializer — identical record dicts."""
    rec = get_flight_recorder()
    rec.clear()
    rec.record(FlightRecord(batch=8, lanes=16, occupancy=0.5,
                            stages_s={"execute": 0.001}, wall_s=0.0011))
    payload = rec.payload()
    assert payload["schema"] == "cpzk-flightrec/1"
    path = tmp_path / "dump.json"
    rec.dump(str(path))
    dumped = json.loads(path.read_text())
    assert dumped["records"] == payload["records"]
    assert dumped["schema"] == payload["schema"]
    # the REPL text renders the same dicts
    from cpzk_tpu.observability import format_flightrec

    out = format_flightrec(payload)
    assert "#1" in out and "n=8" in out
    rec.clear()


def test_tracez_payload_roundtrips_repl_rendering():
    from cpzk_tpu.observability import RequestContext, format_tracez

    tracer = get_tracer()
    tracer.clear()
    ctx = RequestContext()
    tracer.start(ctx, "OpsOp")
    tracer.add_span(ctx.trace_id, "queue_wait", 0.0, 0.002)
    tracer.finish(ctx.trace_id, "success")
    payload = tracer.payload()
    assert payload["schema"] == "cpzk-tracez/1"
    assert payload["traces"][0]["name"] == "OpsOp"
    assert payload["traces"][0]["spans"][0]["name"] == "queue_wait"
    out = format_tracez(payload)
    assert "OpsOp" in out and "queue_wait=2.00ms" in out
    tracer.clear()


# --- the HTTP server itself --------------------------------------------------


def test_unknown_path_404_and_method_not_allowed():
    async def main():
        plane = OpsPlane(OpsSources(), port=0)
        port = await plane.start()
        try:
            status, ctype, body = await aget(port, "/definitely-not-a-path")
            assert status == 404 and "json" in ctype
            doc = json.loads(body)
            assert sorted(doc["endpoints"]) == sorted(ENDPOINTS)

            def post():
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/metrics", data=b"x",
                    method="POST",
                )
                try:
                    urllib.request.urlopen(req, timeout=10)
                except urllib.error.HTTPError as e:
                    return e.code
                return 200

            assert await asyncio.to_thread(post) == 405
            # /slo without an engine attached is a 404, not a crash
            status, _, _ = await aget(port, "/slo")
            assert status == 404
        finally:
            await plane.stop()

    run(main())


def test_healthz_readiness_split():
    """/healthz keys its status code on liveness; ?service=readiness on
    readiness — mirroring the gRPC health split."""
    from cpzk_tpu.server.service import HealthService

    async def main():
        health = HealthService()
        plane = OpsPlane(OpsSources(health=health), port=0)
        port = await plane.start()
        try:
            status, _, body = await aget(port, "/healthz")
            doc = json.loads(body)
            assert status == 200 and doc["live"] and doc["ready"]
            # standby: live but not ready
            health.standby = True
            status, _, body = await aget(port, "/healthz")
            assert status == 200 and json.loads(body)["ready"] is False
            status, _, _ = await aget(port, "/healthz?service=readiness")
            assert status == 503
            # draining: not live either
            health.standby = False
            health.serving = False
            status, _, _ = await aget(port, "/healthz")
            assert status == 503
        finally:
            await plane.stop()

    run(main())


def test_start_in_thread_serves_and_stops():
    """The audit pipeline's attachment: the same server on a daemon
    thread next to a synchronous host."""
    plane = OpsPlane(OpsSources(role="audit"), port=0)
    port = plane.start_in_thread()
    try:
        status, _, body = http_get(port, "/healthz")
        assert status == 200
        assert json.loads(body)["live"] is True
        status, _, body = http_get(port, "/statusz")
        assert json.loads(body)["role"] == "audit"
    finally:
        plane.stop_thread()
    with pytest.raises(OSError):
        http_get(port, "/healthz", timeout=2.0)


# --- /statusz e2e against a live serving stack -------------------------------


def test_statusz_e2e_with_replication_and_audit(tmp_path):
    """The acceptance path: a live daemon-shaped stack (batcher +
    admission + audit trail + replication primary shipping to a real
    standby) serves /metrics /statusz /tracez /healthz /slo over plain
    HTTP, with every cross-plane block populated."""

    async def main():
        # standby side (real gRPC link, like test_replication.make_pair)
        sstate = ServerState()
        smgr = DurabilityManager(
            sstate, DurabilitySettings(enabled=True),
            str(tmp_path / "standby.json"),
        )
        await smgr.recover()
        ssettings = ReplicationSettings(
            enabled=True, role="standby", lease_ms=4000.0,
            renew_interval_ms=50.0, mode="sync", auto_promote=False,
        )
        replica = StandbyReplica(sstate, smgr, ssettings)
        sserver, sport = await serve(
            sstate, RateLimiter(100_000, 100_000), port=0, replica=replica
        )
        replica.start()

        # primary side: the full serving stack
        pstate = ServerState()
        pmgr = DurabilityManager(
            pstate, DurabilitySettings(enabled=True),
            str(tmp_path / "primary.json"),
        )
        await pmgr.recover()
        psettings = ReplicationSettings(
            enabled=True, role="primary", peer=f"127.0.0.1:{sport}",
            lease_ms=4000.0, renew_interval_ms=50.0, mode="sync",
        )
        shipper = SegmentShipper(pstate, pmgr, psettings)
        pmgr.attach_shipper(shipper)
        pstate.attach_replication_barrier(shipper.wait_replicated)
        batcher = DynamicBatcher(CpuBackend(), max_batch=64, window_ms=5.0)
        admission = AdmissionController(
            AdmissionSettings(), batcher=batcher
        )
        audit_log = ProofLogWriter(str(tmp_path / "proofs.log"))
        pserver, pport = await serve(
            pstate, RateLimiter(100_000, 100_000), port=0,
            batcher=batcher, admission=admission, audit_log=audit_log,
        )
        shipper.start()

        cfg = ServerConfig()
        engine = SloEngine(cfg.slo)
        sources = OpsSources(
            state=pstate, batcher=batcher, admission=admission,
            replication=shipper, audit_log=audit_log, durability=pmgr,
            health=pserver.health, service=pserver.auth_service,
            slo=engine, config_fingerprint=cfg.fingerprint(),
        )
        plane = OpsPlane(sources, port=0)
        ops_port = await plane.start()

        try:
            # drive real logins so every plane has numbers to report
            async with AuthClient(f"127.0.0.1:{pport}") as client:
                provers = {}
                for i in range(4):
                    p = Prover(
                        params, Witness(Ristretto255.random_scalar(rng))
                    )
                    provers[f"ops-u{i}"] = p
                    resp = await client.register(
                        f"ops-u{i}", EB(p.statement.y1), EB(p.statement.y2)
                    )
                    assert resp.success
                for uid, p in provers.items():
                    ch = await client.create_challenge(uid)
                    t = Transcript()
                    t.append_context(bytes(ch.challenge_id))
                    proof = p.prove_with_transcript(rng, t)
                    resp = await client.verify_proof(
                        uid, ch.challenge_id, proof.to_bytes()
                    )
                    assert resp.success

            # let the shipper push the journaled mutations to the standby
            deadline = asyncio.get_running_loop().time() + 5.0
            while shipper.acked_seq < pmgr.wal.seq:
                assert asyncio.get_running_loop().time() < deadline, (
                    shipper.status()
                )
                await asyncio.sleep(0.02)

            status, ctype, body = await aget(ops_port, "/statusz")
            assert status == 200 and "json" in ctype
            doc = json.loads(body)
            assert doc["schema"] == "cpzk-statusz/1"
            assert doc["uptime_s"] >= 0.0
            assert doc["config_fingerprint"] == cfg.fingerprint()
            # batcher block
            assert doc["batcher"]["queue_capacity"] == batcher.max_queue
            # shards: the registrations and sessions we just made
            assert doc["shards"]["count"] == pstate.num_shards
            assert doc["shards"]["users"] == 4
            assert doc["shards"]["sessions"] == 4
            assert len(doc["shards"]["per_shard"]) == pstate.num_shards
            # dispatch block: the batcher recorded flight records
            assert doc["dispatch"]["recorded_batches"] >= 1
            assert "execute" in doc["dispatch"]["stage_percentiles_ms"]
            # admission block
            assert doc["admission"]["level"] > 0
            # replication block: primary, synced, fresh last-ship
            repl = doc["replication"]
            assert repl["role"] == "primary"
            assert repl["lag_records"] == 0
            assert repl["last_ship_age_s"] is not None
            # audit block: one record per verify
            assert doc["audit"]["seq"] == 4
            assert doc["audit"]["bytes"] > 0
            # durability + health + streams blocks present
            assert doc["durability"]["wal_seq"] == pmgr.wal.seq
            assert doc["health"] == {"live": True, "ready": True}
            assert doc["streams"] == {"active": 0, "streams": []}

            # cross-plane histograms landed
            assert metrics.read_histogram("state.repl.ship_rtt")[0] >= 1
            assert metrics.read_histogram(
                "state.repl.apply_lag_seconds")[0] >= 1

            # /metrics: families from every plane, incl. scrape-time
            # per-shard gauges
            status, ctype, body = await aget(ops_port, "/metrics")
            text = body.decode()
            assert status == 200 and "text/plain" in ctype
            for family in ("rpc_requests", "state_repl_role",
                           "state_shard_size", "audit_log_appends",
                           "tpu_queue_depth", "state_repl_ship_rtt"):
                assert family in text, family
            assert metrics.read(
                "state.shard.size", "g",
                labels={"shard": str(pstate._shard_index("ops-u0")),
                        "kind": "users"},
            ) >= 1.0

            # /tracez: the logins we just drove, same serializer as REPL
            status, _, body = await aget(ops_port, "/tracez?n=50")
            traces = json.loads(body)
            assert traces["schema"] == "cpzk-tracez/1"
            assert any(
                t["name"] == "VerifyProof" for t in traces["traces"]
            )

            # /healthz + /slo
            status, _, body = await aget(ops_port, "/healthz")
            assert status == 200 and json.loads(body)["ready"] is True
            status, _, body = await aget(ops_port, "/slo")
            slo = json.loads(body)
            assert status == 200 and slo["schema"] == "cpzk-slo/1"
            assert slo["rpcs"]["VerifyProof"]["total_requests"] >= 4

            # unknown path: JSON 404 with the catalog
            status, _, body = await aget(ops_port, "/nope")
            assert status == 404
            assert sorted(json.loads(body)["endpoints"]) == sorted(ENDPOINTS)
        finally:
            await plane.stop()
            await batcher.stop()
            audit_log.close()
            await shipper.stop()
            await replica.stop()
            await pserver.stop(None)
            await sserver.stop(None)
            await pmgr.close()
            await smgr.close()

    run(main())


def test_statusz_reports_active_streams():
    """A live VerifyProofStream shows up as a per-stream /statusz row
    and in the auth.stream.active gauge, and unregisters on close."""

    async def main():
        state = ServerState()
        server, port = await serve(
            state, RateLimiter(10**9, 10**9), port=0,
        )
        service = server.auth_service
        try:
            p = Prover(params, Witness(Ristretto255.random_scalar(rng)))
            async with AuthClient(f"127.0.0.1:{port}") as client:
                resp = await client.register(
                    "s-u0", EB(p.statement.y1), EB(p.statement.y2)
                )
                assert resp.success

                async def entry():
                    ch = await client.create_challenge("s-u0")
                    t = Transcript()
                    t.append_context(bytes(ch.challenge_id))
                    return ("s-u0", bytes(ch.challenge_id),
                            p.prove_with_transcript(rng, t).to_bytes())

                entries = [await entry(), await entry()]

                async def gen():
                    yield entries[0]
                    # mid-stream: exactly one live stream, with rows
                    for _ in range(500):
                        if service.stream_stats()["active"] == 1:
                            break
                        await asyncio.sleep(0.01)
                    stats = service.stream_stats()
                    assert stats["active"] == 1
                    assert metrics.read("auth.stream.active", "g") == 1.0
                    yield entries[1]

                verdicts = [
                    v async for v in
                    client.verify_proof_stream(gen(), chunk=1)
                ]
                assert len(verdicts) == 2
                assert all(v.ok for v in verdicts)
            stats = service.stream_stats()
            assert stats["active"] == 0 and stats["streams"] == []
            assert metrics.read("auth.stream.active", "g") == 0.0
        finally:
            await server.stop(None)

    run(main())


# --- shard lock-wait sampling ------------------------------------------------


def test_shard_lock_wait_is_stride_sampled():
    async def main():
        shard = StateShard()
        before = metrics.read_histogram("state.shard.lock_wait")[0]
        for _ in range(2 * _LOCK_WAIT_STRIDE):
            async with shard.lock:
                pass
        after = metrics.read_histogram("state.shard.lock_wait")[0]
        assert after - before == 2  # exactly 1-in-stride observed

    run(main())


def test_shard_stats_and_gauges():
    async def main():
        state = ServerState(shards=4)
        p = Prover(params, Witness(Ristretto255.random_scalar(rng)))
        from cpzk_tpu.server.state import UserData

        await state.register_user(UserData("g-u0", p.statement, 1))
        stats = state.shard_stats()
        assert len(stats) == 4
        assert sum(s["users"] for s in stats) == 1
        state.export_shard_gauges()
        idx = str(state._shard_index("g-u0"))
        assert metrics.read(
            "state.shard.size", "g", labels={"shard": idx, "kind": "users"}
        ) == 1.0

    run(main())


# --- SLO engine --------------------------------------------------------------


def _slo_drive(engine, clock, req, dur, ticks, dt, ok=0, fail=0,
               latency_s=None):
    for _ in range(ticks):
        clock[0] += dt
        if ok:
            req.labels(rpc="VerifyProof", outcome="success").inc(ok)
        if fail:
            req.labels(rpc="VerifyProof", outcome="failure").inc(fail)
        if latency_s is not None:
            dur.labels(rpc="VerifyProof").observe(latency_s)
        engine.tick()


def test_slo_burn_storm_pages_once_per_window_and_recovers(caplog):
    """The synthetic error storm: burn gauges cross during a 50%-failure
    storm, the page WARNING fires once per (short) window, an slo_burn
    event lands in the trace ring, and the budget recovers after."""
    clock = [10_000.0]
    engine = SloEngine(SloSettings(), clock=lambda: clock[0])
    req = metrics.counter("rpc.requests", labelnames=("rpc", "outcome"))
    dur = metrics.histogram("rpc.duration", labelnames=("rpc",))
    tracer = get_tracer()
    tracer.clear()

    engine.tick()  # baseline sample
    # healthy 10 minutes
    _slo_drive(engine, clock, req, dur, ticks=10, dt=60.0, ok=600)
    view = engine.snapshot()["rpcs"]["VerifyProof"]
    assert view["windows"]["5m"]["burn_rate"] < 1.0
    assert view["error_budget_remaining"] == 1.0
    assert view["paging"] == []

    # the storm: 50% failures for 5 minutes of 60s ticks
    with caplog.at_level(logging.WARNING, "cpzk_tpu.observability.slo"):
        _slo_drive(engine, clock, req, dur, ticks=5, dt=60.0,
                   ok=100, fail=100)
    view = engine.snapshot()["rpcs"]["VerifyProof"]
    assert view["windows"]["5m"]["burn_rate"] > engine.settings.fast_burn_threshold
    assert view["windows"]["1h"]["burn_rate"] > engine.settings.fast_burn_threshold
    assert "fast" in view["paging"]
    assert view["error_budget_remaining"] < 1.0
    # exported gauges crossed too
    assert metrics.read(
        "slo.burn_rate", "g", labels={"rpc": "VerifyProof", "window": "5m"}
    ) > engine.settings.fast_burn_threshold
    # WARNING once per (5m) window across the 5 storm ticks, not 5 times
    fast_warnings = [
        r for r in caplog.records if "SLO burn (fast)" in r.getMessage()
    ]
    assert len(fast_warnings) == 1
    # trace-ring slo_burn event on the shared timeline
    events = [t for t in tracer.completed() if t.name == "slo_burn"]
    assert events and events[0].spans[0].attrs["rpc"] == "VerifyProof"

    # recovery: hours of healthy traffic drain the windows
    _slo_drive(engine, clock, req, dur, ticks=100, dt=300.0, ok=1000)
    view = engine.snapshot()["rpcs"]["VerifyProof"]
    assert view["windows"]["5m"]["burn_rate"] == 0.0
    assert view["windows"]["6h"]["burn_rate"] < 1.0
    assert view["error_budget_remaining"] > 0.99
    assert view["paging"] == []
    tracer.clear()


def test_slo_latency_burn_component():
    """A latency regression (mean over target) burns even at 100%
    availability."""
    clock = [50_000.0]
    settings = SloSettings(latency_ms="VerifyProof=100")
    engine = SloEngine(settings, clock=lambda: clock[0])
    assert engine.latency_ms["VerifyProof"] == 100.0
    req = metrics.counter("rpc.requests", labelnames=("rpc", "outcome"))
    dur = metrics.histogram("rpc.duration", labelnames=("rpc",))
    engine.tick()
    # all successes, but 400ms mean against a 100ms target
    _slo_drive(engine, clock, req, dur, ticks=3, dt=60.0, ok=10,
               latency_s=0.4)
    view = engine.snapshot()["rpcs"]["VerifyProof"]
    w = view["windows"]["5m"]
    assert w["availability_burn"] == 0.0
    assert w["latency_burn"] == pytest.approx(4.0, rel=0.01)
    assert w["burn_rate"] == pytest.approx(4.0, rel=0.01)


def test_slo_known_burn_math():
    """1 failure in 1000 requests at a 99.9% target is burn exactly 1."""
    clock = [90_000.0]
    engine = SloEngine(
        SloSettings(availability_target=0.999), clock=lambda: clock[0]
    )
    req = metrics.counter("rpc.requests", labelnames=("rpc", "outcome"))
    engine.tick()
    clock[0] += 60.0
    req.labels(rpc="CreateChallenge", outcome="success").inc(999)
    req.labels(rpc="CreateChallenge", outcome="failure").inc(1)
    engine.tick()
    view = engine.snapshot()["rpcs"]["CreateChallenge"]
    assert view["windows"]["5m"]["availability_burn"] == pytest.approx(
        1.0, rel=0.01
    )
    # every known RPC class is tracked
    assert set(engine.snapshot()["rpcs"]) == set(RPC_CLASSES)


# --- daemon: ops plane refuses to bind when disabled -------------------------


def test_daemon_does_not_bind_opsplane_when_disabled(tmp_path):
    """[opsplane] enabled=false (the default) means NO HTTP listener —
    a real daemon boot, pinned by connection-refused on the configured
    ops port while gRPC is accepting."""
    grpc_port, ops_port = free_port(), free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("SERVER_OPSPLANE_ENABLED", None)
    env["SERVER_CONFIG_PATH"] = str(tmp_path / "no-such.toml")
    env["SERVER_OPSPLANE_PORT"] = str(ops_port)
    proc = subprocess.Popen(
        [sys.executable, "-m", "cpzk_tpu.server", "--no-repl",
         "--port", str(grpc_port)],
        cwd=str(ROOT), env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            assert proc.poll() is None, "daemon died during boot"
            try:
                socket.create_connection(
                    ("127.0.0.1", grpc_port), timeout=0.5
                ).close()
                break
            except OSError:
                time.sleep(0.1)
        else:
            raise AssertionError("gRPC listener never came up")
        with pytest.raises(OSError):
            socket.create_connection(("127.0.0.1", ops_port), timeout=0.5)
    finally:
        proc.terminate()
        proc.wait(timeout=30)


def test_daemon_metrics_fallback_without_prometheus(tmp_path):
    """The silent-no-exposition satellite: --metrics with
    prometheus_client missing used to leave the configured metrics port
    dead with no log line.  Now the daemon serves the ops-plane text
    exposition on that same port (and /metrics answers scrapes)."""
    shim = tmp_path / "prometheus_client.py"
    shim.write_text('raise ImportError("blocked for the fallback test")\n')
    grpc_port, metrics_port = free_port(), free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = f"{tmp_path}:{ROOT}"
    env["SERVER_CONFIG_PATH"] = str(tmp_path / "no-such.toml")
    proc = subprocess.Popen(
        [sys.executable, "-m", "cpzk_tpu.server", "--no-repl",
         "--port", str(grpc_port),
         "--metrics", "--metrics-port", str(metrics_port)],
        cwd=str(ROOT), env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
    )
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            assert proc.poll() is None, proc.stderr.read()
            try:
                status, ctype, body = http_get(
                    metrics_port, "/metrics", timeout=0.5
                )
                break
            except OSError:
                time.sleep(0.1)
        else:
            raise AssertionError("fallback /metrics never came up")
        assert status == 200 and "text/plain" in ctype
        assert b"# EOF" in body
    finally:
        proc.terminate()
        try:
            _, err = proc.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            _, err = proc.communicate(timeout=30)
    assert "prometheus_client is not installed" in err


# --- config surface ----------------------------------------------------------


def test_opsplane_slo_config_layering_and_env(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    cfg = ServerConfig.from_env()
    assert cfg.opsplane.enabled is False
    assert cfg.opsplane.port == 9092
    assert cfg.slo.availability_target == 0.999

    (tmp_path / "server.toml").write_text(
        "[opsplane]\nenabled = true\nport = 9192\n\n"
        '[slo]\navailability_target = 0.99\nlatency_ms = "VerifyProof=50"\n'
    )
    monkeypatch.setenv("SERVER_CONFIG_PATH", str(tmp_path / "server.toml"))
    cfg = ServerConfig.from_env()
    assert cfg.opsplane.enabled is True and cfg.opsplane.port == 9192
    assert cfg.slo.availability_target == 0.99
    assert cfg.slo.parsed_latency_ms() == {"VerifyProof": 50.0}
    cfg.validate()

    # env overrides the file
    monkeypatch.setenv("SERVER_OPSPLANE_PORT", "9292")
    monkeypatch.setenv("SERVER_SLO_FAST_BURN_THRESHOLD", "10")
    monkeypatch.setenv("SERVER_SLO_TICK_INTERVAL_MS", "250")
    cfg = ServerConfig.from_env()
    assert cfg.opsplane.port == 9292
    assert cfg.slo.fast_burn_threshold == 10.0
    assert cfg.slo.tick_interval_ms == 250.0
    cfg.validate()


def test_opsplane_slo_config_validation():
    for mutate, match in (
        (lambda c: setattr(c.opsplane, "port", 70000), "opsplane.port"),
        (lambda c: setattr(c.opsplane, "port", -1), "opsplane.port"),
        (lambda c: setattr(c.slo, "availability_target", 1.0),
         "availability_target"),
        (lambda c: setattr(c.slo, "availability_target", 0.0),
         "availability_target"),
        (lambda c: setattr(c.slo, "fast_burn_threshold", 0),
         "fast_burn_threshold"),
        (lambda c: setattr(c.slo, "slow_burn_threshold", -1),
         "slow_burn_threshold"),
        (lambda c: setattr(c.slo, "tick_interval_ms", 0),
         "tick_interval_ms"),
        (lambda c: setattr(c.slo, "latency_ms", "garbage"), "latency_ms"),
        (lambda c: setattr(c.slo, "latency_ms", "VerifyProof=-5"),
         "latency_ms"),
    ):
        cfg = ServerConfig()
        mutate(cfg)
        with pytest.raises(ValueError, match=match):
            cfg.validate()
    # enabled + empty host is rejected; port 0 (ephemeral) is fine
    cfg = ServerConfig()
    cfg.opsplane.enabled = True
    cfg.opsplane.host = ""
    with pytest.raises(ValueError, match="host"):
        cfg.validate()
    cfg = ServerConfig()
    cfg.opsplane.port = 0
    cfg.validate()


def test_opsplane_slo_config_keys_documented():
    """CI drift guard (pattern from test_durability.py): every
    [opsplane]/[slo] knob ships in the TOML example, the .env example,
    and the operations-doc knob inventory."""
    docs = (ROOT / "docs" / "operations.md").read_text()
    toml_text = (ROOT / "config" / "server.toml.example").read_text()
    env_text = (ROOT / ".env.example").read_text()
    for section, cls in (
        ("opsplane", OpsplaneSettings), ("slo", SloSettings),
    ):
        keys = [f.name for f in dataclasses.fields(cls)]
        assert keys
        m = re.search(rf"^\[{section}\]$", toml_text, re.M)
        assert m, f"[{section}] section missing from server.toml.example"
        body = toml_text[m.end():].split("\n[", 1)[0]
        for key in keys:
            assert re.search(rf"^{key}\s*=", body, re.M), (
                f"[{section}] key {key!r} missing from server.toml.example"
            )
            assert f"SERVER_{section.upper()}_{key.upper()}" in env_text, (
                f"SERVER_{section.upper()}_{key.upper()} missing from "
                ".env.example"
            )
            assert f"`{section}.{key}`" in docs, (
                f"`{section}.{key}` missing from the docs/operations.md "
                "knob inventory"
            )


def test_config_fingerprint_stable_and_sensitive():
    a, b = ServerConfig(), ServerConfig()
    assert a.fingerprint() == b.fingerprint()
    assert len(a.fingerprint()) == 12
    b.opsplane.port = 9193
    assert a.fingerprint() != b.fingerprint()


def test_statusz_device_block():
    """``/statusz`` carries the daemon's device statement (null when no
    provider is attached, e.g. the audit pipeline)."""
    assert OpsSources().statusz()["device"] is None
    stated = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "native": True, "memory": []}
    assert OpsSources(device=lambda: stated).statusz()["device"] == stated
