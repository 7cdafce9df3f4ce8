"""End-to-end serving throughput curve: gRPC -> batcher -> backend -> sessions.

VERDICT r3 item 3: the kernel benches time device compute alone; this
measures the FULL serving path at realistic batch totals — register,
challenge issuance, proof generation (all untimed setup), then timed
`VerifyProofBatch` RPCs (wire parse, challenge consumption, backend
verification, per-item session issuance), against the reference analog
`src/verifier/service.rs:407-617`.

Prints one JSON line per curve point:
    {"metric": "e2e_curve", "n": N, "grpc_pps": ...,
     "grpc_pipelined_pps": ..., "stream_pps": ..., "direct_pps": ...,
     "platform": ..., "backend": ..., "unit": "proofs/s"}

- grpc_pps  — proofs/s through the real asyncio gRPC loopback service
              (batched RPCs of <=1000 items, reference cap parity),
              one RPC in flight at a time.  BOTH backends route through
              the batcher -> dispatch-lane seam (the production serving
              architecture), so the snapshot carries flight-recorder
              stage percentiles on the CPU path too.
- grpc_pipelined_pps — same, but a wave's RPCs issued concurrently: the
              server verifies on a worker thread (GIL released), so one
              RPC's Python overlaps another's crypto — the many-client
              deployment shape.
- stream_pps — proofs/s through ONE VerifyProofStream bidi stream
              (verdict-only, no session issuance): entries feed the
              batcher continuously with no per-RPC boundary or 1000-item
              cap — the workload the streaming API exists for.  The
              acceptance bar is >= 0.95x direct_pps at n=64k.
- direct_pps — proofs/s through BatchVerifier.verify alone on the same
              backend (no RPC/session overhead); the serial gap is the
              serving layer's cost.

Backends: --backend cpu (native host core; the production CPU serving
config) or tpu (device data plane; meaningful on real TPU — on the XLA
CPU backend it is a correctness emulation ~1000x slower than silicon).
Env: CPZK_E2E_NS (comma list), CPZK_BENCH_PLATFORM (jax platform pin).

Usage: python benches/bench_e2e_curve.py [--ns 256,4096] [--backend cpu|tpu]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

USERS = 512            # corpus users registered once
CHALLENGES_PER_WAVE = 3  # per-user outstanding-challenge cap (state parity)
RPC_CAP = 1000         # MAX_BATCH parity (service.rs:428-432)
PIPELINE_WAYS = 4      # concurrent RPCs per wave in the pipelined pass


def build_corpus():
    from cpzk_tpu import Parameters, Prover, SecureRng, Witness
    from cpzk_tpu.core.ristretto import Ristretto255

    rng = SecureRng()
    params = Parameters.new()
    provers = [
        Prover(params, Witness(Ristretto255.random_scalar(rng)))
        for _ in range(USERS)
    ]
    return rng, params, provers


STREAM_CHUNK = 1024    # entries packed per stream message


def build_serving_plane(backend_name: str, lanes: int, quantum: int):
    """(backend, router, resolved_lanes): the serving compute plane of
    one curve point.  ``lanes != 1`` builds the multi-chip LaneRouter —
    per-device ``TpuBackend`` lanes on the tpu backend (emulate chips on
    CPU with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``),
    per-host-core ``CpuBackend`` lanes on the cpu backend (the router
    machinery at native verify speeds — what the perf gate's lanes leg
    measures)."""
    if backend_name == "tpu":
        from cpzk_tpu.ops.backend import TpuBackend, prewarm_executables

        if lanes != 1:
            from cpzk_tpu.parallel import resolve_lane_devices
            from cpzk_tpu.server.router import LaneRouter

            devices = resolve_lane_devices(lanes)
            if devices is not None:
                # per-device AOT prewarm: every lane's first timed batch
                # books jit HITs, like a production [tpu] prewarm_quanta
                prewarm_executables([quantum], devices=devices)
                backends = [TpuBackend(device=d) for d in devices]
                router = LaneRouter(backends, devices=devices)
                return backends[0], router, len(devices)
        prewarm_executables([quantum])
        return TpuBackend(), None, 1
    from cpzk_tpu.protocol.batch import CpuBackend

    if lanes != 1:
        from cpzk_tpu.server.router import LaneRouter

        k = lanes if lanes > 0 else (os.cpu_count() or 1)
        if k > 1:
            return (
                CpuBackend(),
                LaneRouter([CpuBackend() for _ in range(k)]),
                k,
            )
    return CpuBackend(), None, 1


async def grpc_curve_point(
    n: int, provers, rng, backend_name: str, lanes: int = 1,
    wire: str = "native",
) -> tuple[float, float, float]:
    """(serial_pps, pipelined_pps, stream_pps): wall time of the timed
    verify RPCs for n proofs with one RPC in flight, then with each
    wave's RPCs issued concurrently (~PIPELINE_WAYS at a time), then
    pushed through one VerifyProofStream per wave (verdict-only)."""
    import grpc  # noqa: F401  (import check before server spin-up)

    from cpzk_tpu import Transcript
    from cpzk_tpu.client import AuthClient
    from cpzk_tpu.core.ristretto import Ristretto255
    from cpzk_tpu.server import RateLimiter, ServerState
    from cpzk_tpu.server.service import serve

    from cpzk_tpu.server.batching import DynamicBatcher

    backend, router, _ = build_serving_plane(
        backend_name, lanes, min(n, RPC_CAP)
    )
    # BOTH backends serve through the batcher -> dispatch-lane seam (the
    # production serving architecture since the dedicated-lane PR); the
    # flight recorder therefore has stage percentiles for the snapshot
    # on the CPU path too, not only on device runs.  With lanes != 1 the
    # batcher places every settled batch through the LaneRouter instead.
    batcher = DynamicBatcher(backend, max_batch=RPC_CAP, window_ms=5.0,
                             pipeline_depth=2,  # serve() starts it
                             router=router)

    state = ServerState()
    # CPZK_BENCH_FLEET=1: enable fleet routing with a single-partition
    # map — the perf gate's proof that the N=1 ownership fast path taxes
    # the serving hot path by nothing measurable (the address is a
    # placeholder: a one-partition router never redirects)
    fleet = None
    if os.environ.get("CPZK_BENCH_FLEET"):
        from cpzk_tpu.fleet import FleetRouter, PartitionMap

        fleet = FleetRouter(PartitionMap.uniform(["127.0.0.1:0"]), 0)
    server, port = await serve(
        state, RateLimiter(10**9, 10**9), host="127.0.0.1", port=0,
        backend=backend, batcher=batcher, fleet=fleet, wire=wire,
    )
    # CPZK_BENCH_OPSPLANE=1: run the full HTTP introspection server +
    # SLO engine alongside the timed passes — the perf gate's proof that
    # the ops plane costs nothing measurable on the serving path
    ops_plane = None
    if os.environ.get("CPZK_BENCH_OPSPLANE"):
        from cpzk_tpu.observability.opsplane import OpsPlane, OpsSources
        from cpzk_tpu.observability.slo import SloEngine
        from cpzk_tpu.server.config import SloSettings

        ops_plane = OpsPlane(OpsSources(
            state=state, batcher=batcher, backend=backend,
            health=server.health, service=server.auth_service,
            slo=SloEngine(SloSettings()),
        ), port=0)
        await ops_plane.start()
    eb = Ristretto255.element_to_bytes
    timed = 0.0
    done = 0
    try:
        async with AuthClient(f"127.0.0.1:{port}") as client:
            resp = await client.register_batch(
                [f"u{i}" for i in range(len(provers))],
                [eb(pr.statement.y1) for pr in provers],
                [eb(pr.statement.y2) for pr in provers],
            )
            assert all(r.success for r in resp.results)
            async def make_wave(wave):
                ids, cids, proofs = [], [], []
                for k in range(wave):
                    u = k % USERS
                    ch = await client.create_challenge(f"u{u}")
                    cid = bytes(ch.challenge_id)
                    t = Transcript()
                    t.append_context(cid)
                    proof = provers[u].prove_with_transcript(rng, t)
                    ids.append(f"u{u}")
                    cids.append(cid)
                    proofs.append(proof.to_bytes())
                return ids, cids, proofs

            # untimed warmup RPC at the dominant batch shape (tpu backends
            # JIT-compile per padded shape; compile must not be timed)
            w0 = min(n, RPC_CAP)
            ids, cids, proofs = await make_wave(w0)
            resp = await client.verify_proof_batch(ids, cids, proofs)
            assert all(r.success for r in resp.results)
            for s in list(state._sessions):
                await state.revoke_session(s)

            while done < n:
                wave = min(n - done, USERS * CHALLENGES_PER_WAVE)
                ids, cids, proofs = await make_wave(wave)
                for lo in range(0, wave, RPC_CAP):
                    hi = min(lo + RPC_CAP, wave)
                    t0 = time.perf_counter()
                    resp = await client.verify_proof_batch(
                        ids[lo:hi], cids[lo:hi], proofs[lo:hi])
                    timed += time.perf_counter() - t0
                    assert all(r.success for r in resp.results), "verify failed"
                done += wave
                # free session capacity for the next wave (untimed): the
                # per-user session cap is 5, and each success mints one
                for s in list(state._sessions):
                    await state.revoke_session(s)

            # pipelined pass: each wave's RPCs in flight CONCURRENTLY, in
            # ~PIPELINE_WAYS chunks regardless of wave size (a single
            # RPC_CAP chunk would degenerate to the serial path).  The
            # server runs the crypto on a worker thread (GIL released), so
            # RPC k+1's Python overlaps RPC k's verify — the deployment
            # shape with many clients, and the fairer analog of the
            # reference's per-request tokio tasks (service.rs:321-405).
            done = 0
            timed_p = 0.0
            while done < n:
                wave = min(n - done, USERS * CHALLENGES_PER_WAVE)
                ids, cids, proofs = await make_wave(wave)
                step = min(RPC_CAP, max(1, -(-wave // PIPELINE_WAYS)))
                chunks = [(lo, min(lo + step, wave))
                          for lo in range(0, wave, step)]
                t0 = time.perf_counter()
                resps = await asyncio.gather(*[
                    client.verify_proof_batch(
                        ids[lo:hi], cids[lo:hi], proofs[lo:hi])
                    for lo, hi in chunks
                ])
                timed_p += time.perf_counter() - t0
                for resp in resps:
                    assert all(r.success for r in resp.results), "verify failed"
                done += wave
                for s in list(state._sessions):
                    await state.revoke_session(s)

            # streaming pass: every wave's proofs ride ONE bidi stream
            # (verdict-only — mint_sessions off, the bulk-verification
            # shape).  Entries flow into the batcher with no RPC
            # boundary, so the device sees the same deep batches the
            # direct path builds by hand.
            done = 0
            timed_s = 0.0
            while done < n:
                wave = min(n - done, USERS * CHALLENGES_PER_WAVE)
                ids, cids, proofs = await make_wave(wave)
                entries = list(zip(ids, cids, proofs))
                t0 = time.perf_counter()
                n_ok = 0
                # the chunk-level iterator is the bulk-driver surface:
                # per-verdict Python objects are pure client overhead at
                # device-batch rates
                async for chunk_v in client.verify_proof_stream_chunks(
                    entries, chunk=STREAM_CHUNK
                ):
                    n_ok += sum(chunk_v[1])
                timed_s += time.perf_counter() - t0
                assert n_ok == wave, f"stream verify failed: {n_ok}/{wave}"
                done += wave
    finally:
        if ops_plane is not None:
            await ops_plane.stop()
        if batcher is not None:
            await batcher.stop()
        await server.stop(None)
    return n / timed, n / timed_p, n / timed_s


def direct_curve_point(n: int, provers, rng, params, backend_name: str) -> float:
    """BatchVerifier.verify alone (reference batch.rs:171-183 analog)."""
    from cpzk_tpu import BatchVerifier, Transcript
    from cpzk_tpu.protocol.batch import BatchEntry

    if backend_name == "tpu":
        from cpzk_tpu.ops.backend import TpuBackend

        backend = TpuBackend()
    else:
        from cpzk_tpu.protocol.batch import CpuBackend

        backend = CpuBackend()

    proofs = [
        (pr.statement, pr.prove_with_transcript(rng, Transcript()))
        for pr in provers[:64]
    ]
    bv = BatchVerifier(backend=backend, max_size=max(n, 1000))
    for i in range(n):
        st, prf = proofs[i % 64]
        bv.entries.append(BatchEntry(params, st, prf, None))
    assert not any(r is not None for r in bv.verify(rng))  # untimed warmup:
    # on the tpu backend the first call at a new padded shape JIT-compiles;
    # the timed pass below measures throughput, not compilation
    t0 = time.perf_counter()
    results = bv.verify(rng)  # per-proof error-or-None; None == accepted
    dt = time.perf_counter() - t0
    assert not any(r is not None for r in results)
    return n / dt


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ns", default=os.environ.get("CPZK_E2E_NS", ""))
    ap.add_argument("--backend", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--lanes", type=int, default=1,
                    help="serve through N per-device dispatch lanes "
                         "behind the LaneRouter (-1 = one per local "
                         "device / host core; emulate devices on CPU "
                         "with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8).  Entries carry the lane "
                         "count as a perf-gate config key, so a new "
                         "lane count seeds its own trajectory")
    ap.add_argument("--wire", default="native",
                    choices=["native", "python"],
                    help="transport wire path for the serving passes: "
                         "native = the C++ request parser straight off "
                         "the socket bytes (with protobuf fallback), "
                         "python = the protobuf runtime only (the "
                         "historical baseline).  Serving entries carry "
                         "the mode as a perf-gate config key (old "
                         "baselines load as wire=python; a new mode "
                         "seeds its own trajectory); the direct entries "
                         "never touch a wire and keep the python key")
    ap.add_argument("--snapshot", default=None,
                    help="also write a cpzk-perf-snapshot JSON here "
                         "(throughput per n + flight-recorder stage "
                         "percentiles when the batcher path ran)")
    args = ap.parse_args()

    plat = os.environ.get("CPZK_BENCH_PLATFORM")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat)
    if args.backend == "tpu":
        # the shared persistent compile cache: a serving batch's device
        # program must not re-pay a compile an earlier run performed
        from cpzk_tpu import jaxrt

        jaxrt.enable_compile_cache()

    if args.ns:
        ns = [int(x) for x in args.ns.split(",")]
    else:
        # full curve by default; CPU runs should pass --ns to stay small
        ns = [256, 4096, 16384, 65536]

    import jax

    platform = jax.devices()[0].platform if args.backend == "tpu" else "host"

    rng, params, provers = build_corpus()
    snapshot_entries = []
    for n in ns:
        from cpzk_tpu.observability import get_flight_recorder
        from cpzk_tpu.observability.perf import PerfEntry, stage_percentiles

        recorder = get_flight_recorder()
        recorder.clear()  # stage percentiles attribute to this n only
        direct = direct_curve_point(n, provers, rng, params, args.backend)
        grpc_pps, grpc_pipelined, stream_pps = asyncio.run(
            grpc_curve_point(n, provers, rng, args.backend,
                             lanes=args.lanes, wire=args.wire))
        resolved_lanes = args.lanes
        if args.lanes == -1:
            # report the resolved count, not the sentinel
            if args.backend == "tpu":
                resolved_lanes = jax.local_device_count()
            else:
                resolved_lanes = os.cpu_count() or 1
        print(json.dumps({
            "metric": "e2e_curve",
            "n": n,
            "lanes": resolved_lanes,
            "wire": args.wire,
            "grpc_pps": round(grpc_pps, 1),
            "grpc_pipelined_pps": round(grpc_pipelined, 1),
            "stream_pps": round(stream_pps, 1),
            "stream_vs_direct": round(stream_pps / direct, 3),
            "direct_pps": round(direct, 1),
            "platform": platform,
            "backend": args.backend,
            "unit": "proofs/s",
        }), flush=True)
        stages = stage_percentiles(recorder.snapshot())
        for name, pps in (
            ("e2e_curve.grpc", grpc_pps),
            ("e2e_curve.grpc_pipelined", grpc_pipelined),
            ("e2e_curve.stream", stream_pps),
            ("e2e_curve.direct", direct),
        ):
            snapshot_entries.append(PerfEntry(
                name=name, backend=args.backend, n=n,
                value=round(pps, 2), unit="proofs/s",
                lanes=resolved_lanes,
                # direct never touches a wire: it keeps the python key
                # so it gates against the historical baseline on every
                # run regardless of --wire
                wire=args.wire if name != "e2e_curve.direct" else "python",
                stages_ms=stages if name.startswith("e2e_curve.grpc") else {},
            ))

    if args.snapshot:
        from cpzk_tpu.observability.perf import write_snapshot

        write_snapshot(
            args.snapshot, snapshot_entries,
            meta={"bench": "bench_e2e_curve", "platform": platform,
                  "dispatch": "lane"},
        )
        print(f"# perf snapshot written to {args.snapshot}", file=sys.stderr)


if __name__ == "__main__":
    main()
