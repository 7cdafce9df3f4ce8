#!/usr/bin/env python3
"""On-chip smoke: the served authentication path, end to end, on a TPU.

Drives the daemon through its normal entry point
(``python -m cpzk_tpu.server --backend tpu --no-repl``) and checks every
verdict against the host verifier.  The daemon child is the only process
that touches JAX (a chip belongs to one process); this parent does the
client work and the reference checks with the host library, and never
imports jax.

Default (one chip):

1. build the native core from ``cpzk_tpu/native`` and boot the daemon
   (durability on with ``fsync=interval``, soak-sized user caps,
   ``[tpu] batch_max`` past ``LANE_CHUNK``, prewarm of every shape the run
   dispatches); fail at once unless its JAX platform is ``tpu``;
2. register ``USERS`` users through ``RegisterBatch`` in chunks of 1,000
   (statements from a pool of keypairs derived from ``--seed``);
3. ``LOGINS`` concurrent logins (``CreateChallenge`` then ``VerifyProof``),
   1% with a wrong secret, which must be refused; every accepted login
   must mint a session;
4. restart the daemon over the same durable state as a bulk deployment
   (a batch window long enough for a bulk batch to fill: host ingest, not
   the chip, paces a stream) and send ``STREAM_CHUNKS`` x ``STREAM_CHUNK``
   proofs through ``VerifyProofStream`` with invalid proofs at seeded
   indices; every verdict must equal the host ``CpuBackend`` verdict, and
   one device batch must span more than ``LANE_CHUNK`` lanes (the
   full-chunk and remainder-chunk programs);
5. after each phase the ops plane must show the failover never engaged
   and the flight recorder must hold the device batches;
6. SIGTERM, and each daemon must exit 0.

``--chips 4`` runs only step 4, against one daemon that shards every
batch over all devices (``[tpu] mesh_devices = 0``, the default) and one
with a dispatch lane per chip (``[tpu] lanes = -1``), and checks that each
of the 4 devices held arrays (allocator peaks on ``/statusz``) and, for the
lanes, that every lane dispatched.

Every phase prints one informational line (timings are not claims).  The
last line is ``{"ok": true, "device": {...}}`` with the device as the
daemon's JAX reports it; any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import asyncio
import ctypes
import json
import os
import random
import re
import signal
import socket
import statistics
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

# ROADMAP Queue 2 item 1 (the interactive deployment) is 1,000,000 users,
# registered on the chip host in 109.6 s (PR 21).  A cold compile cache
# costs minutes of prewarm that no run has measured, so the smoke keeps
# its margin under the driver's 1,200 s by registering a quarter of them.
USERS = 250_000
REG_CHUNK = 1000      # RegisterBatch size (the reference's MAX_BATCH)
POOL = 1024           # distinct keypairs; user i holds pool[i % POOL]
LOGINS = 4096
# the interactive deployment coalesces logins for up to LOGIN_WINDOW_MS:
# at the host-bound unary rate (~600 logins/s on the chip host) batches
# of a few hundred rows, padded to 128..512 lanes
LOGIN_WINDOW_MS = 250
LOGIN_QUANTA = (127, 255, 511)
# 4095-entry chunks: a batch of k whole chunks is 4095k rows, whose
# combined check (one correction row) and per-row fallback both pad to
# exactly 4096k lanes
STREAM_CHUNK = 4095
STREAM_CHUNKS = 16    # 65,520 proofs
BATCH_MAX = 9 * STREAM_CHUNK  # 36,855 rows: >= 32,768, whole chunks only
# the bulk deployment's window: a stream arrives at ~2 chunks/s (host
# ingest), so a batch crossing LANE_CHUNK needs seconds to fill
BULK_WINDOW_MS = 5000
# ~2 chunks/s fill the first batch to BATCH_MAX (9 chunks) and leave 7 for
# the second; a batch of another size compiles on first sight (counted)
BULK_BATCH_CHUNKS = (7, 9)
STREAM_BAD = 24       # invalid stream proofs, at seeded indices
LANE_CHUNK = 16384    # ops/backend.LANE_CHUNK
CONCURRENCY = 512     # in-flight unary RPCs while staging challenges
# four chips: ten chunks (40,950 proofs) per daemon.  The mesh takes
# 20,475-row batches: 131,072 MSM terms, two 65,536-term mesh slices of
# one sharded program (16,384 lanes a device), prewarmed at boot with the
# sharded verify_each.  The lanes take 4,095-row batches, several per
# lane: every chip compiles its own single-device programs, and one
# chunk keeps that to two ~10 s compiles each (the chunked single-device
# dispatch is the one-chip run's to prove).
CHIPS4_CHUNKS = 10
CHIPS4_RUNS = (
    ("mesh", 5 * STREAM_CHUNK, {}),
    ("lanes", STREAM_CHUNK, {"SERVER_TPU_LANES": "-1"}),
)
PLATFORM = "tpu"      # what the daemon's JAX must report
TIMEOUT_S = 1150      # inside the driver's 1,200 s: fail and stop the daemons


def info(msg: str) -> None:
    print(f"# {msg}", flush=True)


class Rng:
    """Seeded byte source with the ``SecureRng`` surface: keys and nonces
    from ``--seed``.  Not a CSPRNG; this is test data."""

    def __init__(self, seed: int):
        self._r = random.Random(seed)

    def fill_bytes(self, n: int) -> bytes:
        return self._r.randbytes(n)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _die_with_parent() -> None:
    # the daemon must not outlive this process, even on SIGKILL
    ctypes.CDLL("libc.so.6", use_errno=True).prctl(1, signal.SIGKILL)


def build_native() -> None:
    t0 = time.monotonic()
    subprocess.run(["make", "-s", "-B"], cwd=os.path.join(ROOT, "cpzk_tpu", "native"),
                   check=True, timeout=600)
    from cpzk_tpu.core import _native

    if _native.load() is None:
        raise RuntimeError("native core built but did not load")
    info(f"native core: built from cpzk_tpu/native in {time.monotonic() - t0:.1f}s")


# -- the daemon ---------------------------------------------------------------


class Daemon:
    """One ``cpzk_tpu.server --backend tpu`` child and its ops plane."""

    def __init__(self, workdir: str, log_dir: str, name: str, users: int,
                 extra_env: dict, state: str | None = None):
        self.name = name
        self.port = free_port()
        self.ops_port = free_port()
        self.address = f"127.0.0.1:{self.port}"
        self.log_path = os.path.join(log_dir, f"{name}.log")
        state_dir = os.path.join(workdir, state or name)
        os.makedirs(state_dir, exist_ok=True)
        env = dict(os.environ)
        env.update({
            "SERVER_CONFIG_PATH": os.path.join(state_dir, "none.toml"),
            "SERVER_STATE_FILE": os.path.join(state_dir, "state.json"),
            # caps sized to the corpus (benches/bench_soak.py daemon_env)
            "SERVER_MAX_USERS": str(max(2 * users, 10_000)),
            "SERVER_MAX_SESSIONS": str(max(2 * users, 100_000)),
            "SERVER_MAX_CHALLENGES": str(max(users, 200_000)),
            "SERVER_DURABILITY_ENABLED": "1",
            "SERVER_DURABILITY_FSYNC": "interval",
            "SERVER_DURABILITY_FSYNC_INTERVAL_MS": "100",
            "SERVER_DURABILITY_WAL_SEGMENT_BYTES": str(4 << 20),
            "SERVER_DURABILITY_COMPACT_BYTES": str(8 << 20),
            "SERVER_OPSPLANE_ENABLED": "1",
            "SERVER_OPSPLANE_PORT": str(self.ops_port),
            "SERVER_RATE_LIMIT_REQUESTS_PER_MINUTE": "1000000000",
            "SERVER_RATE_LIMIT_BURST": "100000000",
        })
        env.update(extra_env)
        # a stream holds at most two batches in flight: inside the
        # batcher's queue capacity (4 x batch_max), so gRPC flow control,
        # not a QueueFull shed, paces the sender
        env["SERVER_TPU_STREAM_WINDOW"] = str(2 * int(env["SERVER_TPU_BATCH_MAX"]))
        self._log = open(self.log_path, "ab")
        self.started = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "cpzk_tpu.server", "--backend", "tpu",
             "--no-repl", "--host", "127.0.0.1", "--port", str(self.port)],
            env=env, cwd=ROOT, stdout=self._log, stderr=subprocess.STDOUT,
            preexec_fn=_die_with_parent,
        )

    def log_text(self) -> str:
        with open(self.log_path, encoding="utf-8", errors="replace") as f:
            return f.read()

    def log_tail(self, n: int = 40) -> str:
        return "\n".join(self.log_text().splitlines()[-n:])

    def get(self, path: str):
        url = f"http://127.0.0.1:{self.ops_port}{path}"
        with urllib.request.urlopen(url, timeout=30) as r:
            body = r.read().decode()
        return body if path == "/metrics" else json.loads(body)

    async def wait_serving(self, timeout_s: float = 900.0) -> dict:
        """Block until gRPC health is SERVING; returns the boot statement
        parsed from the daemon's "serving plane" log line, failing as soon
        as that line names a platform other than tpu."""
        import grpc

        from cpzk_tpu.client import AuthClient

        plane = re.compile(r"serving plane: .*platform=(\S+) kind=(.+?) "
                           r"count=(\d+) native=(\w+)")
        device = None
        deadline = time.monotonic() + timeout_s
        while True:
            rc = self.proc.poll()
            if rc is not None:
                raise RuntimeError(
                    f"{self.name} daemon exited rc={rc} while booting:\n"
                    f"{self.log_tail()}")
            log_text = self.log_text()
            if device is None:
                m = plane.search(log_text)
                if m:
                    device = {"platform": m.group(1),
                              "kind": m.group(2).strip("'\""),
                              "count": int(m.group(3)),
                              "native": m.group(4) == "True"}
                    if device["platform"] != PLATFORM:
                        raise SystemExit(
                            "chip_smoke: no TPU found — the daemon's JAX "
                            f"platform is {device['platform']!r}")
            # the daemon logs its wire path just before the listener
            # starts; a channel opened earlier would back off for minutes
            # across a long prewarm, so each poll opens a fresh one
            if device is not None and "wire path:" in log_text:
                try:
                    async with AuthClient(self.address) as client:
                        resp = await client.health_check(timeout=5)
                    if resp.status == 1:  # SERVING
                        break
                except grpc.RpcError:
                    pass  # listener not bound yet
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"{self.name} daemon not SERVING after {timeout_s}s:\n"
                    f"{self.log_tail()}")
            await asyncio.sleep(0.5)
        boot_s = time.monotonic() - self.started
        warm = re.search(r"prewarmed (\d+) verify executables .*? in ([\d.]+)s",
                         self.log_text())
        info(f"boot[{self.name}]: SERVING after {boot_s:.1f}s; prewarm "
             + (f"{warm.group(1)} executables in {warm.group(2)}s" if warm
                else "none")
             + f"; platform={device['platform']} kind={device['kind']!r} "
             f"count={device['count']} native={device['native']}")
        if not device["native"]:
            raise RuntimeError("the daemon did not load the native core")
        return device

    def stop(self) -> None:
        t0 = time.monotonic()
        self.proc.send_signal(signal.SIGTERM)
        rc = self.proc.wait(timeout=300)
        self._log.close()
        if rc != 0:
            raise RuntimeError(f"{self.name} daemon exited rc={rc} on SIGTERM:\n"
                               f"{self.log_tail()}")
        info(f"shutdown[{self.name}]: exit 0 {time.monotonic() - t0:.1f}s "
             "after SIGTERM")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


# -- traffic ------------------------------------------------------------------


class Corpus:
    """Keypairs from ``--seed``: user ``u{i}`` holds ``POOL`` key i % POOL."""

    def __init__(self, seed: int):
        from cpzk_tpu import Parameters, Prover, Ristretto255, Witness

        self.rng = Rng(seed)
        self.params = Parameters.new()
        self.provers = [
            Prover(self.params, Witness(Ristretto255.random_scalar(self.rng)))
            for _ in range(POOL)
        ]
        eb = Ristretto255.element_to_bytes
        self.y1 = [eb(p.statement.y1) for p in self.provers]
        self.y2 = [eb(p.statement.y2) for p in self.provers]

    def prove(self, i: int, challenge_id: bytes, valid: bool) -> bytes:
        """User i's proof for one challenge; ``valid=False`` proves with
        another user's secret (a well-formed proof that must be refused)."""
        from cpzk_tpu import Transcript

        prover = self.provers[i % POOL if valid else (i + 1) % POOL]
        t = Transcript()
        t.append_context(challenge_id)
        return prover.prove_with_transcript(self.rng, t).to_bytes()

    def host_verdicts(self, users: list[int], cids: list[bytes],
                      wires: list[bytes]) -> list[bool]:
        """The plain reference: the host ``CpuBackend`` batch verifier."""
        from cpzk_tpu import BatchVerifier, Proof
        from cpzk_tpu.protocol.batch import CpuBackend

        bv = BatchVerifier(backend=CpuBackend(), max_size=len(users))
        for i, cid, wire in zip(users, cids, wires, strict=True):
            bv.add_with_context(self.params, self.provers[i % POOL].statement,
                                Proof.from_bytes(wire), cid)
        return [r is None for r in bv.verify(self.rng)]


async def gather_limited(coros, limit: int = CONCURRENCY) -> list:
    sem = asyncio.Semaphore(limit)

    async def one(c):
        async with sem:
            return await c

    return await asyncio.gather(*(one(c) for c in coros))


async def register(client, corpus: Corpus, n_users: int) -> None:
    t0 = time.monotonic()

    async def chunk(lo: int) -> None:
        hi = min(lo + REG_CHUNK, n_users)
        resp = await client.register_batch(
            [f"u{i}" for i in range(lo, hi)],
            [corpus.y1[i % POOL] for i in range(lo, hi)],
            [corpus.y2[i % POOL] for i in range(lo, hi)],
            timeout=300,
        )
        bad = [r.message for r in resp.results if not r.success]
        if bad:
            raise RuntimeError(f"registration failed: {bad[:3]}")

    await gather_limited([chunk(lo) for lo in range(0, n_users, REG_CHUNK)], 4)
    dt = time.monotonic() - t0
    info(f"registration: {n_users} users in {dt:.1f}s ({n_users / dt:.0f}/s)")


async def challenges(client, users: list[int]) -> list[bytes]:
    resps = await gather_limited(
        [client.create_challenge(f"u{i}", timeout=120) for i in users])
    return [bytes(r.challenge_id) for r in resps]


def batch_stats(daemon: Daemon, after_seq: int) -> tuple[list[dict], int]:
    """Flight records newer than ``after_seq``, and the newest seq."""
    recs = daemon.get("/flightrec")["records"]
    new = [r for r in recs if r["seq"] > after_seq]
    return new, max((r["seq"] for r in recs), default=after_seq)


def describe_batches(recs: list[dict]) -> str:
    if not recs:
        return "no device batches"
    sizes = [r["batch"] for r in recs]
    return (f"{len(recs)} device batches of {min(sizes)}..{max(sizes)} rows "
            f"(median {statistics.median(sizes):.0f}), largest "
            f"{max(r['lanes'] for r in recs)} lanes, "
            f"{sum(r['jit_misses'] for r in recs)} compiles while serving")


async def logins(client, corpus: Corpus, daemon: Daemon, seed: int) -> None:
    import grpc

    users = list(range(LOGINS))
    bad = set(random.Random(seed + 1).sample(users, LOGINS // 100))
    t0 = time.monotonic()
    cids = await challenges(client, users)
    t_ch = time.monotonic() - t0
    wires = [corpus.prove(i, c, i not in bad) for i, c in zip(users, cids)]
    expect = corpus.host_verdicts(users, cids, wires)
    _, seq0 = batch_stats(daemon, 0)
    lat: list[float] = []

    async def login(i: int) -> tuple[bool, str]:
        t0 = time.monotonic()
        try:
            resp = await client.verify_proof(f"u{i}", cids[i], wires[i])
        except grpc.RpcError as e:
            if e.code() != grpc.StatusCode.PERMISSION_DENIED:
                raise
            return False, ""
        finally:
            lat.append((time.monotonic() - t0) * 1000.0)
        return resp.success, resp.session_token

    t0 = time.monotonic()
    got = await asyncio.gather(*(login(i) for i in users))
    dt = time.monotonic() - t0
    wrong = [i for i in users if got[i][0] != expect[i]]
    if wrong:
        raise RuntimeError(f"{len(wrong)} login verdicts differ from the host "
                           f"verifier (first: {wrong[:5]})")
    if sorted(i for i in users if not expect[i]) != sorted(bad):
        raise RuntimeError("the host verifier disagrees with the seeded "
                           "wrong-secret logins")
    no_session = [i for i in users if got[i][0] and not got[i][1]]
    if no_session:
        raise RuntimeError(f"{len(no_session)} accepted logins minted no session")
    recs, _ = batch_stats(daemon, seq0)
    lat.sort()
    info(f"logins[{daemon.name}]: {LOGINS} concurrent in {dt:.2f}s, {len(bad)} wrong-secret "
         f"refused, all verdicts equal the host's, every accept minted a "
         f"session; VerifyProof p50 {lat[len(lat) // 2]:.1f}ms p99 "
         f"{lat[int(len(lat) * 0.99)]:.1f}ms ({t_ch:.1f}s to stage the "
         f"challenges); {describe_batches(recs)}")
    return recs


async def stream(client, corpus: Corpus, daemon: Daemon, first_user: int,
                 chunks: int, seed: int, label: str) -> list[dict]:
    n = STREAM_CHUNK * chunks
    users = list(range(first_user, first_user + n))
    # invalid proofs only in the last quarter: the first batches are all
    # valid, so the device's combined check must ACCEPT them (a false
    # reject is masked in the verdicts by the per-row fallback)
    bad = set(random.Random(seed + 2).sample(range(n - n // 4, n), STREAM_BAD))
    t0 = time.monotonic()
    cids = await challenges(client, users)
    t_ch = time.monotonic() - t0
    wires = [corpus.prove(u, c, k not in bad)
             for k, (u, c) in enumerate(zip(users, cids))]
    expect = corpus.host_verdicts(users, cids, wires)
    _, seq0 = batch_stats(daemon, 0)
    entries = [(f"u{u}", c, w) for u, c, w in zip(users, cids, wires)]
    verdicts: list[bool | None] = [None] * n
    t0 = time.monotonic()
    async for ids, ok, _msgs, _tokens, push in client.verify_proof_stream_chunks(
            entries, chunk=STREAM_CHUNK):
        if push:
            raise RuntimeError(f"stream entries shed (retry after {push} ms)")
        for k, v in zip(ids, ok, strict=True):
            verdicts[k] = v
    dt = time.monotonic() - t0
    if None in verdicts:
        raise RuntimeError(f"{verdicts.count(None)} stream entries got no verdict")
    wrong = [k for k in range(n) if verdicts[k] != expect[k]]
    if wrong:
        raise RuntimeError(f"{len(wrong)} stream verdicts differ from the host "
                           f"verifier (first: {wrong[:5]})")
    if sorted(k for k in range(n) if not expect[k]) != sorted(bad):
        raise RuntimeError("the host verifier disagrees with the seeded "
                           "invalid stream proofs")
    recs, _ = batch_stats(daemon, seq0)
    info(f"stream[{label}]: {n} proofs ({len(bad)} invalid) in {dt:.2f}s "
         f"({n / dt:.0f} proofs/s; {t_ch:.1f}s to stage the challenges), "
         f"every verdict equals the host's; {describe_batches(recs)}")
    if all(r["lane"] is None for r in recs):
        check_combined(recs, bad, label)
    return recs


def check_combined(recs: list[dict], bad: set[int], label: str) -> None:
    """One dispatch lane takes the stream's entries in order, so record k
    holds entries [sum of earlier batches, + its batch): its combined check,
    where it ran, must accept exactly when that range holds no invalid
    proof.  A batch after one that held an invalid proof may skip it
    (``combined`` null: the backend's combined-check gate)."""
    lo = 0
    rows = []
    for r in sorted(recs, key=lambda r: r["seq"]):
        hi = lo + r["batch"]
        valid = not any(lo <= k < hi for k in bad)
        if r["combined"] is None:
            rows.append(f"{r['batch']}/{r['lanes']}.{'' if valid else '!'}")
            lo = hi
            continue
        if r["combined"] and not valid:
            raise RuntimeError(
                f"[{label}] the combined check accepted a batch of "
                f"{r['batch']} rows / {r['lanes']} lanes holding an invalid proof")
        if valid and not r["combined"]:
            # the per-row fallback would still give correct verdicts, so
            # only this record shows the device got the check wrong
            raise RuntimeError(
                f"[{label}] the combined check rejected an all-valid batch "
                f"of {r['batch']} rows / {r['lanes']} lanes")
        rows.append(f"{r['batch']}/{r['lanes']}"
                    f"{'+' if r['combined'] else '-'}{'' if valid else '!'}")
        lo = hi
    info(f"combined check[{label}]: rows/lanes per batch, +/- accepted/"
         f"rejected on device, . skipped, ! holds an invalid proof: "
         f"{' '.join(rows)}")


def check_ops_plane(daemon: Daemon, recs: list[dict],
                    cross_chunk: bool = True) -> dict:
    """The failover never engaged, and the recorder holds device batches
    (one past LANE_CHUNK lanes, with ``cross_chunk``); returns /statusz."""
    status = daemon.get("/statusz")
    metrics = daemon.get("/metrics")
    fallback = sum(
        float(line.rsplit(" ", 1)[1]) for line in metrics.splitlines()
        if line.startswith("tpu_batch_device_time_count{")
        and 'backend="fallback"' in line)
    state = [float(line.rsplit(" ", 1)[1]) for line in metrics.splitlines()
             if line.startswith("tpu_backend_state ")]
    breaker = status["breaker"]
    lanes = status["lanes"]
    if breaker is not None and breaker["state"] != "closed":
        raise RuntimeError(f"failover engaged: breaker {breaker}")
    if lanes is not None:
        bad = [r for r in lanes["lanes"] if r["breaker"] != "closed" or r["errors"]]
        if bad:
            raise RuntimeError(f"lane failover engaged: {bad}")
    if any(state):
        raise RuntimeError(f"tpu.backend.state is {state}, not primary")
    if fallback:
        raise RuntimeError(f"{fallback:.0f} batches carry the fallback label")
    if not recs or any(r["backend"] == "fallback" for r in recs):
        raise RuntimeError("the flight recorder holds no primary device batches")
    widest = max(r["lanes"] for r in recs)
    if cross_chunk and widest <= LANE_CHUNK:
        raise RuntimeError(f"largest device batch was {widest} lanes; the run "
                           f"must cross LANE_CHUNK={LANE_CHUNK}")
    info(f"ops plane[{daemon.name}]: breaker closed, tpu.backend.state primary, "
         f"0 fallback batches, largest device batch {widest} lanes, "
         f"{status['dispatch']['recorded_batches']} flight records")
    return status


def check_all_devices_worked(status: dict, name: str) -> None:
    """Each of the 4 devices held arrays (allocator peaks), and under
    ``lanes = -1`` each lane dispatched batches."""
    peaks = {m["id"]: m["peak_bytes_in_use"] for m in status["device"]["memory"]}
    idle = [d for d in range(4) if peaks.get(d, 0) < (1 << 20)]
    if idle:
        raise RuntimeError(f"[{name}] devices {idle} held no arrays "
                           f"(allocator peaks {peaks})")
    work = f"allocator peaks {peaks}"
    if status["lanes"] is not None:
        dispatches = [r["dispatches"] for r in status["lanes"]["lanes"]]
        if len(dispatches) != 4 or not all(dispatches):
            raise RuntimeError(f"[{name}] per-lane dispatches {dispatches}")
        work += f"; per-lane dispatches {dispatches}"
    info(f"devices[{name}]: all 4 did work — {work}")


# -- the two runs -------------------------------------------------------------


def client_for(daemon: Daemon):
    """An AuthClient that honors overload pushback: after a saturating
    phase the admission controller sheds the challenge tier until its
    level recovers, and staging tens of thousands of challenges must
    ride that out (CreateChallenge and RegisterBatch are retry-safe)."""
    from cpzk_tpu.client import AuthClient
    from cpzk_tpu.resilience.retry import RetryPolicy

    return AuthClient(daemon.address,
                      retry=RetryPolicy(max_attempts=30, budget=None))


async def one_chip(args, workdir: str, log_dir: str) -> dict:
    corpus = Corpus(args.seed)
    interactive = Daemon(workdir, log_dir, "interactive", USERS, {
        "SERVER_TPU_BATCH_MAX": str(BATCH_MAX),
        "SERVER_TPU_BATCH_WINDOW_MS": str(LOGIN_WINDOW_MS),
        "SERVER_TPU_PREWARM_QUANTA": ",".join(map(str, LOGIN_QUANTA)),
    }, state="state")
    try:
        device = await interactive.wait_serving()
        if device["count"] != 1:
            raise RuntimeError(f"expected one chip, JAX reports {device['count']}")
        async with client_for(interactive) as client:
            info(f"registration: {USERS} users, a quarter of the 1M "
                 "deployment: the margin for a cold compile cache")
            await register(client, corpus, USERS)
            recs = await logins(client, corpus, interactive, args.seed)
        check_ops_plane(interactive, recs, cross_chunk=False)
        interactive.stop()
    finally:
        interactive.kill()
    bulk = Daemon(workdir, log_dir, "bulk", USERS, {
        "SERVER_TPU_BATCH_MAX": str(BATCH_MAX),
        "SERVER_TPU_BATCH_WINDOW_MS": str(BULK_WINDOW_MS),
        "SERVER_TPU_PREWARM_QUANTA": ",".join(
            str(k * STREAM_CHUNK) for k in BULK_BATCH_CHUNKS),
    }, state="state")
    try:
        await bulk.wait_serving()
        restored = bulk.get("/statusz")["shards"]["users"]
        if restored != USERS:
            raise RuntimeError(f"restart restored {restored} of {USERS} users")
        info(f"restart[bulk]: {restored} users restored from the durable state")
        async with client_for(bulk) as client:
            recs = await stream(client, corpus, bulk, LOGINS, STREAM_CHUNKS,
                                args.seed, "bulk")
        status = check_ops_plane(bulk, recs)
        bulk.stop()
    finally:
        bulk.kill()
    return status["device"]


async def four_chips(args, workdir: str, log_dir: str) -> dict:
    corpus = Corpus(args.seed)
    n = STREAM_CHUNK * CHIPS4_CHUNKS
    device = None
    for name, batch_max, extra in CHIPS4_RUNS:
        daemon = Daemon(workdir, log_dir, name, n, {
            **extra,
            "SERVER_TPU_BATCH_MAX": str(batch_max),
            "SERVER_TPU_BATCH_WINDOW_MS": str(BULK_WINDOW_MS),
            "SERVER_TPU_PREWARM_QUANTA": str(batch_max),
        })
        try:
            device = await daemon.wait_serving()
            if device["count"] != 4:
                raise RuntimeError(f"expected 4 chips, JAX reports {device['count']}")
            async with client_for(daemon) as client:
                await register(client, corpus, n)
                recs = await stream(client, corpus, daemon, 0, CHIPS4_CHUNKS,
                                    args.seed, name)
            status = check_ops_plane(daemon, recs,
                                     cross_chunk=batch_max > LANE_CHUNK)
            check_all_devices_worked(status, name)
            daemon.stop()
        finally:
            daemon.kill()
        device = status["device"]
    return device


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-dir", default=None,
                   help="keep the daemon logs here (default: a temp dir)")
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "cpzk_tpu")):
        sys.exit("chip_smoke: no cpzk_tpu/ beside this script — run it from a "
                 "checkout of the repository")
    sys.path.insert(0, ROOT)

    def expire(signum, _frame):
        raise TimeoutError(f"chip_smoke: signal {signum} (timeout "
                           f"{TIMEOUT_S}s or termination)")

    signal.signal(signal.SIGALRM, expire)
    signal.signal(signal.SIGTERM, expire)
    signal.alarm(TIMEOUT_S)
    t0 = time.monotonic()
    build_native()
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    # daemon state (WAL, snapshots: hundreds of MB at 1M users) stays in
    # a temp dir; only the logs go to --log-dir
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as tmp:
        run = one_chip if args.chips == 1 else four_chips
        device = asyncio.run(run(args, tmp, args.log_dir or tmp))
    assert "jax" not in sys.modules, "the parent imported jax"
    if device["platform"] != PLATFORM or device["count"] != args.chips:
        raise RuntimeError(f"daemon device {device} is not {args.chips} TPU chip(s)")
    info(f"total {time.monotonic() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
