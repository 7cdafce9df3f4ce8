"""Audit subsystem tests: the proof log (WAL-framed, append-only), the
bulk replay pipeline (resumable cursor, byte-exact SIGKILL resume,
mismatch detection), the Schnorr-signed report (offline verification,
single-flipped-byte failure), the service-side trail (unary, batch, and
stream paths all append records), and the ``[audit]`` config section
(layering + drift guard)."""

import asyncio
import dataclasses
import json
import os
import pathlib
import re
import signal
import struct
import subprocess
import sys
import threading
import time

import pytest

from cpzk_tpu import Parameters, Prover, SecureRng, Transcript, Witness
from cpzk_tpu.audit import (
    ProofLogWriter,
    proof_record,
    read_log,
    run_audit,
    scan_records,
    verify_report_file,
)
from cpzk_tpu.audit import pipeline
from cpzk_tpu.audit import sign as audit_sign
from cpzk_tpu.audit.log import validate_proof_record
from cpzk_tpu.audit.pipeline import AuditState
from cpzk_tpu.core.ristretto import Ristretto255
from cpzk_tpu.protocol.batch import CpuBackend
from cpzk_tpu.server.config import AuditSettings, ServerConfig

ROOT = pathlib.Path(__file__).resolve().parent.parent


def run(coro):
    return asyncio.run(coro)


def make_log(
    path, n, users=4, reject_every=0, mismatch_every=0, rng=None
):
    """A proof log of ``n`` REAL records (same construction as the
    service's trail): returns (writer_seq, provers)."""
    rng = rng or SecureRng()
    params = Parameters.new()
    eb = Ristretto255.element_to_bytes
    provers = [
        Prover(params, Witness(Ristretto255.random_scalar(rng)))
        for _ in range(users)
    ]
    writer = ProofLogWriter(str(path))
    payloads = []
    for i in range(n):
        prover = provers[i % users]
        ctx = rng.fill_bytes(32)
        t = Transcript()
        t.append_context(ctx)
        wire = prover.prove_with_transcript(rng, t).to_bytes()
        verdict = True
        if reject_every and i % reject_every == 1:
            wire = wire[:-1] + bytes([wire[-1] ^ 1])
            verdict = False
        if mismatch_every and i % mismatch_every == 2:
            verdict = not verdict
        payloads.append(proof_record(
            f"u{i % users}",
            eb(prover.statement.y1), eb(prover.statement.y2),
            ctx, wire, verdict,
        ))
    writer.append_proofs(payloads)
    writer.close()
    return writer.seq, provers


# --- proof log ---------------------------------------------------------------


def test_proof_log_roundtrip_seq_resume_and_perms(tmp_path):
    path = tmp_path / "p.log"
    seq, _ = make_log(path, 5)
    assert seq == 5
    records, valid, total = read_log(str(path))
    assert [r["seq"] for r in records] == [1, 2, 3, 4, 5]
    assert valid == total
    assert all(validate_proof_record(r) is None for r in records)
    assert oct(path.stat().st_mode & 0o777) == "0o600"

    # reopening resumes the sequence, keeping the prefix contract intact
    w2 = ProofLogWriter(str(path))
    assert w2.seq == 5
    w2.append_proofs([records[0] | {}])  # payload fields reused; new seq
    w2.close()
    records2, valid2, total2 = read_log(str(path))
    assert [r["seq"] for r in records2] == [1, 2, 3, 4, 5, 6]
    assert valid2 == total2


def test_validate_proof_record_rejections():
    good = proof_record("u", b"\x01" * 32, b"\x02" * 32, b"c" * 32,
                        b"p" * 109, True)
    good["type"] = "proof"
    assert validate_proof_record(good) is None
    assert validate_proof_record({"type": "register_user"}) is not None
    for key in ("y1", "y2", "ctx", "p"):
        bad = dict(good)
        bad[key] = "zz-not-hex"
        assert validate_proof_record(bad) == f"bad-{key}"
        bad[key] = ""
        assert validate_proof_record(bad) == f"bad-{key}"
    bad = dict(good)
    bad["v"] = 2
    assert validate_proof_record(bad) == "bad-verdict"
    bad["v"] = True  # JSON booleans are not the 0/1 the service writes
    assert validate_proof_record(bad) == "bad-verdict"
    bad = dict(good)
    bad["u"] = 7
    assert validate_proof_record(bad) == "bad-user"


def test_scan_records_split_resume_equivalence(tmp_path):
    """Scanning from a cursor (offset, prev_seq) at ANY frame boundary
    yields exactly the whole-buffer scan's suffix — the property SIGKILL
    resume rests on."""
    path = tmp_path / "p.log"
    make_log(path, 9)
    buf = path.read_bytes()
    records, valid = scan_records(buf)
    assert len(records) == 9 and valid == len(buf)
    from cpzk_tpu.durability.wal import HEADER_BYTES, _HEADER

    off = 0
    for k in range(9):
        tail, tail_valid = scan_records(
            buf, offset=off, prev_seq=records[k - 1]["seq"] if k else None
        )
        assert tail == records[k:]
        assert tail_valid == valid
        length, _ = _HEADER.unpack_from(buf, off)
        off += HEADER_BYTES + length


# --- pipeline ----------------------------------------------------------------


def test_pipeline_report_totals_and_offline_signature(tmp_path):
    log = tmp_path / "p.log"
    make_log(log, 40, reject_every=10, mismatch_every=13)
    report_path = str(tmp_path / "report.json")
    report = run_audit(str(log), report_path, quantum=16)
    t = report["totals"]
    assert t["records"] == 40
    assert t["audited"] == 40
    assert t["verified"] + t["rejected"] == 40
    assert t["rejected"] == 4       # i % 10 == 1
    assert t["mismatched"] == 3     # i % 13 == 2 (and not also a reject)
    ok, reason, loaded = verify_report_file(report_path)
    assert ok, reason
    assert loaded["digest"] == report["digest"]
    # the cursor is gone after a completed run
    assert not os.path.exists(report_path + ".cursor")
    # exit-code contract: mismatches are a FINDING
    from cpzk_tpu.audit.__main__ import main as audit_main

    assert audit_main([
        "verify-report", "--report", report_path
    ]) == 0


def test_pipeline_resume_is_byte_exact(tmp_path):
    log = tmp_path / "p.log"
    make_log(log, 30, reject_every=7)
    a = str(tmp_path / "a.json")
    b = str(tmp_path / "b.json")
    key = str(tmp_path / "audit.key")
    full = run_audit(str(log), a, key_path=key, quantum=8)
    assert full is not None
    # interrupted run: 2 quanta then stop (modelling a crash after the
    # checkpoint landed), then resume to completion
    assert run_audit(str(log), b, key_path=key, quantum=8,
                     max_batches=2) is None
    assert os.path.exists(b + ".cursor")
    resumed = run_audit(str(log), b, key_path=key, quantum=8)
    assert resumed is not None
    assert open(a).read() == open(b).read()  # signature included
    assert resumed["digest"] == full["digest"]


class _Boom(RuntimeError):
    pass


class _DeviceFault(CpuBackend):
    """Raises in the device phase of quantum ``fail_at`` (each quantum
    holds a wrong secret, so each dispatch reaches ``verify_each``)."""

    def __init__(self, fail_at: int):
        super().__init__()
        self.fail_at = fail_at
        self.calls = 0

    def verify_each(self, rows):
        self.calls += 1
        if self.calls == self.fail_at + 1:
            raise _Boom("device phase")
        return super().verify_each(rows)


def _frame_offsets(path) -> list[int]:
    """Byte offset after each frame of a proof log, read apart from the
    pipeline: ``offsets[n]`` is where a cursor stands after n records."""
    buf = pathlib.Path(path).read_bytes()
    offsets = [0]
    while offsets[-1] < len(buf):
        (length,) = struct.unpack_from(">I", buf, offsets[-1])
        offsets.append(offsets[-1] + 8 + length)
    return offsets


def _audit_workers() -> list:
    return [t for t in threading.enumerate()
            if t.name.startswith("cpzk-audit-device")]


@pytest.mark.parametrize("phase", ["device", "host"])
def test_pipeline_failure_leaves_cursor_after_previous_quantum(
        tmp_path, monkeypatch, phase):
    """A failure in quantum k — its device phase on the worker, or its
    host prep on the caller while k-1 is in flight — raises its own type
    and leaves the cursor after k-1, as a serial loop does; no worker
    thread outlives the call, and a resume signs the identical report."""
    quantum, k = 8, 2
    log = tmp_path / "p.log"
    make_log(log, 4 * quantum, reject_every=quantum)
    key = str(tmp_path / "audit.key")
    full = str(tmp_path / "full.json")
    assert run_audit(str(log), full, key_path=key, quantum=quantum)

    if phase == "device":
        monkeypatch.setattr(pipeline, "build_backend",
                            lambda *a, **kw: _DeviceFault(k))
    else:
        decoded = []
        real = pipeline._record_entry

        def record_entry(rec):
            if len(decoded) == k * quantum:
                raise _Boom("host prep")
            decoded.append(rec)
            return real(rec)

        monkeypatch.setattr(pipeline, "_record_entry", record_entry)
    out = str(tmp_path / "out.json")
    with pytest.raises(_Boom):
        run_audit(str(log), out, key_path=key, quantum=quantum)
    assert not _audit_workers()
    with open(out + ".cursor", encoding="utf-8") as f:
        cursor = json.load(f)
    assert cursor["records"] == k * quantum
    assert cursor["offset"] == _frame_offsets(log)[k * quantum]

    monkeypatch.undo()
    assert run_audit(str(log), out, key_path=key, quantum=quantum)
    assert pathlib.Path(out).read_bytes() == pathlib.Path(full).read_bytes()


@pytest.mark.parametrize("max_batches", [1, 2])
def test_pipeline_max_batches_folds_exactly_that_many(tmp_path, max_batches):
    """``max_batches=k`` folds and checkpoints k quanta, calls
    ``progress`` once after each checkpoint, and never prepares or
    dispatches quantum k+1."""
    from cpzk_tpu.observability import get_tracer

    quantum = 8
    log = tmp_path / "p.log"
    make_log(log, 4 * quantum, reject_every=quantum)
    offsets = _frame_offsets(log)
    out = str(tmp_path / "out.json")
    seen = []

    def progress(state):
        with open(out + ".cursor", encoding="utf-8") as f:
            seen.append((state.records, json.load(f)["offset"]))

    assert run_audit(str(log), out, quantum=quantum, max_batches=max_batches,
                     progress=progress) is None
    assert not _audit_workers()
    assert seen == [(n * quantum, offsets[n * quantum])
                    for n in range(1, max_batches + 1)]
    trace = [t for t in get_tracer().completed() if t.name == "audit.run"][-1]
    assert trace.status == "checkpointed"
    names = trace.span_names()
    assert names.count("audit.fold") == max_batches
    assert names.count("pad_and_pack") == max_batches
    assert names.count("device_dispatch") == max_batches
    assert "audit.decode" not in [
        s.name for s in trace.spans if s.attrs.get("quantum") == max_batches]


def test_pipeline_skips_garbage_and_stops_at_corruption(tmp_path):
    from cpzk_tpu.durability.wal import encode_record

    log = tmp_path / "p.log"
    make_log(log, 6)
    # append a non-proof record (skipped) and a bad-hex proof record
    # (skipped), then a torn tail (scan stops, never raises)
    with open(log, "ab") as f:
        f.write(encode_record({"seq": 7, "type": "register_user", "u": "x"}))
        f.write(encode_record({
            "seq": 8, "type": "proof", "u": "x", "y1": "zz", "y2": "zz",
            "ctx": "00", "p": "00", "v": 1, "t": 0,
        }))
        f.write(b"\x00\x00\x00\x10CORRUPTED-TAIL")
    report = run_audit(str(log), str(tmp_path / "r.json"), quantum=4)
    t = report["totals"]
    assert t["records"] == 8
    assert t["audited"] == 6 and t["verified"] == 6
    assert t["skipped"] == 2
    assert report["log"]["valid_bytes"] < report["log"]["file_bytes"]
    ok, reason, _ = verify_report_file(str(tmp_path / "r.json"))
    assert ok, reason


def test_report_single_flipped_byte_fails_offline_verify(tmp_path):
    log = tmp_path / "p.log"
    make_log(log, 8)
    report_path = str(tmp_path / "r.json")
    run_audit(str(log), report_path, quantum=4)
    blob = bytearray(open(report_path, "rb").read())
    # flip one byte in several structurally different places
    for pos in (blob.find(b'"verified"') + 12,
                blob.find(b'"digest"') + 12,
                blob.find(b'"public_key"') + 16):
        tampered = bytearray(blob)
        tampered[pos] = tampered[pos] ^ 0x01 or 0x31
        bad_path = str(tmp_path / "bad.json")
        open(bad_path, "wb").write(bytes(tampered))
        ok, reason, _ = verify_report_file(bad_path)
        assert not ok, f"tamper at {pos} went unnoticed"


def test_wrong_log_for_cursor_refused(tmp_path):
    log1, log2 = tmp_path / "one.log", tmp_path / "two.log"
    make_log(log1, 12)
    make_log(log2, 12)
    report = str(tmp_path / "r.json")
    assert run_audit(str(log1), report, quantum=4, max_batches=1) is None
    with pytest.raises(ValueError, match="cursor belongs to"):
        run_audit(str(log2), report, quantum=4)


# --- signatures --------------------------------------------------------------


def test_schnorr_sign_verify_roundtrip(tmp_path):
    key = audit_sign.generate_key()
    pub = audit_sign.public_key(key)
    msg = b"the audit transcript digest"
    r, s = audit_sign.sign(key, msg)
    assert audit_sign.verify(pub, msg, r, s)
    assert not audit_sign.verify(pub, b"another message", r, s)
    other = audit_sign.generate_key()
    assert not audit_sign.verify(audit_sign.public_key(other), msg, r, s)
    # deterministic: same (key, message) -> same signature bytes
    assert audit_sign.sign(key, msg) == (r, s)
    # malformed inputs answer False, never raise
    assert not audit_sign.verify(b"\x00" * 32, msg, r, s)
    assert not audit_sign.verify(pub, msg, b"junk", s)
    assert not audit_sign.verify(pub, msg, r, b"short")


def test_key_file_minted_0600_and_reloaded(tmp_path):
    path = tmp_path / "audit.key"
    k1 = audit_sign.load_or_create_key(str(path))
    assert oct(path.stat().st_mode & 0o777) == "0o600"
    k2 = audit_sign.load_or_create_key(str(path))
    assert k1 == k2
    path.write_text("not hex")
    with pytest.raises(ValueError, match="not hex"):
        audit_sign.load_or_create_key(str(path))


# --- fold-state invariants ---------------------------------------------------


def test_audit_state_cursor_roundtrip(tmp_path):
    st = AuditState()
    st.note({"seq": 1, "type": "proof"}, b"V")
    st.note({"seq": 2, "type": "proof"}, b"R", mismatch=True)
    st.note({"seq": 3, "type": "junk"}, b"S")
    st.offset = 123
    cur = st.to_cursor("/var/log/proofs.log")
    back = AuditState.from_cursor(cur, "/elsewhere/proofs.log")
    assert back.chain == st.chain
    assert back.records == 3 and back.audited == 2
    assert back.mismatched == 1 and back.skipped == 1
    assert back.prev_seq == 3 and back.first_seq == 1
    with pytest.raises(ValueError, match="cursor belongs to"):
        AuditState.from_cursor(cur, "/var/log/other.log")


# --- service-side trail ------------------------------------------------------


def test_service_appends_records_on_all_verify_paths(tmp_path):
    """Unary VerifyProof, VerifyProofBatch, and VerifyProofStream all
    append (statement, challenge, proof, verdict) records; the bulk
    pipeline then re-verifies the trail to an all-clean report."""
    from cpzk_tpu.client import AuthClient
    from cpzk_tpu.protocol.batch import CpuBackend
    from cpzk_tpu.server import RateLimiter, ServerState
    from cpzk_tpu.server.batching import DynamicBatcher
    from cpzk_tpu.server.service import serve

    log_path = tmp_path / "service.log"

    async def main():
        rng = SecureRng()
        params = Parameters.new()
        provers = [
            Prover(params, Witness(Ristretto255.random_scalar(rng)))
            for _ in range(6)
        ]
        eb = Ristretto255.element_to_bytes
        backend = CpuBackend()
        batcher = DynamicBatcher(backend, max_batch=64, window_ms=1.0)
        audit_log = ProofLogWriter(str(log_path))
        server, port = await serve(
            ServerState(), RateLimiter(10**9, 10**9), port=0,
            backend=backend, batcher=batcher, audit_log=audit_log,
        )
        try:
            async with AuthClient(f"127.0.0.1:{port}") as client:
                for i, p in enumerate(provers):
                    r = await client.register(
                        f"u{i}", eb(p.statement.y1), eb(p.statement.y2))
                    assert r.success

                async def login_args(i):
                    ch = await client.create_challenge(f"u{i}")
                    cid = bytes(ch.challenge_id)
                    t = Transcript()
                    t.append_context(cid)
                    return cid, provers[i].prove_with_transcript(
                        rng, t).to_bytes()

                # unary (1 record)
                cid, wire = await login_args(0)
                assert (await client.verify_proof("u0", cid, wire)).success
                # unary failure (1 record, verdict 0) — bad proof byte
                cid, wire = await login_args(1)
                bad = wire[:-1] + bytes([wire[-1] ^ 1])
                import grpc

                with pytest.raises(grpc.aio.AioRpcError):
                    await client.verify_proof("u1", cid, bad)
                # batch (2 records)
                pairs = [await login_args(i) for i in (2, 3)]
                resp = await client.verify_proof_batch(
                    ["u2", "u3"], [p[0] for p in pairs],
                    [p[1] for p in pairs])
                assert all(r.success for r in resp.results)
                # stream (2 records)
                entries = []
                for i in (4, 5):
                    cid, wire = await login_args(i)
                    entries.append((f"u{i}", cid, wire))
                oks = [
                    v.ok async for v in client.verify_proof_stream(entries)
                ]
                assert oks == [True, True]
        finally:
            await batcher.stop()
            audit_log.close()
            await server.stop(None)

    run(main())
    records, valid, total = read_log(str(log_path))
    assert len(records) == 6
    assert sum(r["v"] for r in records) == 5
    # the trail replays clean: recorded verdicts match re-verification
    report = run_audit(
        str(log_path), str(log_path) + ".report.json", quantum=4)
    assert report["totals"]["mismatched"] == 0
    assert report["totals"]["verified"] == 5
    assert report["totals"]["rejected"] == 1


# --- SIGKILL resume (real process) ------------------------------------------


@pytest.mark.slow
def test_pipeline_sigkill_resume_byte_exact(tmp_path):
    """Kill -9 the pipeline mid-run; the rerun's signed report is
    byte-identical to an uninterrupted run (CI audit-smoke twin)."""
    log = tmp_path / "p.log"
    make_log(log, 400, reject_every=11)
    key = str(tmp_path / "k.key")
    ref = str(tmp_path / "ref.json")
    assert run_audit(str(log), ref, key_path=key, quantum=50) is not None

    out = str(tmp_path / "killed.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "cpzk_tpu.audit", "run",
         "--log", str(log), "--report", out, "--key", key,
         "--quantum", "50", "--quiet"],
        cwd=str(ROOT), env=env,
    )
    # wait for the first checkpoint, then SIGKILL mid-run
    deadline = time.monotonic() + 60
    cursor = out + ".cursor"
    while time.monotonic() < deadline and proc.poll() is None:
        if os.path.exists(cursor):
            break
        time.sleep(0.02)
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=30)
    # resume (fresh process) and compare byte-for-byte
    done = subprocess.run(
        [sys.executable, "-m", "cpzk_tpu.audit", "run",
         "--log", str(log), "--report", out, "--key", key,
         "--quantum", "50", "--quiet"],
        cwd=str(ROOT), env=env, capture_output=True, timeout=180,
    )
    assert done.returncode == 0, done.stderr
    assert open(out).read() == open(ref).read()


# --- config ------------------------------------------------------------------


def test_audit_config_layering_and_validation(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    cfg = ServerConfig.from_env()
    assert cfg.audit.enabled is False
    assert cfg.audit.fsync == "off"

    (tmp_path / "server.toml").write_text(
        '[audit]\nenabled = true\nlog_path = "proofs.log"\n'
        'fsync = "interval"\n'
    )
    monkeypatch.setenv("SERVER_CONFIG_PATH", str(tmp_path / "server.toml"))
    cfg = ServerConfig.from_env()
    assert cfg.audit.enabled is True
    assert cfg.audit.log_path == "proofs.log"
    assert cfg.audit.fsync == "interval"
    cfg.validate()
    monkeypatch.setenv("SERVER_AUDIT_FSYNC", "ALWAYS")
    monkeypatch.setenv("SERVER_AUDIT_FSYNC_INTERVAL_MS", "77")
    monkeypatch.setenv("SERVER_AUDIT_LOG_PATH", "/tmp/other.log")
    cfg = ServerConfig.from_env()
    assert cfg.audit.fsync == "always"
    assert cfg.audit.fsync_interval_ms == 77.0
    assert cfg.audit.log_path == "/tmp/other.log"

    bad = ServerConfig()
    bad.audit.enabled = True  # without a log_path
    with pytest.raises(ValueError, match="requires log_path"):
        bad.validate()
    bad = ServerConfig()
    bad.audit.fsync = "sometimes"
    with pytest.raises(ValueError, match="audit.fsync"):
        bad.validate()
    bad = ServerConfig()
    bad.audit.fsync_interval_ms = 0
    with pytest.raises(ValueError, match="fsync_interval_ms"):
        bad.validate()
    # stream knobs ride [tpu]
    bad = ServerConfig()
    bad.tpu.stream_window = 0
    with pytest.raises(ValueError, match="stream_window"):
        bad.validate()
    bad = ServerConfig()
    bad.tpu.stream_entry_deadline_ms = -1
    with pytest.raises(ValueError, match="stream_entry_deadline_ms"):
        bad.validate()


def test_audit_config_keys_documented():
    """CI drift guard (pattern from test_durability.py): every [audit]
    knob ships in the TOML example, the .env example, and the
    operations-doc knob inventory."""
    keys = [f.name for f in dataclasses.fields(AuditSettings)]
    assert keys

    toml_text = (ROOT / "config" / "server.toml.example").read_text()
    m = re.search(r"^\[audit\]$", toml_text, re.M)
    assert m, "[audit] section missing from config/server.toml.example"
    section = toml_text[m.end():].split("\n[", 1)[0]
    env_text = (ROOT / ".env.example").read_text()
    docs = (ROOT / "docs" / "operations.md").read_text()
    for key in keys:
        assert re.search(rf"^{key}\s*=", section, re.M), (
            f"[audit] key {key!r} missing from config/server.toml.example"
        )
        assert f"SERVER_AUDIT_{key.upper()}" in env_text, (
            f"SERVER_AUDIT_{key.upper()} missing from .env.example"
        )
        assert f"`audit.{key}`" in docs, (
            f"`audit.{key}` missing from the docs/operations.md "
            "knob inventory"
        )
    # the streaming knobs live in [tpu]; guard them too
    for key in ("stream_window", "stream_entry_deadline_ms"):
        assert f"`tpu.{key}`" in docs, (
            f"`tpu.{key}` missing from the docs/operations.md knob "
            "inventory"
        )


def test_cli_generate_run_verify(tmp_path, monkeypatch):
    """The CLI surface end to end in-process: generate -> run -> tamper
    -> verify-report exit codes."""
    from cpzk_tpu.audit.__main__ import main as audit_main

    log = str(tmp_path / "g.log")
    rc = audit_main(["generate", "--n", "30", "--out", log,
                     "--users", "3", "--reject-frac", "0.2"])
    assert rc == 0
    report = str(tmp_path / "g.json")
    rc = audit_main(["run", "--log", log, "--report", report,
                     "--quantum", "8", "--quiet"])
    assert rc == 0  # rejects recorded as rejects are not mismatches
    assert audit_main(["verify-report", "--report", report]) == 0
    blob = open(report).read().replace('"mismatched":0', '"mismatched":1')
    open(report, "w").write(blob)
    assert audit_main(["verify-report", "--report", report]) == 1
