"""Driver for the offline proof-log audit: ``cpzk_tpu.audit.pipeline.run_audit``
(what ``python -m cpzk_tpu.audit run`` calls) in this process, over a log
the mix generates from the seed, replayed in whole passes.

Set-up: the device check, the compile cache, the log (a seeded copy of
``audit generate``, traffic.py), the prewarm of the configuration's
quantum, and one warm-up quantum through ``run_audit`` itself.  The window
then runs passes back to back; it closes at the first quantum that
settles at or after ``--seconds``, so the rate is whole quanta over the
time they took.  The pass that straddles the close runs to its end and is
checked like the others.

Correctness: the plain reference (reference.py) verifies every record of
the log once; each quantum's counts (verified, rejected, mismatched) and
each pass's outcome digest (the report's SHA-256 chain over record and
outcome) must equal the reference's.
"""

from __future__ import annotations

import hashlib
import json
import os
import struct
import time
import zlib

import harness
import reference
import trace_reduce
import traffic
from reference import Row


def _frames(path: str) -> list[dict]:
    """The log's records, read with nothing of the program: a big-endian
    (length, crc32) header before each compact-JSON payload."""
    with open(path, "rb") as f:
        buf = f.read()
    out = []
    off = 0
    while off + 8 <= len(buf):
        n, crc = struct.unpack_from(">II", buf, off)
        payload = buf[off + 8:off + 8 + n]
        if len(payload) != n or zlib.crc32(payload) != crc:
            break
        out.append(json.loads(payload))
        off += 8 + n
    return out


def _expected(records: list[dict], verdicts: list[bool], quantum: int):
    """Per quantum (verified, rejected, mismatched), and the pass's chain."""
    counts = []
    chain = bytes(32)
    for lo in range(0, len(records), quantum):
        v = r = m = 0
        for rec, ok in zip(records[lo:lo + quantum], verdicts[lo:lo + quantum]):
            v += ok
            r += not ok
            m += bool(rec["v"]) != ok
            h = hashlib.sha256(chain)
            h.update(json.dumps(rec, separators=(",", ":"), sort_keys=True).encode())
            h.update(b"V" if ok else b"R")
            chain = h.digest()
        counts.append((v, r, m))
    return counts, chain.hex()


def _write_log(path: str, records: list[dict]) -> None:
    from cpzk_tpu.audit.log import ProofLogWriter

    writer = ProofLogWriter(path, fsync="off")
    for lo in range(0, len(records), 1024):
        writer.append_proofs(records[lo:lo + 1024])
    writer.close()


def run(r: harness.Run) -> harness.Outcome:
    import faults

    with faults.planted(r.fault):
        return _run(r)


def _setup(r: harness.Run):
    """The device check, the native core, the compile cache and the prewarm:
    (device, run_audit keywords)."""
    import jax

    from cpzk_tpu import jaxrt
    from cpzk_tpu.ops.backend import prewarm_executables

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    harness.check_device(r, device)
    cfg = r.config["audit"]
    if int(cfg["mesh_devices"]) != int(r.workload["chips"]):
        # 0 would mean every visible chip: the cell runs the shape it names
        raise ValueError(f"audit mesh_devices {cfg['mesh_devices']} is not the "
                         f"cell's {r.workload['chips']} chips")
    built = harness.build_native()
    jaxrt.enable_compile_cache()
    quantum = int(r.sizes.get("quantum", cfg["quantum"]))
    t = time.monotonic()
    warmed = prewarm_executables([quantum])
    r.note(f"prewarm: {len(warmed)} programs compiled in "
           f"{time.monotonic() - t:.1f}s ({', '.join(warmed) or 'all cached'}); "
           f"native core make {built:.1f}s")
    return device, dict(quantum=quantum, backend=cfg["backend"],
                        mesh_devices=int(cfg["mesh_devices"]),
                        lanes=int(cfg["lanes"]), resume=False)


def _log(r: harness.Run, seed: int) -> str:
    """The seed's proof log, written where the pipeline reads it."""
    mix = dict(r.mix, **{k: v for k, v in r.sizes.items() if k in r.mix})
    t = time.monotonic()
    records, wrong, lie = traffic.proof_log(mix, seed)
    path = os.path.join(r.work_dir, f"proofs-{seed}.log")
    _write_log(path, records)
    r.note(f"log: {len(records)} records ({len(wrong)} wrong-secret, {len(lie)} "
           f"lying verdicts) in {time.monotonic() - t:.1f}s")
    return path


# a traced run traces the window's first quanta up to this long: a
# trace of the whole window is hundreds of MB, and saving and reducing it
# would take the run past its time limit (PR 22)
TRACE_S = 5.0


def _replay(r: harness.Run, log: str, kw: dict, seconds: float, trace: bool):
    """Whole passes over ``log`` until a quantum settles at or after
    ``seconds``: (passes, reports, window).  With ``trace`` the profiler
    runs from the window's start to the first quantum that settles at or
    after ``TRACE_S`` (or the window's close, if sooner), inside a
    ``trace_reduce.WINDOW`` annotation that bounds it in the trace's own
    clock; ``window["traced"]`` counts the proofs in it."""
    import jax

    from cpzk_tpu.audit.pipeline import run_audit

    report_dir = os.path.join(r.work_dir, f"reports-{os.path.basename(log)}")
    os.makedirs(report_dir)
    passes = []   # per pass, per quantum: (settled at, verified, rejected, mismatched)
    window = {"end": None, "settled": 0}
    if trace:
        jax.profiler.start_trace(os.path.join(r.work_dir, "trace"),
                                 profiler_options=harness.profile_options())
        window["mark"] = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        window["mark"].__enter__()
        window["started"] = time.time_ns()
    start = time.monotonic()

    def progress(state) -> None:
        now = time.monotonic()
        p = passes[-1]
        p.append((now, state.verified, state.rejected, state.mismatched))
        if window["end"] is None:
            prev = p[-2] if len(p) > 1 else (0, 0, 0, 0)
            window["settled"] += (p[-1][1] + p[-1][2]) - (prev[1] + prev[2])
            if (trace and "stopping" not in window
                    and now - start >= min(TRACE_S, seconds)):
                window["mark"].__exit__(None, None, None)
                window["stopping"] = time.time_ns()
                window["traced"] = window["settled"]
                jax.profiler.stop_trace()
            if now - start >= seconds:
                window["end"] = now

    reports = []
    while window["end"] is None:
        passes.append([])
        reports.append(run_audit(
            log, os.path.join(report_dir, f"pass{len(passes)}.json"),
            progress=progress, **kw))
    window["s"] = window["end"] - start
    r.note(f"window: {window['settled']} proofs settled in {window['s']:.3f}s "
           f"({len(passes)} passes, the last run to its end after the close)")
    return passes, reports, window


def _checks(r, log: str, passes, reports, quantum: int, control: bool) -> list:
    """Each quantum's counts and each pass's digest against the reference
    over the log; with ``control`` the control's outcomes stand in the
    program's place."""
    t = time.monotonic()
    scanned = _frames(log)
    rows = [Row(bytes.fromhex(x["y1"]), bytes.fromhex(x["y2"]),
                bytes.fromhex(x["ctx"]), bytes.fromhex(x["p"])) for x in scanned]
    ref = reference.verdicts(rows)
    counts, digest = _expected(scanned, ref, quantum)
    r.note(f"reference: {len(rows)} records in {time.monotonic() - t:.1f}s, "
           f"{ref.count(False)} refused")
    got = [[(v, rj, m) for _, v, rj, m in p] for p in passes]
    digests = [rep["digest"] if rep else None for rep in reports]
    if control:
        c_counts, c_digest = _expected(
            scanned, reference.verdicts(rows, control=True), quantum)
        acc, cum = [0, 0, 0], []
        for q in c_counts:
            acc = [a + b for a, b in zip(acc, q)]
            cum.append(tuple(acc))
        got = [cum[:len(p)] for p in passes]
        digests = [c_digest] * len(passes)
    off = 0
    for p in got:
        prev = (0, 0, 0)
        for cur, want in zip(p, counts):
            off += sum(abs((a - b) - w) for a, b, w in zip(cur, prev, want))
            prev = cur
        off += abs(len(p) - len(counts)) * quantum
    wrong = sum(d != digest for d in digests)
    return [("quantum_counts_off", off, 0), ("pass_digests_wrong", wrong, 0)]


def _run(r: harness.Run) -> harness.Outcome:
    from cpzk_tpu import jaxrt
    from cpzk_tpu.audit.pipeline import run_audit

    device, kw = _setup(r)
    log = _log(r, r.seed)
    # warm-up: one quantum through the same entry (checkpointed, then dropped)
    run_audit(log, os.path.join(r.work_dir, "warm.json"), max_batches=1, **kw)
    setup_s = time.monotonic() - r.t0
    passes, reports, window = _replay(r, log, kw, r.seconds, r.trace)
    mem = [m["peak_bytes_in_use"] for m in jaxrt.memory()]
    device["memory_peak_bytes"] = max(mem, default=0)
    reduced = None
    if r.trace:
        reduced = harness.reduce_trace(os.path.join(r.work_dir, "trace"),
                                       (window["started"], window["stopping"]))
    checks = _checks(r, log, passes, reports, kw["quantum"], False)
    device, breakdown = harness.trace_device(device, reduced)
    return harness.Outcome(
        device=device, attempted=sum(p[-1][1] + p[-1][2] for p in passes if p),
        failed=0, checks=checks,
        artifacts={"setup_s": setup_s, "window_s": window["s"],
                   "settled": window["settled"], "trace": reduced,
                   "traced": window.get("traced")},
        breakdown=breakdown)


def readings(r: harness.Run, seeds: list[int]) -> list[dict]:
    """control.py: one process; per seed its own log and one pass; the
    program's checks and the control's on each."""
    from cpzk_tpu.audit.pipeline import run_audit

    device, kw = _setup(r)
    out = []
    for k, seed in enumerate(seeds):
        log = _log(r, seed)
        if k == 0:
            run_audit(log, os.path.join(r.work_dir, "warm.json"),
                      max_batches=1, **kw)
        passes, reports, _ = _replay(r, log, kw, r.seconds, False)
        out.append({"seed": seed, "device": device,
                    "program": _checks(r, log, passes, reports, kw["quantum"],
                                       False),
                    "control": _checks(r, log, passes, reports, kw["quantum"],
                                       True)})
    return out
