"""The audit replay on a mesh of 4 of the host's virtual devices, as the
CLI runs it on a 4-chip host (``--backend tpu --mesh-devices 0``): the
sharded Pippenger check in the first quantum of a pass and the sharded
per-row checks in every quantum (each holds a reject, so the combined
check's gate stays closed after the first), verdicts and report equal
to the host oracle's, the sharded
programs compiled once per process, and the ``mesh.*`` spans and
counters."""

import contextlib
import random
import threading
import time

import pytest

from cpzk_tpu import Parameters, Prover, SecureRng, Transcript, Witness
from cpzk_tpu.audit import run_audit
from cpzk_tpu.audit.log import ProofLogWriter, proof_record
from cpzk_tpu.core.ristretto import Ristretto255
from cpzk_tpu.observability import get_flight_recorder, get_tracer, tracing
from cpzk_tpu.ops import backend as backend_mod
from cpzk_tpu.parallel import mesh as mesh_mod
from cpzk_tpu.server import metrics

DEVICES = 4
RECORDS = 32
QUANTUM = 8
SEED = 2**31 + 26
MESH_SPANS = ("mesh.digits", "mesh.msm", "mesh.each")


def _write_log(path: str) -> None:
    """RECORDS proof records over 4 statements: one proved with a wrong
    secret in every quantum, and some logged with a lying verdict."""
    pick = random.Random(SEED)
    rng = SecureRng()
    params = Parameters.new()
    provers = [Prover(params, Witness(Ristretto255.random_scalar(rng)))
               for _ in range(4)]
    eb = Ristretto255.element_to_bytes
    wrong = {lo + pick.randrange(QUANTUM) for lo in range(0, RECORDS, QUANTUM)}
    lie = set(pick.sample(range(RECORDS), 3))
    records = []
    for i in range(RECORDS):
        owner = provers[i % 4]
        signer = provers[(i + 1) % 4] if i in wrong else owner
        ctx = rng.fill_bytes(32)
        t = Transcript()
        t.append_context(ctx)
        wire = signer.prove_with_transcript(rng, t).to_bytes()
        records.append(proof_record(
            f"u{i % 4}", eb(owner.statement.y1), eb(owner.statement.y2),
            ctx, wire, (i not in wrong) != (i in lie)))
    writer = ProofLogWriter(path, fsync="off")
    writer.append_proofs(records)
    writer.close()


def _counter(name: str, label: str, value: str) -> float:
    return metrics.read(name, labels={label: value})


def _replay(log: str, out: str, **kw) -> dict:
    """One ``run_audit`` call: its report, per-quantum counts, trace,
    flight records, profiler annotations and counter deltas."""
    get_tracer().clear()
    get_flight_recorder().clear()
    annotations = []  # (thread, name, enter, exit)

    @contextlib.contextmanager
    def record(name):
        t0 = time.monotonic()
        yield
        annotations.append((threading.get_ident(), name, t0, time.monotonic()))

    counters = [("mesh.compiles", "when", w) for w in ("prewarm", "serving")]
    counters += [("mesh.lanes", "kind", k) for k in ("term", "pad")]
    before = {c: _counter(*c) for c in counters}
    counts = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tracing, "_trace_annotation", record)
        report = run_audit(
            log, out, key_path=out + ".key", quantum=QUANTUM, resume=False,
            progress=lambda s: counts.append(
                (s.verified, s.rejected, s.mismatched)), **kw)
    (trace,) = [t for t in get_tracer().completed() if t.name == "audit.run"]
    return {"report": report, "counts": counts, "trace": trace,
            "flights": get_flight_recorder().snapshot(),
            "annotations": annotations,
            "delta": {c[2]: _counter(*c) - before[c] for c in counters}}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The host oracle's replay, then two mesh replays in one process,
    from no compiled sharded program (a fresh process's state)."""
    import jax

    if jax.device_count() < DEVICES:
        pytest.skip(f"needs {DEVICES} devices")
    d = tmp_path_factory.mktemp("audit-mesh")
    log = str(d / "proofs.log")
    _write_log(log)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mesh_mod, "_EXES", {})
        mp.setattr(backend_mod, "_JIT_SEEN", set())
        out = {"cpu": _replay(log, str(d / "cpu.json"))}
        for k in ("first", "second"):
            out[k] = _replay(log, str(d / f"{k}.json"), backend="tpu",
                             mesh_devices=DEVICES)
        out["prewarm"] = backend_mod.TpuBackend(
            mesh_devices=DEVICES).prewarm([QUANTUM])
    get_tracer().clear()
    get_flight_recorder().clear()
    return out


def test_mesh_replay_matches_the_host_oracle(runs):
    cpu = runs["cpu"]
    quanta = RECORDS // QUANTUM
    assert len(cpu["counts"]) == quanta
    assert cpu["report"]["totals"]["rejected"] >= quanta  # a reject a quantum
    assert cpu["report"]["totals"]["mismatched"] > 0      # the log lies
    for k in ("first", "second"):
        mesh = runs[k]
        assert mesh["counts"] == cpu["counts"], k
        assert mesh["report"]["digest"] == cpu["report"]["digest"], k
        assert mesh["report"]["totals"] == cpu["report"]["totals"], k
        # the first quantum took the sharded combined check and its
        # fallback; its reject closed the gate, so the rest went per-row
        assert [f.combined for f in mesh["flights"]] == (
            [False] + [None] * (quanta - 1))


def test_second_replay_compiles_no_sharded_program(runs):
    first, second = runs["first"], runs["second"]
    # the first run compiled the slice MSM, its reduction and verify_each
    # while serving (nothing prewarmed them)
    assert first["delta"]["serving"] == 3
    assert sum(f.jit_misses for f in first["flights"]) == 3
    # a new backend in the same process finds them all
    assert second["delta"]["serving"] == second["delta"]["prewarm"] == 0
    assert all(f.jit_misses == 0 and f.jit_hits > 0 for f in second["flights"])
    assert not [s for s in second["trace"].spans if s.name == "compile"]
    # and so does a new backend's prewarm
    assert runs["prewarm"] == []


def test_mesh_spans_and_lanes(runs):
    mesh = runs["second"]
    spans = mesh["trace"].spans
    dispatch = [s for s in spans if s.name == "device_dispatch"]
    quanta = RECORDS // QUANTUM
    assert len(dispatch) == quanta
    # the combined check (digits, MSM) ran in the first quantum only
    runs_in = {"mesh.digits": 1, "mesh.msm": 1, "mesh.each": quanta}
    for name in MESH_SPANS:
        mine = [s for s in spans if s.name == name]
        assert len(mine) == runs_in[name], name
        # each sits under its quantum's device_dispatch
        for s in mine:
            assert any(o.start <= s.start and s.start + s.duration_s
                       <= o.start + o.duration_s + 1e-6 for o in dispatch), name

    # the lanes the mesh programs took: 4q+2 MSM terms once and q rows a
    # quantum, padded as the slice programs pad them
    _, m_pad = backend_mod._msm_shape(QUANTUM)
    terms = 4 * QUANTUM + 2
    msm_to = mesh_mod._mesh_pad(DEVICES, m_pad)[1]
    each_to = mesh_mod._mesh_pad(DEVICES, backend_mod._pad_lanes(QUANTUM))[1]
    assert mesh["delta"]["term"] == terms + quanta * QUANTUM
    assert mesh["delta"]["pad"] == (msm_to - terms
                                    + quanta * (each_to - QUANTUM))
    assert mesh["delta"]["pad"] > 0

    # device_dispatch is annotated and the mesh stages inside it are not,
    # so no annotation encloses or overlaps another on a thread
    annotated = {name for _, name, _, _ in mesh["annotations"]}
    assert "device_dispatch" in annotated
    assert not set(MESH_SPANS) & annotated
    for thread in {a[0] for a in mesh["annotations"]}:
        marks = sorted(a[2:] for a in mesh["annotations"] if a[0] == thread)
        for (_, end), (start, _) in zip(marks, marks[1:]):
            assert start >= end, "nested or overlapping cpzk.* annotations"
