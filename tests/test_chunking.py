"""Lane-chunked dispatch differentials (the PROFILE.md §7a workaround).

On TPU v5 lite, monolithic device programs past ~33k lanes miscompile:
deterministic wrong MSM output at m>=40,962, an internal XLA error at
49,154, all-zero output buffers at 57,346 (the round-5 on-chip sweep),
and the per-row combined kernel fails its in-kernel check at 65,538
rows.  The backend therefore tiles large batches into ``LANE_CHUNK``-lane
programs and adds partial points (``ops/backend.py``).

These tests force MULTI-chunk execution with a tiny chunk size on the
CPU backend and require bit-identical accept/reject against the host
oracle — the same differential bar as tests/test_tpu_backend.py
(reference semantics: ``src/verifier/batch.rs:171-318``).
"""

import pytest

from cpzk_tpu import BatchVerifier, SecureRng, Statement, Witness
from cpzk_tpu.core.ristretto import Ristretto255
from cpzk_tpu.ops import backend as backend_mod
from cpzk_tpu.ops.backend import TpuBackend, _pad_lanes
from cpzk_tpu.protocol.batch import CpuBackend

from test_tpu_backend import make_entries


@pytest.fixture
def tiny_chunks(monkeypatch):
    monkeypatch.setattr(backend_mod, "LANE_CHUNK", 8)


def _run(backend, entries):
    bv = BatchVerifier(backend=backend)
    for p, st, pr in entries:
        bv.add(p, st, pr)
    return [e is None for e in bv.verify(SecureRng())]


def test_pad_lanes_schedule(tiny_chunks):
    assert _pad_lanes(5) == 8
    assert _pad_lanes(8) == 8
    assert _pad_lanes(9) == 16
    assert _pad_lanes(17) == 24
    assert _pad_lanes(24) == 24


@pytest.mark.parametrize("n, m", [
    (1, 6), (2, 10), (3, 16), (5, 32), (8, 34), (20, 128), (36855, 262144),
])
def test_msm_shape_whole_tiles(n, m):
    """The combined MSM's 4n+2 terms fill 4 * pow2(n) slots unless n is
    a power of two: whole tiles, no near-empty remainder program."""
    c, m_pad = backend_mod._msm_shape(n)
    assert m_pad == m >= 4 * n + 2
    assert c == backend_mod.msm.pick_window(min(m, backend_mod.LANE_CHUNK))


def test_chunked_rowcombined_accepts_valid_batch(tiny_chunks):
    # n+1 = 21 lanes -> 3 chunks of 8 through combined_partial_kernel
    entries = make_entries(20)
    assert _run(TpuBackend(), entries) == [True] * 20


def test_chunked_rowcombined_mixed_matches_oracle(tiny_chunks):
    entries = make_entries(20)
    rng = SecureRng()
    params = entries[7][0]
    wrong = Statement.from_witness(params, Witness(Ristretto255.random_scalar(rng)))
    entries[7] = (params, wrong, entries[7][2])
    expect = _run(CpuBackend(), entries)
    # the combined check fails -> the chunked verify_each fallback decides
    assert _run(TpuBackend(), entries) == expect
    assert expect == [i != 7 for i in range(20)]


def test_chunked_pippenger_accepts_valid_batch(monkeypatch):
    # m = 4*pad_pow2(20)+2 = 130 terms -> 5 chunks of 32 through _msm_partial
    monkeypatch.setattr(backend_mod, "LANE_CHUNK", 32)
    entries = make_entries(20)
    assert _run(TpuBackend(pippenger_min=2), entries) == [True] * 20


def test_chunked_pippenger_mixed_matches_oracle(monkeypatch):
    monkeypatch.setattr(backend_mod, "LANE_CHUNK", 32)
    entries = make_entries(12)
    rng = SecureRng()
    params = entries[3][0]
    wrong = Statement.from_witness(params, Witness(Ristretto255.random_scalar(rng)))
    entries[3] = (params, wrong, entries[3][2])
    expect = _run(CpuBackend(), entries)
    assert _run(TpuBackend(pippenger_min=2), entries) == expect
    assert expect == [i != 3 for i in range(12)]


def test_chunked_pippenger_device_rlc(monkeypatch):
    monkeypatch.setattr(backend_mod, "LANE_CHUNK", 32)
    monkeypatch.setenv("CPZK_DEVICE_RLC", "1")
    entries = make_entries(10)
    assert _run(TpuBackend(pippenger_min=2), entries) == [True] * 10


def test_chunked_rowcombined_device_rlc(tiny_chunks, monkeypatch):
    """Device-RLC windows are built full-width (correction spliced at lane
    n, possibly inside a middle chunk) and then chunk-sliced — the layout
    must survive the tiling."""
    monkeypatch.setenv("CPZK_DEVICE_RLC", "1")
    entries = make_entries(20)  # correction lane lands at 20, chunk 3 of 3
    assert _run(TpuBackend(), entries) == [True] * 20
    entries = make_entries(11)  # correction lane 11 inside chunk 2 of 2
    assert _run(TpuBackend(), entries) == [True] * 11


def test_chunked_batch_prover(tiny_chunks):
    """BatchProver lane-tiles past LANE_CHUNK; the wire bytes must stay
    bit-identical to the host prover's statement computation and verify
    under the standard Verifier."""
    from cpzk_tpu import Parameters, SecureRng, Verifier, Statement, Proof, Transcript
    from cpzk_tpu.core.ristretto import Ristretto255
    from cpzk_tpu.ops.prove import BatchProver

    rng = SecureRng()
    params = Parameters.new()
    bp = BatchProver(params)
    witnesses = [Ristretto255.random_scalar(rng) for _ in range(20)]
    ctxs = [b"chunk-ctx-%02d" % i for i in range(20)]
    statements, proof_wires = bp.prove(witnesses, ctxs, rng)
    for (y1b, y2b), wire, ctx, w in zip(statements, proof_wires, ctxs, witnesses):
        st = Statement(
            Ristretto255.element_from_bytes(y1b),
            Ristretto255.element_from_bytes(y2b),
        )
        expected = Statement.from_witness(params, Witness(w))
        assert (y1b, y2b) == (
            Ristretto255.element_to_bytes(expected.y1),
            Ristretto255.element_to_bytes(expected.y2),
        )
        t = Transcript()
        t.append_context(ctx)
        # raises on failure (verifier/mod.rs:120-139 parity)
        Verifier(params, st).verify_with_transcript(Proof.from_bytes(wire), t)


def test_mesh_chunked_prove(monkeypatch):
    """The sharded prover's over-cap slicing (n > d*LANE_CHUNK) must emit
    wire bytes bit-identical to the single-device prover."""
    from cpzk_tpu import Parameters, SecureRng
    from cpzk_tpu.core.ristretto import Ristretto255
    from cpzk_tpu.ops.prove import BatchProver

    monkeypatch.setattr(backend_mod, "LANE_CHUNK", 4)
    rng = SecureRng()
    params = Parameters.new()
    sharded = BatchProver(params, mesh_devices=0)
    if sharded._sharded is None:
        pytest.skip("no multi-device mesh available")
    single = BatchProver(params)
    witnesses = [Ristretto255.random_scalar(rng) for _ in range(40)]
    # n=40 > step=8*4=32 -> the parts/concatenate branch runs
    assert sharded.statements(witnesses) == single.statements(witnesses)


def test_mesh_chunked_paths(monkeypatch):
    """Sharded mesh paths under the per-device lane cap: the sharded MSM
    (combined) and sharded verify_each both split into mesh-sized slices
    of d * LANE_CHUNK lanes and must stay bit-identical to the oracle."""
    monkeypatch.setattr(backend_mod, "LANE_CHUNK", 4)
    entries = make_entries(40)
    be = TpuBackend(mesh_devices=0)  # the 8-virtual-device CPU mesh
    if be._mesh is None:
        pytest.skip("no multi-device mesh available")
    # combined: m = 4*pad_pow2(40)+2 = 258 terms, step 8*4=32 -> 9 slices
    assert _run(be, entries) == [True] * 40

    rng = SecureRng()
    params = entries[11][0]
    wrong = Statement.from_witness(params, Witness(Ristretto255.random_scalar(rng)))
    entries[11] = (params, wrong, entries[11][2])
    # combined fails -> sharded verify_each (n=40, step 32 -> 2 slices)
    expect = _run(CpuBackend(), entries)
    assert _run(TpuBackend(mesh_devices=0), entries) == expect
    assert expect == [i != 11 for i in range(40)]
