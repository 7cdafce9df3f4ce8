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


def _combined(backend, entries):
    """The backend's combined RLC verdict alone.  ``_run`` cannot see a
    misplaced correction lane on a valid batch (the per-row fallback
    still accepts every row), and it routes one-row batches past the
    backend entirely."""
    bv = BatchVerifier(backend=backend)
    for p, st, pr in entries:
        bv.add(p, st, pr)
    rng = SecureRng()
    return backend.verify_combined(
        bv.prepare_rows(rng), Ristretto255.random_scalar(rng))


def _corrupt(entries, bad):
    """Swap row ``bad``'s statement for one of a fresh witness."""
    params = entries[bad][0]
    wrong = Statement.from_witness(
        params, Witness(Ristretto255.random_scalar(SecureRng())))
    entries[bad] = (params, wrong, entries[bad][2])
    return entries


# With LANE_CHUNK 8 the correction row sits at lane n of _pad_lanes(n + 1):
# in a 2-lane pad (1), at the end of chunk 1 (7), at the start / middle /
# end of chunk 2 (8, 11, 15), and at the start / middle / end of chunk 3
# (16, 20, 23).
@pytest.mark.parametrize("n", [1, 7, 8, 11, 15, 16, 20, 23])
def test_chunked_rowcombined_accepts_valid_batch(tiny_chunks, n):
    entries = make_entries(n)
    assert _combined(TpuBackend(), entries) is True
    assert _run(TpuBackend(), entries) == [True] * n


@pytest.mark.parametrize("n, bad", [
    (20, 7), (8, 0), (8, 7), (16, 15), (23, 22),
])
def test_chunked_rowcombined_mixed_matches_oracle(tiny_chunks, n, bad):
    entries = _corrupt(make_entries(n), bad)
    assert _combined(TpuBackend(), entries) is False
    expect = _run(CpuBackend(), entries)
    # the combined check fails -> the chunked verify_each fallback decides
    assert _run(TpuBackend(), entries) == expect
    assert expect == [i != bad for i in range(n)]


# With LANE_CHUNK 32 the 4n+2 MSM terms fill _msm_shape(n) slots: 6 and
# 10 (one short chunk), 32 (one full chunk), 34 -> 64 (the two correction
# terms spill past a power of two) and 128 (four full chunks).
@pytest.mark.parametrize("n", [1, 2, 7, 8, 20])
def test_chunked_pippenger_accepts_valid_batch(monkeypatch, n):
    monkeypatch.setattr(backend_mod, "LANE_CHUNK", 32)
    entries = make_entries(n)
    assert _combined(TpuBackend(pippenger_min=1), entries) is True
    assert _run(TpuBackend(pippenger_min=1), entries) == [True] * n


@pytest.mark.parametrize("n", [8, 12])
def test_chunked_pippenger_mixed_matches_oracle(monkeypatch, n):
    monkeypatch.setattr(backend_mod, "LANE_CHUNK", 32)
    entries = _corrupt(make_entries(n), 3)
    assert _combined(TpuBackend(pippenger_min=2), entries) is False
    expect = _run(CpuBackend(), entries)
    assert _run(TpuBackend(pippenger_min=2), entries) == expect
    assert expect == [i != 3 for i in range(n)]


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
