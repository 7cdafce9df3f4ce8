"""The plain reference against published vectors and the program, and its
control reading not correct."""

import hashlib
import random

import numpy as np

import reference as R


def _sha3_256(msg: bytes) -> bytes:
    rate = 136
    st = np.zeros((1, 200), dtype=np.uint8)
    m = bytearray(msg) + b"\x06" + bytes((-len(msg) - 1) % rate)
    m[-1] |= 0x80
    for i in range(0, len(m), rate):
        st[0, :rate] ^= np.frombuffer(bytes(m[i:i + rate]), dtype=np.uint8)
        R.keccak_f1600(st)
    return st[0, :32].tobytes()


def test_keccak_matches_hashlib_sha3():
    rnd = random.Random(1)
    for n in (0, 1, 135, 136, 137, 400):
        msg = rnd.randbytes(n)
        assert _sha3_256(msg) == hashlib.sha3_256(msg).digest()


def test_merlin_published_vector():
    # merlin crate, transcript.rs test_simple_transcript
    t = R.Merlin(1, b"test protocol")
    t.append(b"some label", b"some data")
    assert t.challenge_bytes(b"challenge", 32)[0].tobytes().hex() == (
        "d5a21972d0d5fe320c0d263fac7fffb8145aa640af6e9bca177c03c7efcf0615")


def _proofs(n, bad_every=7):
    import cpzk_tpu as zkp
    from cpzk_tpu.core.ristretto import Ristretto255

    rng = zkp.SecureRng()
    params = zkp.Parameters.new()
    provers = [zkp.Prover(params, zkp.Witness(Ristretto255.random_scalar(rng)))
               for _ in range(5)]
    eb = Ristretto255.element_to_bytes
    rows, expect = [], []
    for i in range(n):
        p = provers[i % 5]
        bad = i % bad_every == 3
        ctx = rng.fill_bytes(32 if i % 2 else 16)
        t = zkp.Transcript()
        t.append_context(ctx)
        wire = (provers[(i + 1) % 5] if bad else p).prove_with_transcript(rng, t).to_bytes()
        if i % 11 == 5:                      # a wire that does not parse
            wire = wire[:-1]
            bad = True
        rows.append(R.Row(eb(p.statement.y1), eb(p.statement.y2), ctx, wire))
        expect.append(not bad)
    return rows, expect, params


def test_generators_and_verdicts_agree_with_the_program():
    from cpzk_tpu.core.ristretto import Ristretto255

    rows, expect, params = _proofs(120)
    ref = R.Reference()
    assert ref.g == Ristretto255.element_to_bytes(params.generator_g)
    assert ref.h == Ristretto255.element_to_bytes(params.generator_h)
    assert ref.verdicts(rows) == expect
    assert R.verdicts(rows * 40, workers=2) == expect * 40


def test_the_control_reads_not_correct():
    rows, expect, _ = _proofs(120)
    control = R.Reference().verdicts(rows, control=True)
    wrong = sum(a != b for a, b in zip(control, expect))
    assert wrong > 0          # the check's limit is 0
