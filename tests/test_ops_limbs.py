"""Differential tests: JAX limb field arithmetic vs the integer-exact host
field (:mod:`cpzk_tpu.core.field`). Runs on the JAX CPU backend (conftest
forces ``JAX_PLATFORMS=cpu`` with a virtual 8-device topology)."""

import secrets

import numpy as np
import pytest

from cpzk_tpu.core import field as hf
from cpzk_tpu.ops import limbs

N = 64  # batch size for randomized differential checks


def rand_fes(n: int) -> list[int]:
    vals = [secrets.randbelow(hf.P) for _ in range(n - 4)]
    # adversarial corners
    vals += [0, 1, hf.P - 1, hf.P - 19]
    return vals


@pytest.fixture(scope="module")
def ab():
    a = rand_fes(N)
    b = rand_fes(N)
    return a, b, limbs.ints_to_limbs(a), limbs.ints_to_limbs(b)


def check(expected: list[int], got) -> None:
    got_ints = limbs.limbs_to_ints(np.asarray(got))
    assert [v % hf.P for v in got_ints] == [v % hf.P for v in expected]


def test_roundtrip_conversions(ab):
    a, _, la, _ = ab
    assert limbs.limbs_to_ints(la) == a
    # single-int path
    assert limbs.limbs_to_int(limbs.int_to_limbs(12345)) == 12345


def test_add_sub_neg(ab):
    a, b, la, lb = ab
    check([hf.fadd(x, y) for x, y in zip(a, b)], limbs.add(la, lb))
    check([hf.fsub(x, y) for x, y in zip(a, b)], limbs.sub(la, lb))
    check([hf.fneg(x) for x in a], limbs.neg(la))


def test_mul_square(ab):
    a, b, la, lb = ab
    check([hf.fmul(x, y) for x, y in zip(a, b)], limbs.mul(la, lb))
    check([hf.fmul(x, x) for x in a], limbs.square(la))


def test_mul_small(ab):
    a, _, la, _ = ab
    check([x * 121 % hf.P for x in a], limbs.mul_small(la, 121))
    check([(-x * 2) % hf.P for x in a], limbs.mul_small(la, -2))


def test_canonical_idempotent_on_large_values():
    vals = [hf.P, hf.P + 1, 2 * hf.P + 5, (1 << 260) - 1, hf.P - 1]
    la = np.stack([limbs.int_to_limbs(v) for v in vals], axis=-1)
    check([v % hf.P for v in vals], limbs.canonical(la))


def test_inv(ab):
    a, _, la, _ = ab
    nz = [x if x != 0 else 1 for x in a]
    lnz = limbs.ints_to_limbs(nz)
    check([hf.finv(x) for x in nz], limbs.inv(lnz))


def test_is_negative_fabs_eq(ab):
    a, b, la, lb = ab
    assert list(np.asarray(limbs.is_negative(la))) == [hf.is_negative(x) for x in a]
    check([hf.fabs(x) for x in a], limbs.fabs(la))
    assert list(np.asarray(limbs.eq(la, la))) == [True] * N
    eq_ab = list(np.asarray(limbs.eq(la, lb)))
    assert eq_ab == [x == y for x, y in zip(a, b)]


def test_sqrt_ratio_m1(ab):
    a, b, la, lb = ab
    ok_host, r_host = zip(*[hf.sqrt_ratio_m1(x, y) for x, y in zip(a, b)])
    ok_dev, r_dev = limbs.sqrt_ratio_m1(la, lb)
    assert list(np.asarray(ok_dev)) == list(ok_host)
    check(list(r_host), r_dev)


def test_sqrt_ratio_corner_cases():
    # (0,0) -> (True, 0); (u!=0, v=0) -> (False, 0)
    u = limbs.ints_to_limbs([0, 5])
    v = limbs.ints_to_limbs([0, 0])
    ok, r = limbs.sqrt_ratio_m1(u, v)
    assert list(np.asarray(ok)) == [True, False]
    check([0, 0], r)


def test_loose_limb_bounds_adversarial():
    """Overflow-safety check for the loose-carry discipline: feed limb
    vectors at the +/-BOUND extremes (valid redundant representations that
    never arise from canonical inputs) through add/sub/mul and compare with
    exact big-int arithmetic."""
    BOUND = 9500
    patterns = [
        np.full(limbs.NLIMBS, BOUND, dtype=np.int32),
        np.full(limbs.NLIMBS, -BOUND, dtype=np.int32),
        np.asarray([BOUND if i % 2 else -BOUND for i in range(limbs.NLIMBS)], dtype=np.int32),
        np.asarray([-BOUND] + [BOUND] * (limbs.NLIMBS - 1), dtype=np.int32),
    ]
    la = np.stack(patterns, axis=-1)  # [20, 4] limb-major
    vals = [limbs.limbs_to_int(p) for p in patterns]
    for out, expect in (
        (limbs.mul(la, la), [v * v for v in vals]),
        (limbs.mul(la, la[:, ::-1].copy()), [v * w for v, w in zip(vals, vals[::-1])]),
        (limbs.add(la, la), [2 * v for v in vals]),
        (limbs.sub(la, la[:, ::-1].copy()), [v - w for v, w in zip(vals, vals[::-1])]),
        (limbs.square(limbs.add(la, la[:, ::-1].copy())), [(v + w) ** 2 for v, w in zip(vals, vals[::-1])]),
    ):
        check([e % hf.P for e in expect], out)

    # loose outputs stay mul-safe: |limb| <= BOUND after every op
    for op_out in (limbs.mul(la, la), limbs.add(la, la), limbs.sub(la, la[:, ::-1].copy())):
        assert int(np.abs(np.asarray(op_out)).max()) <= BOUND


def test_bytes_roundtrip(ab):
    a, _, la, _ = ab
    enc = np.asarray(limbs.to_bytes_le(la))  # [32, n]
    expected = [hf.fe_to_bytes(x) for x in a]
    assert [bytes(enc[:, j].astype(np.uint8).tobytes()) for j in range(N)] == expected
    back = limbs.from_bytes_le(enc)
    check(a, back)


def test_bytes_to_limbs_vectorized(ab):
    a, _, _, _ = ab
    rows = np.stack([np.frombuffer(hf.fe_to_bytes(x), dtype=np.uint8) for x in a])
    check(a, limbs.bytes_to_limbs(rows))


def _mul_operands_random():
    xs = [secrets.randbelow(limbs.P) for _ in range(32)] + [limbs.P - 1, 0, 1]
    ys = [secrets.randbelow(limbs.P) for _ in range(32)] + [limbs.P - 1, limbs.P - 1, 2]
    exp = [x * y % limbs.P for x, y in zip(xs, ys)]
    return limbs.ints_to_limbs(xs), limbs.ints_to_limbs(ys), exp


def _mul_operands_mixed_sign_bound():
    # adversarial max-limb carried-form inputs with MIXED-SIGN halves —
    # the shape that overflowed the removed Karatsuba variant's middle
    # product (review r3): low half +bound, high half -bound
    am = np.concatenate([np.full((10, 3), 9500), np.full((10, 3), -9500)]).astype(np.int32)
    bm = np.concatenate([np.full((10, 3), -9500), np.full((10, 3), 9500)]).astype(np.int32)
    ia, ib = limbs.limbs_to_int(am[:, 0]), limbs.limbs_to_int(bm[:, 0])
    return am, bm, [ia * ib % limbs.P] * 3


@pytest.mark.parametrize("operands", [
    _mul_operands_random, _mul_operands_mixed_sign_bound,
])
def test_mul_variants_bit_exact(operands):
    """The schoolbook mul agrees with the host oracle on random operands
    and on mixed-sign-half loose carried-form inputs at the limb bound."""
    a, b, exp = operands()
    assert limbs.limbs_to_ints(limbs.canonical(limbs.mul(a, b))) == exp
