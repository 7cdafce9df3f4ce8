"""Batched GF(2^255 - 19) arithmetic over int32 limb vectors (JAX).

TPU-first design (not a port): the TPU vector unit has no 64-bit integer
lanes, so field elements are represented as ``[20, ...batch]`` int32 arrays
in radix 2^13 ("13x20"): value = sum(limb[i] * 2^(13 i)).  With
|limb| <= 2^13, a schoolbook product limb is a sum of at most 20 terms each
< 2^26, i.e. < 20 * 2^26 < 2^31 — the entire multiply fits int32 lanes with
no widening.  Intermediates may carry *signed* limbs (subtraction is
representation-level negative); the carry chain uses arithmetic shifts, and
wrap-around of the top carry uses 2^260 ≡ 608 (mod p) since 608 = 19 * 2^5.

Layout: the limb axis is the LEADING axis and the batch axes trail.  On TPU
the minor-most axis maps to the 128-wide vector lanes, so a ``[20, n]``
array puts the batch dimension on the lanes (100% occupancy for n >= 128)
instead of wasting 84% of each lane group on a 20-entry limb axis — this
single layout choice is worth ~5x arithmetic throughput over the
batch-major ``[n, 20]`` alternative.

Multiplication uses a pad-flatten-reshape alignment trick to sum the
schoolbook anti-diagonals in O(1) XLA ops (one outer product, one pad, one
reshape, one slice, one reduce) instead of 20 shifted adds — this keeps both
the op count per lane and the XLA graph size (compile time) small.

Every public op returns "carried" form: limbs in a loose symmetric bound
(|limb| <= ~9500), value congruent mod p.  ``canonical`` reduces to the
unique representative < p for encoding and equality.

Reference parity: the field layer of curve25519-dalek under
``src/primitives/ristretto.rs`` (SURVEY.md §2.2) — re-designed for batched
TPU execution; bit-exact against :mod:`cpzk_tpu.core.field` by the
differential tests in ``tests/test_ops_limbs.py``.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

from ..core import field as host_field

NLIMBS = 20
LIMB_BITS = 13
LIMB_MASK = (1 << LIMB_BITS) - 1
NBITS = NLIMBS * LIMB_BITS  # 260
# 2^260 mod p = 19 * 2^5
TOP_FOLD = 19 << (NBITS - 255)

P = host_field.P


# ---------------------------------------------------------------------------
# host-side conversions (numpy; used for test oracles and data marshalling)
# ---------------------------------------------------------------------------

def int_to_limbs(v: int) -> np.ndarray:
    """One integer -> [NLIMBS] int32 (value must be in [0, 2^260))."""
    out = np.empty(NLIMBS, dtype=np.int32)
    for i in range(NLIMBS):
        out[i] = v & LIMB_MASK
        v >>= LIMB_BITS
    if v:
        raise ValueError("value too large for 20x13 limbs")
    return out

def ints_to_limbs(values: list[int]) -> np.ndarray:
    """Batch conversion -> [NLIMBS, n] int32 (limb-major device layout)."""
    blob = b"".join((v % P).to_bytes(33, "little") for v in values)
    raw = np.frombuffer(blob, dtype=np.uint8).reshape(len(values), 33)
    bits = np.unpackbits(raw, axis=1, bitorder="little")[:, :NBITS]
    weights = (1 << np.arange(LIMB_BITS, dtype=np.int32))
    rows = bits.reshape(len(values), NLIMBS, LIMB_BITS).astype(np.int32) @ weights
    return np.ascontiguousarray(rows.T)

def bytes_to_limbs(blob: bytes | np.ndarray) -> np.ndarray:
    """[n, 32] little-endian byte rows -> [NLIMBS, n] int32 limbs.

    Interprets all 256 bits; values >= 2^255 stay un-reduced (carried form
    handles them).  Vectorized — no per-row Python ints.
    """
    raw = np.asarray(blob, dtype=np.uint8).reshape(-1, 32)
    bits = np.unpackbits(raw, axis=1, bitorder="little")
    bits = np.pad(bits, [(0, 0), (0, NBITS - 256)])
    weights = (1 << np.arange(LIMB_BITS, dtype=np.int32))
    rows = bits.reshape(len(raw), NLIMBS, LIMB_BITS).astype(np.int32) @ weights
    return np.ascontiguousarray(rows.T)

def limbs_to_int(limbs) -> int:
    """One [NLIMBS] limb vector -> integer (host, for tests)."""
    arr = np.asarray(limbs, dtype=object).reshape(-1)
    return int(sum(int(arr[i]) << (LIMB_BITS * i) for i in range(NLIMBS)))

def limbs_to_ints(limbs) -> list[int]:
    """[NLIMBS, n] limb array -> list of n integers (host, for tests)."""
    arr = np.asarray(limbs).reshape(NLIMBS, -1)
    return [limbs_to_int(arr[:, j]) for j in range(arr.shape[1])]


def constant(v: int) -> jnp.ndarray:
    """Module-load-time field constant as a [NLIMBS, 1] device array."""
    return jnp.asarray(int_to_limbs(v % P))[:, None]


ZERO = None  # initialized below (after function defs, constants section)


def _align(a: jnp.ndarray, b: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Insert batch axes after the limb axis so [20, 1] constants broadcast
    against arbitrarily-batched [20, ...] operands."""
    if a.ndim < b.ndim:
        a = a.reshape(a.shape[:1] + (1,) * (b.ndim - a.ndim) + a.shape[1:])
    elif b.ndim < a.ndim:
        b = b.reshape(b.shape[:1] + (1,) * (a.ndim - b.ndim) + b.shape[1:])
    return a, b


# ---------------------------------------------------------------------------
# carry / reduction
# ---------------------------------------------------------------------------

def _chain(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential signed carry chain along the limb axis (axis 0).

    Returns (limbs in [0, 2^13), top carry). Arithmetic (floor) shifts make
    this correct for negative limbs: the remainder x - (x>>13 << 13) is
    always in [0, 2^13).
    """
    n = x.shape[0]
    outs = []
    c = jnp.zeros_like(x[0])
    for i in range(n):
        t = x[i] + c
        c = t >> LIMB_BITS
        outs.append(t & LIMB_MASK)
    return jnp.stack(outs, axis=0), c


def _wrap_round(x: jnp.ndarray) -> jnp.ndarray:
    """One carry-save round on a 20-limb vector with modular wrap.

    Splits every limb into (low 13 bits, carry) in parallel and re-adds the
    carries one position up; the carry leaving limb 19 (weight 2^260) wraps
    to limb 0 scaled by 608 = 19 * 2^5.  Five whole-vector ops — no
    sequential chain, which is what keeps the XLA graphs (and compile time)
    small.  Works for signed limbs via arithmetic shifts.
    """
    lo = x & LIMB_MASK
    hi = x >> LIMB_BITS
    shifted = jnp.concatenate([hi[-1:] * TOP_FOLD, hi[:-1]], axis=0)
    return lo + shifted


def _round_widen(x: jnp.ndarray) -> jnp.ndarray:
    """One carry-save round without wrap; output is one limb wider."""
    lo = x & LIMB_MASK
    hi = x >> LIMB_BITS
    pad_cfg = [(0, 0)] * (x.ndim - 1)
    return jnp.pad(lo, [(0, 1)] + pad_cfg) + jnp.pad(hi, [(1, 0)] + pad_cfg)


def carry20(x: jnp.ndarray) -> jnp.ndarray:
    """Normalize a signed [20, ...] vector to |limb| <= ~9500 ("loose"
    carried form; BOUND).  Valid for inputs with |limb| < 2^22.5 — every
    caller in this module stays far inside that."""
    for _ in range(4):
        x = _wrap_round(x)
    return x


def carry_product(x: jnp.ndarray) -> jnp.ndarray:
    """Reduce a [39, ...] schoolbook product (|limb| < 2^30.8) to loose
    carried [20, ...] form.

    Three widening rounds bring product limbs to ~2^13; the 42-limb result
    is folded mod p in two steps (608 = 2^260 mod p per 20-limb block, with
    the top 2-limb block folded into the middle block first), then four wrap
    rounds restore the loose bound.  All bounds are validated by the
    adversarial max-limb tests in tests/test_ops_limbs.py.
    """
    pad_cfg = [(0, 0)] * (x.ndim - 1)
    x = jnp.pad(x, [(0, 3)] + pad_cfg)  # 42 limbs of headroom
    for _ in range(3):
        x = _round_widen(x)[:42]  # widened carries beyond 42 are zero
    c0 = x[:NLIMBS]
    c1 = x[NLIMBS : 2 * NLIMBS]
    c2 = jnp.pad(x[2 * NLIMBS :], [(0, NLIMBS - 2)] + pad_cfg)
    t = c1 + c2 * TOP_FOLD
    t = _wrap_round(_wrap_round(t))  # |t limb| <= 2^13 + 2^9.2
    return carry20(c0 + t * TOP_FOLD)


def _bump(x: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """x with v added at limb 0 (concat-based, no scatter HLO)."""
    return jnp.concatenate([x[:1] + v[None], x[1:]], axis=0)


def canonical(x: jnp.ndarray) -> jnp.ndarray:
    """Unique representative < p, digits in [0, 2^13) (encode/compare).

    The only sequential-carry path left; used by eq / is_negative / byte
    encoding, not by the bulk arithmetic. Two fold rounds make the value
    non-negative for any loose input (including representation-negative
    subtraction results)."""
    x = carry20(x)
    x, c = _chain(x)
    x = _bump(x, c * TOP_FOLD)
    x, c = _chain(x)
    x = _bump(x, c * TOP_FOLD)
    x, _ = _chain(x)
    # fold bits 255..259 (top 5 bits of limb 19): 2^255 ≡ 19
    hi = x[NLIMBS - 1] >> (255 - LIMB_BITS * (NLIMBS - 1))  # >> 8
    x = jnp.concatenate(
        [x[:1] + (hi * 19)[None], x[1 : NLIMBS - 1], (x[NLIMBS - 1] & 0xFF)[None]],
        axis=0,
    )
    x, _ = _chain(x)  # value now < 2^255 + 608
    for _ in range(2):
        x = _cond_sub_p(x)
    return x


_P_LIMBS = None  # set in constants section


def _cond_sub_p(x: jnp.ndarray) -> jnp.ndarray:
    p = _P_LIMBS.reshape((NLIMBS,) + (1,) * (x.ndim - 1))
    y, borrow = _chain(x - p)
    return jnp.where(borrow < 0, x, y)


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    # raw sum <= 2*BOUND; one wrap round restores the loose bound
    a, b = _align(a, b)
    return _wrap_round(a + b)

def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    a, b = _align(a, b)
    return _wrap_round(a - b)

def neg(a: jnp.ndarray) -> jnp.ndarray:
    # |-limb| <= BOUND already: mul-safe without a round
    return -a


#: Multiplication is the schoolbook product below.  A one-level Karatsuba
#: variant was built and REMOVED: with the loose carried-form bound
#: (|limb| <= ~9500) the subtractive middle product's anti-diagonal sums
#: reach 10*(2*9500)^2 = 3.61e9 > int32, and the carry passes needed to
#: restore headroom cost more vector ops than the 25% multiply saving
#: buys (exact bound walk in PROFILE.md §2).
def _raw_schoolbook(a: jnp.ndarray, b: jnp.ndarray, n: int) -> jnp.ndarray:
    """[n, ...] x [n, ...] -> un-carried [2n-1, ...] anti-diagonal sums.

    The pad-flatten trick: pad the outer product's j axis from n to 2n,
    flatten (i, j) -> 2n i + j, reslice as rows of 2n-1 — then
    flat[(2n-1) i + k] lands at outer[i, k - i], so a single sum over i
    yields the anti-diagonals.  One multiply + one pad + one reduce
    instead of n shifted adds: ~6 XLA ops per product, which keeps
    compile time flat no matter how many muls a kernel inlines.
    """
    batch = a.shape[1:]
    outer = a[:, None] * b[None, :]  # [n, n, ...]
    pad_cfg = [(0, 0)] * len(batch)
    outer = jnp.pad(outer, [(0, 0), (0, n)] + pad_cfg)  # [n, 2n, ...]
    flat = outer.reshape((n * 2 * n,) + batch)
    flat = flat[: n * (2 * n - 1)]
    return flat.reshape((n, 2 * n - 1) + batch).sum(axis=0)  # [2n-1, ...]


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Batched field multiply: schoolbook 20x20 -> 39-limb product, then
    fold+carry."""
    a, b = _align(a, b)
    batch = jnp.broadcast_shapes(a.shape[1:], b.shape[1:])
    a = jnp.broadcast_to(a, a.shape[:1] + batch)
    b = jnp.broadcast_to(b, b.shape[:1] + batch)
    return carry_product(_raw_schoolbook(a, b, NLIMBS))


def square(a: jnp.ndarray) -> jnp.ndarray:
    return mul(a, a)


def mul_small(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """Multiply by a small public integer: |k| * BOUND must stay < 2^22.5
    (carry20's input range), i.e. |k| <= ~400."""
    assert abs(k) <= 400, "mul_small bound"
    return carry20(a * jnp.int32(k))


def pow2k(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """a^(2^k) by k squarings (k is a static Python int)."""
    def body(_, x):
        return square(x)
    if k <= 4:
        for _ in range(k):
            a = square(a)
        return a
    return lax.fori_loop(0, k, body, a)


def _pow_p58(a: jnp.ndarray) -> jnp.ndarray:
    """a^((p-5)/8), dalek-style addition chain.

    (p-5)/8 = 2^252 - 3. Chain from curve25519 literature.
    """
    t0 = square(a)                     # a^2
    t1 = square(square(t0))            # a^8
    t2 = mul(a, t1)                    # a^9
    t3 = mul(t0, t2)                   # a^11
    t4 = square(t3)                    # a^22
    t5 = mul(t2, t4)                   # a^31 = a^(2^5 - 1)
    t6 = mul(pow2k(t5, 5), t5)         # a^(2^10 - 1)
    t7 = mul(pow2k(t6, 10), t6)        # a^(2^20 - 1)
    t8 = mul(pow2k(t7, 20), t7)        # a^(2^40 - 1)
    t9 = mul(pow2k(t8, 10), t6)        # a^(2^50 - 1)
    t10 = mul(pow2k(t9, 50), t9)       # a^(2^100 - 1)
    t11 = mul(pow2k(t10, 100), t10)    # a^(2^200 - 1)
    t12 = mul(pow2k(t11, 50), t9)      # a^(2^250 - 1)
    return mul(pow2k(t12, 2), a)       # a^(2^252 - 3)


def inv(a: jnp.ndarray) -> jnp.ndarray:
    """a^(p-2) (Fermat); p-2 = 8*(2^252 - 3) + 2^2 + 1 -> reuse the chain."""
    t = _pow_p58(a)            # a^(2^252 - 3)
    t = pow2k(t, 3)            # a^(2^255 - 24)
    return mul(t, mul(square(a), a))  # * a^3 = a^(2^255 - 21) = a^(p-2)


def is_negative(a: jnp.ndarray) -> jnp.ndarray:
    """RFC 9496 sign: parity of the canonical representative. [...] bool."""
    return (canonical(a)[0] & 1).astype(jnp.bool_)


def fabs(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(is_negative(a), neg(a), a)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Field equality -> [...] bool."""
    a, b = _align(a, b)
    return jnp.all(canonical(a) == canonical(b), axis=0)


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(canonical(a) == 0, axis=0)


def select(mask: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """where(mask, a, b) with mask shaped [...batch] (no limb axis)."""
    return jnp.where(mask, a, b)


def sqrt_ratio_m1(u: jnp.ndarray, v: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched SQRT_RATIO_M1 (RFC 9496 §3.1) — twin of
    :func:`cpzk_tpu.core.field.sqrt_ratio_m1`.

    Returns (was_square [...] bool, root [20, ...]).
    """
    v3 = mul(square(v), v)
    v7 = mul(square(v3), v)
    r = mul(mul(u, v3), _pow_p58(mul(u, v7)))
    check = mul(v, square(r))

    neg_u = neg(u)
    correct_sign = eq(check, u)
    flipped_sign = eq(check, neg_u)
    flipped_sign_i = eq(check, mul(neg_u, SQRT_M1))

    r = select(flipped_sign | flipped_sign_i, mul(r, SQRT_M1), r)
    r = fabs(r)
    return correct_sign | flipped_sign, r


# ---------------------------------------------------------------------------
# byte/bit conversions (device-side; byte axis leading, like the limb axis)
# ---------------------------------------------------------------------------

def from_bytes_le(b: jnp.ndarray) -> jnp.ndarray:
    """[32, ...] uint8/int32 little-endian bytes -> carried limbs [20, ...].

    Interprets all 256 bits (caller masks bit 255 if needed); result is
    carried but NOT canonicalized.
    """
    b = b.astype(jnp.int32)
    batch = b.shape[1:]
    shifts = jnp.arange(8, dtype=jnp.int32).reshape((1, 8) + (1,) * len(batch))
    bits = (b[:, None] >> shifts) & 1  # [32, 8, ...]
    bits = bits.reshape((256,) + batch)
    bits = jnp.concatenate(
        [bits, jnp.zeros((NBITS - 256,) + batch, dtype=jnp.int32)], axis=0
    )
    w = jnp.asarray(1 << np.arange(LIMB_BITS, dtype=np.int32)).reshape(
        (1, LIMB_BITS) + (1,) * len(batch)
    )
    return jnp.sum(bits.reshape((NLIMBS, LIMB_BITS) + batch) * w, axis=1)


def to_bytes_le(a: jnp.ndarray) -> jnp.ndarray:
    """Canonical [32, ...] int32 byte values (0..255) of a field element."""
    x = canonical(a)
    batch = x.shape[1:]
    shifts = jnp.arange(LIMB_BITS, dtype=jnp.int32).reshape(
        (1, LIMB_BITS) + (1,) * len(batch)
    )
    bits = (x[:, None] >> shifts) & 1  # [20, 13, ...]
    bits = bits.reshape((NBITS,) + batch)[:256]
    w = jnp.asarray(1 << np.arange(8, dtype=np.int32)).reshape(
        (1, 8) + (1,) * len(batch)
    )
    return jnp.sum(bits.reshape((32, 8) + batch) * w, axis=1)


# ---------------------------------------------------------------------------
# constants (derived from the host field module — single source of truth)
# ---------------------------------------------------------------------------

_P_LIMBS = jnp.asarray(int_to_limbs(P))[:, None]

ZERO = constant(0)
ONE = constant(1)
D = constant(host_field.D)
D2 = constant(2 * host_field.D % P)
SQRT_M1 = constant(host_field.SQRT_M1)
ONE_MINUS_D_SQ = constant(host_field.ONE_MINUS_D_SQ)
D_MINUS_ONE_SQ = constant(host_field.D_MINUS_ONE_SQ)
SQRT_AD_MINUS_ONE = constant(host_field.SQRT_AD_MINUS_ONE)
INVSQRT_A_MINUS_D = constant(host_field.INVSQRT_A_MINUS_D)
