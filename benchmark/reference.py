"""The plain reference: Chaum-Pedersen verification over ristretto255,
written apart from the program under test.

It imports nothing of ``cpzk_tpu``.  The group law is libsodium's
RFC 9496 ristretto255 (a system library, called through ctypes); the
Fiat-Shamir challenge is Merlin over STROBE-128 over Keccak-f[1600],
written here from the published specifications and vectorised over rows
with numpy (every transcript of one batch has the same framing, so one
byte layout serves all rows).  What it follows:

- proof wire: ``[version u8 = 1]`` then three u32-big-endian
  length-prefixed 32-byte fields ``r1 || r2 || s``, 109 bytes in all;
  ``r1``/``r2`` canonical non-identity ristretto255 encodings, ``s`` a
  canonical nonzero scalar (reference crate ``gadgets.rs``);
- transcript: Merlin label ``"Chaum-Pedersen ZKP v1.0.0"``, then
  ``protocol`` = ``"chaum-pedersen-ristretto255"``, ``context`` = the
  challenge id, ``generator-g``, ``generator-h``, ``y1``, ``y2``, ``r1``,
  ``r2``; the challenge is 64 bytes under ``"challenge"`` reduced mod l
  (reference crate ``transcript.rs``);
- generators: g the ristretto255 base point, h = from_uniform_bytes(
  SHA-512(``"chaum-pedersen-zkp-v1.0.0-generator-h"``));
- verdict: accept iff the wire parses and ``g*s == r1 + c*y1`` and
  ``h*s == r2 + c*y2``.

``verdicts(..., control=True)`` is the benchmark's control: the same
reference with soundness broken (every proof that parses is accepted,
no equation is checked).  See PERF.md §2.
"""

from __future__ import annotations

import ctypes
import ctypes.util
import hashlib
from dataclasses import dataclass

import numpy as np

L = 2**252 + 27742317777372353535851937790883648493
GENERATOR_H_DST = b"chaum-pedersen-zkp-v1.0.0-generator-h"
PROOF_BYTES = 109

# -- Keccak-f[1600] (FIPS 202), vectorised over rows --------------------------

_RC = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]
# rotation offsets r[x][y]
_ROT = [
    [0, 36, 3, 41, 18],
    [1, 44, 10, 45, 2],
    [62, 6, 43, 15, 61],
    [28, 55, 25, 21, 56],
    [27, 20, 39, 8, 14],
]
_RC_NP = [np.uint64(c) for c in _RC]


def _rotl(a: np.ndarray, r: int) -> np.ndarray:
    if r == 0:
        return a
    return (a << np.uint64(r)) | (a >> np.uint64(64 - r))


def keccak_f1600(state: np.ndarray) -> np.ndarray:
    """Permute ``state`` (rows x 200 bytes, uint8) in place; returns it."""
    lanes = state.view("<u8")  # rows x 25, lane x + 5y
    a = [[lanes[:, x + 5 * y].copy() for y in range(5)] for x in range(5)]
    for rc in _RC_NP:
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4] for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rotl(c[(x + 1) % 5], 1) for x in range(5)]
        b = [[None] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                b[y][(2 * x + 3 * y) % 5] = _rotl(a[x][y] ^ d[x], _ROT[x][y])
        a = [[b[x][y] ^ (~b[(x + 1) % 5][y] & b[(x + 2) % 5][y])
              for y in range(5)] for x in range(5)]
        a[0][0] = a[0][0] ^ rc
    for x in range(5):
        for y in range(5):
            lanes[:, x + 5 * y] = a[x][y]
    return state


# -- STROBE-128, the subset Merlin uses (strobe.sourceforge.io v1.0.2) --------

_R = 166
_FLAG_I, _FLAG_A, _FLAG_C, _FLAG_M = 0x01, 0x02, 0x04, 0x10


class Strobe128:
    """One STROBE state per row; every row absorbs the same lengths."""

    def __init__(self, rows: int, protocol_label: bytes):
        st = np.zeros((rows, 200), dtype=np.uint8)
        st[:, 0:6] = [1, _R + 2, 1, 0, 1, 96]
        st[:, 6:18] = np.frombuffer(b"STROBEv1.0.2", dtype=np.uint8)
        self.state = keccak_f1600(st)
        self.pos = 0
        self.pos_begin = 0
        self.meta_ad(protocol_label)

    def copy(self, rows: int) -> "Strobe128":
        """A state per row, each a copy of row 0 (a shared prefix)."""
        out = object.__new__(Strobe128)
        out.state = np.repeat(self.state[:1], rows, axis=0)
        out.pos, out.pos_begin = self.pos, self.pos_begin
        return out

    def _run_f(self) -> None:
        self.state[:, self.pos] ^= self.pos_begin
        self.state[:, self.pos + 1] ^= 0x04
        self.state[:, _R + 1] ^= 0x80
        keccak_f1600(self.state)
        self.pos = 0
        self.pos_begin = 0

    def _absorb(self, data: np.ndarray) -> None:
        i = 0
        n = data.shape[1]
        while i < n:
            k = min(_R - self.pos, n - i)
            self.state[:, self.pos:self.pos + k] ^= data[:, i:i + k]
            self.pos += k
            i += k
            if self.pos == _R:
                self._run_f()

    def _squeeze(self, n: int) -> np.ndarray:
        out = np.zeros((self.state.shape[0], n), dtype=np.uint8)
        i = 0
        while i < n:
            k = min(_R - self.pos, n - i)
            out[:, i:i + k] = self.state[:, self.pos:self.pos + k]
            self.state[:, self.pos:self.pos + k] = 0
            self.pos += k
            i += k
            if self.pos == _R:
                self._run_f()
        return out

    def _const(self, data: bytes) -> np.ndarray:
        row = np.frombuffer(data, dtype=np.uint8)
        return np.broadcast_to(row, (self.state.shape[0], len(row)))

    def _begin_op(self, flags: int) -> None:
        old_begin = self.pos_begin
        self.pos_begin = self.pos + 1
        self._absorb(self._const(bytes([old_begin, flags])))
        if flags & _FLAG_C and self.pos != 0:
            self._run_f()

    def meta_ad(self, data: bytes, more: bool = False) -> None:
        if not more:
            self._begin_op(_FLAG_M | _FLAG_A)
        self._absorb(self._const(data))

    def ad(self, data: np.ndarray) -> None:
        self._begin_op(_FLAG_A)
        self._absorb(data)

    def prf(self, n: int) -> np.ndarray:
        self._begin_op(_FLAG_I | _FLAG_A | _FLAG_C)
        return self._squeeze(n)


class Merlin:
    """Merlin transcripts (merlin.cool), one per row."""

    def __init__(self, rows: int, label: bytes):
        self.strobe = Strobe128(rows, b"Merlin v1.0")
        self.append(b"dom-sep", label)

    @classmethod
    def fork(cls, prefix: "Merlin", rows: int) -> "Merlin":
        out = object.__new__(cls)
        out.strobe = prefix.strobe.copy(rows)
        return out

    def append(self, label: bytes, message) -> None:
        """``message``: bytes (the same for every row) or rows x len."""
        if isinstance(message, (bytes, bytearray)):
            message = self.strobe._const(bytes(message))
        self.strobe.meta_ad(label)
        self.strobe.meta_ad(message.shape[1].to_bytes(4, "little"), more=True)
        self.strobe.ad(np.ascontiguousarray(message))

    def challenge_bytes(self, label: bytes, n: int) -> np.ndarray:
        self.strobe.meta_ad(label)
        self.strobe.meta_ad(n.to_bytes(4, "little"), more=True)
        return self.strobe.prf(n)


# -- ristretto255 through libsodium -------------------------------------------


class Sodium:
    """The ristretto255 calls of libsodium (>= 1.0.18)."""

    def __init__(self):
        lib = None
        for name in ("libsodium.so.23", "libsodium.so.26", "libsodium.so",
                     ctypes.util.find_library("sodium")):
            if not name:
                continue
            try:
                lib = ctypes.CDLL(name)
                break
            except OSError:
                continue
        if lib is None or not hasattr(lib, "crypto_core_ristretto255_add"):
            raise RuntimeError("the reference needs libsodium >= 1.0.18")
        if lib.sodium_init() < 0:
            raise RuntimeError("sodium_init failed")
        self.lib = lib
        self._q = ctypes.create_string_buffer(32)

    def is_valid(self, p: bytes) -> bool:
        return self.lib.crypto_core_ristretto255_is_valid_point(p) == 1

    def base(self, n: bytes) -> bytes:
        self.lib.crypto_scalarmult_ristretto255_base(self._q, n)
        return self._q.raw  # all zeros: the identity

    def mul(self, n: bytes, p: bytes) -> bytes:
        self.lib.crypto_scalarmult_ristretto255(self._q, n, p)
        return self._q.raw

    def add(self, p: bytes, q: bytes) -> bytes:
        if self.lib.crypto_core_ristretto255_add(self._q, p, q) != 0:
            raise ValueError("invalid point")
        return self._q.raw

    def from_hash(self, h64: bytes) -> bytes:
        self.lib.crypto_core_ristretto255_from_hash(self._q, h64)
        return self._q.raw


_IDENTITY = bytes(32)


@dataclass(frozen=True)
class Row:
    """One proof to check: the statement, its challenge context, the wire."""

    y1: bytes
    y2: bytes
    ctx: bytes
    wire: bytes


def parse_wire(wire: bytes, na: Sodium) -> tuple[bytes, bytes, bytes] | None:
    """(r1, r2, s) of a well-formed proof wire, else None."""
    if len(wire) != PROOF_BYTES or wire[0] != 1:
        return None
    fields = []
    pos = 1
    for _ in range(3):
        if int.from_bytes(wire[pos:pos + 4], "big") != 32:
            return None
        fields.append(wire[pos + 4:pos + 36])
        pos += 36
    r1, r2, s = fields
    for r in (r1, r2):
        if r == _IDENTITY or not na.is_valid(r):
            return None
    sv = int.from_bytes(s, "little")
    if sv == 0 or sv >= L:
        return None
    return r1, r2, s


class Reference:
    """Verdicts of the plain reference for rows of (statement, ctx, proof)."""

    PROTOCOL_LABEL = b"Chaum-Pedersen ZKP v1.0.0"
    PROTOCOL_DST = b"chaum-pedersen-ristretto255"

    def __init__(self):
        self.na = Sodium()
        one = (1).to_bytes(32, "little")
        self.g = self.na.base(one)
        self.h = self.na.from_hash(hashlib.sha512(GENERATOR_H_DST).digest())
        prefix = Merlin(1, self.PROTOCOL_LABEL)
        prefix.append(b"protocol", self.PROTOCOL_DST)
        self._prefix = prefix

    def challenges(self, rows: list[Row], parsed: list[tuple]) -> list[int]:
        """Fiat-Shamir challenges (mod l) of parsed rows, grouped by
        context length (one framing per group)."""
        out: list[int] = [0] * len(rows)
        groups: dict[int, list[int]] = {}
        for i, row in enumerate(rows):
            groups.setdefault(len(row.ctx), []).append(i)

        def col(idx, get):
            return np.frombuffer(b"".join(get(i) for i in idx),
                                 dtype=np.uint8).reshape(len(idx), -1)

        for idx in groups.values():
            t = Merlin.fork(self._prefix, len(idx))
            t.append(b"context", col(idx, lambda i: rows[i].ctx))
            t.append(b"generator-g", self.g)
            t.append(b"generator-h", self.h)
            t.append(b"y1", col(idx, lambda i: rows[i].y1))
            t.append(b"y2", col(idx, lambda i: rows[i].y2))
            t.append(b"r1", col(idx, lambda i: parsed[i][0]))
            t.append(b"r2", col(idx, lambda i: parsed[i][1]))
            wide = t.challenge_bytes(b"challenge", 64)
            for k, i in enumerate(idx):
                out[i] = int.from_bytes(wide[k].tobytes(), "little") % L
        return out

    def verdicts(self, rows: list[Row], control: bool = False) -> list[bool]:
        na = self.na
        parsed = [parse_wire(r.wire, na) for r in rows]
        ok = [p is not None for p in parsed]
        if control:
            return ok  # soundness broken: no equation is checked
        live = [i for i, p in enumerate(parsed) if p is not None]
        cs = self.challenges([rows[i] for i in live], [parsed[i] for i in live])
        out = [False] * len(rows)
        for i, c in zip(live, cs):
            r1, r2, s = parsed[i]
            cb = c.to_bytes(32, "little")
            row = rows[i]
            eq1 = na.base(s) == na.add(r1, na.mul(cb, row.y1))
            eq2 = na.mul(s, self.h) == na.add(r2, na.mul(cb, row.y2))
            out[i] = eq1 and eq2
        return out


def _chunk_verdicts(rows: list[Row], control: bool) -> list[bool]:
    return Reference().verdicts(rows, control=control)


def verdicts(rows: list[Row], control: bool = False, workers: int = 8) -> list[bool]:
    """``Reference().verdicts`` over a pool of spawned processes (each
    row is independent; none of them touches JAX)."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    workers = max(1, min(workers, len(rows) // 2048))
    if workers == 1:
        return _chunk_verdicts(rows, control)
    step = -(-len(rows) // workers)
    chunks = [rows[i:i + step] for i in range(0, len(rows), step)]
    with ProcessPoolExecutor(len(chunks),
                             mp_context=multiprocessing.get_context("spawn")) as ex:
        parts = list(ex.map(_chunk_verdicts, chunks, [control] * len(chunks)))
    return [v for part in parts for v in part]
