"""Plain secp256k1: the benchmark's own reference arithmetic.

Nothing here comes from the program under test.  It verifies ECDSA and
BCH-Schnorr (2019-05 upgrade) signatures the slow, obvious way, and it
makes signatures the cheap way the generator needs: keys and nonces are
chains of point additions (``P[i+1] = P[i] + G``), so no signature costs
a scalar multiplication.  Such keys and nonces are worthless as secrets
and perfectly good as verification work.

``Checks`` exists for the control: a verifier with one of its range /
curve / residue checks switched off is the fault ``correct`` has to see.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

P = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEFFFFFC2F
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8
G = (GX, GY)


@dataclass(frozen=True)
class Checks:
    """Which consensus checks a verifier applies.  The reference applies
    all; the control verifiers drop some."""

    scalar_range: bool = True  # 0 < r, s < n (ECDSA); r < p, s < n (Schnorr)
    on_curve: bool = True  # the public key is a curve point
    residue: bool = True  # Schnorr: y(R') is a quadratic residue
    equation: bool = True  # the signature equation itself


FULL = Checks()


def add(a, b):
    """Affine addition; ``None`` is the point at infinity."""
    if a is None:
        return b
    if b is None:
        return a
    (x1, y1), (x2, y2) = a, b
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        lam = 3 * x1 * x1 * pow(2 * y1, -1, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    return x3, (lam * (x1 - x3) - y1) % P


def _jdouble(X, Y, Z):
    if not Y:
        return 0, 1, 0
    S = 4 * X * Y * Y % P
    M = 3 * X * X % P
    X3 = (M * M - 2 * S) % P
    return X3, (M * (S - X3) - 8 * pow(Y, 4, P)) % P, 2 * Y * Z % P


def _jadd_affine(X, Y, Z, x2, y2):
    """Jacobian + affine; Z == 0 is the point at infinity."""
    if not Z:
        return x2, y2, 1
    ZZ = Z * Z % P
    U2, S2 = x2 * ZZ % P, y2 * ZZ * Z % P
    H, R = (U2 - X) % P, (S2 - Y) % P
    if not H:
        return _jdouble(X, Y, Z) if not R else (0, 1, 0)
    HH = H * H % P
    HHH, V = H * HH % P, X * HH % P
    X3 = (R * R - HHH - 2 * V) % P
    return X3, (R * (V - X3) - Y * HHH) % P, Z * H % P


def mul(k: int, pt):
    """Double-and-add over Jacobian coordinates, one inversion at the end;
    plain on purpose."""
    k %= N
    if pt is None or not k:
        return None
    X, Y, Z = 0, 1, 0
    for bit in bin(k)[2:]:
        X, Y, Z = _jdouble(X, Y, Z)
        if bit == "1":
            X, Y, Z = _jadd_affine(X, Y, Z, pt[0], pt[1])
    if not Z:
        return None
    zi = pow(Z, -1, P)
    return X * zi * zi % P, Y * zi * zi * zi % P


def on_curve(pt) -> bool:
    x, y = pt
    return 0 <= x < P and 0 <= y < P and (y * y - x * x * x - 7) % P == 0


def is_residue(y: int) -> bool:
    return pow(y, (P - 1) // 2, P) == 1


def compress(pt) -> bytes:
    return bytes([2 + (pt[1] & 1)]) + pt[0].to_bytes(32, "big")


def decode_pubkey(blob: bytes, checks: Checks = FULL):
    """33-byte compressed or 65-byte uncompressed key -> point, or None."""
    if len(blob) == 33 and blob[0] in (2, 3):
        x = int.from_bytes(blob[1:], "big")
        if x >= P:
            return None
        y = pow((x * x * x + 7) % P, (P + 1) // 4, P)
        if (y * y - x * x * x - 7) % P:
            return None
        if (y & 1) != (blob[0] & 1):
            y = P - y
        return x, y
    if len(blob) == 65 and blob[0] == 4:
        pt = int.from_bytes(blob[1:33], "big"), int.from_bytes(blob[33:], "big")
        if checks.on_curve and not on_curve(pt):
            return None
        return pt
    return None


def parse_der(sig: bytes):
    """Strict-enough DER: ``30 len 02 lr r 02 ls s`` -> (r, s) or None."""
    if len(sig) < 8 or sig[0] != 0x30 or sig[1] != len(sig) - 2:
        return None
    if sig[2] != 0x02:
        return None
    lr = sig[3]
    if lr == 0 or 4 + lr + 2 > len(sig) or sig[4 + lr] != 0x02:
        return None
    ls = sig[5 + lr]
    if ls == 0 or 6 + lr + ls != len(sig):
        return None
    return (int.from_bytes(sig[4:4 + lr], "big"),
            int.from_bytes(sig[6 + lr:], "big"))


def der(r: int, s: int) -> bytes:
    def enc(v: int) -> bytes:
        b = v.to_bytes((v.bit_length() + 8) // 8 or 1, "big")
        return b"\x02" + bytes([len(b)]) + b

    body = enc(r) + enc(s)
    return b"\x30" + bytes([len(body)]) + body


def ecdsa_verify(pub, z: int, r: int, s: int, checks: Checks = FULL) -> bool:
    if pub is None:
        return False
    if checks.scalar_range and not (0 < r < N and 0 < s < N):
        return False
    if checks.on_curve and not on_curve(pub):
        return False
    if not checks.equation:
        return True
    if s % N == 0:
        return False
    w = pow(s, -1, N)
    pt = add(mul(z * w, G), mul(r * w, pub))
    return pt is not None and pt[0] % N == r % N


def schnorr_challenge(r: int, pub, m: int) -> int:
    h = hashlib.sha256(
        (r % (1 << 256)).to_bytes(32, "big") + compress(pub)
        + m.to_bytes(32, "big")
    ).digest()
    return int.from_bytes(h, "big") % N


def schnorr_verify(pub, m: int, r: int, s: int, checks: Checks = FULL) -> bool:
    """BCH Schnorr: R' = sG - eP; accept iff R' finite, y(R') a residue,
    x(R') = r."""
    if pub is None:
        return False
    if checks.scalar_range and not (r < P and s < N):
        return False
    if checks.on_curve and not on_curve(pub):
        return False
    if not checks.equation:
        return True
    e = schnorr_challenge(r, pub, m)
    pt = add(mul(s, G), mul(N - e, pub))
    if pt is None:
        return False
    if checks.residue and not is_residue(pt[1]):
        return False
    return pt[0] == r


def _batch_inverse(vals: list, m: int) -> list:
    """Montgomery's trick: one inversion for the whole list."""
    pre, acc = [], 1
    for v in vals:
        pre.append(acc)
        acc = acc * v % m
    inv = pow(acc, -1, m)
    out = [0] * len(vals)
    for i in range(len(vals) - 1, -1, -1):
        out[i] = inv * pre[i] % m
        inv = inv * vals[i] % m
    return out


class Chain:
    """``base*G, (base+1)*G, ...`` by one point addition each: the cheap
    source of distinct keys and nonces.  Points are stepped in Jacobian
    coordinates and normalised a batch at a time, so a step costs no
    inversion."""

    BATCH = 256

    def __init__(self, base: int):
        self.k = base % (N - (1 << 64)) or 1  # the chain never wraps
        x, y = mul(self.k, G)
        self._jac = (x, y, 1)
        self._ready: list = []

    def _refill(self) -> None:
        jac, J = [], self._jac
        for _ in range(self.BATCH):
            jac.append(J)
            J = _jadd_affine(*J, GX, GY)
        self._jac = J
        zinv = _batch_inverse([j[2] for j in jac], P)
        kinv = _batch_inverse(list(range(self.k, self.k + self.BATCH)), N)
        ready = []
        for i, ((X, Y, _), zi) in enumerate(zip(jac, zinv)):
            zz = zi * zi % P
            ready.append((self.k + i, (X * zz % P, Y * zz * zi % P), kinv[i]))
        self.k += self.BATCH
        ready.reverse()
        self._ready = ready

    def next(self):
        """(scalar, point, scalar^-1 mod n), then step."""
        if not self._ready:
            self._refill()
        return self._ready.pop()


def ecdsa_sign(d: int, z: int, kinv: int, kpt) -> tuple:
    """ECDSA with a nonce whose point and inverse are already known."""
    r = kpt[0] % N
    return r, kinv * (z + r * d) % N


def schnorr_sign(d: int, pub, m: int, k: int, kpt, residue: bool = True):
    """BCH Schnorr with a known nonce point.  ``residue=False`` makes the
    twin whose x(R) matches and whose y(R) is a non-residue: only the
    residue check rejects it."""
    if is_residue(kpt[1]) != residue:
        k, kpt = N - k, (kpt[0], P - kpt[1])
    r = kpt[0]
    return r, (k + schnorr_challenge(r, pub, m) * d) % N
