"""The tests' reference for a lane's wire form (kernel.py, "the lane's wire
form"): the sixteen arrays the device program computes on, rebuilt from the
packed buffer with Python ints and numpy, independently of
``kernel.expand_lane``.  Until PR 39 the host expanded these itself and
handed sixteen arrays to the device; ``ints_to_digits_np`` /
``ints_to_limbs_np`` are that host expansion, kept as the reference.
"""

from __future__ import annotations

import numpy as np

from tpunode.verify import field as F
from tpunode.verify import kernel as K

# verify_core's signature, in order: (name, ndim)
DEVICE_FIELDS = (
    ("d1a", 2), ("d1b", 2), ("d2a", 2), ("d2b", 2),
    ("n1a", 1), ("n1b", 1), ("n2a", 1), ("n2b", 1),
    ("qx", 2), ("qy", 2), ("r1", 2), ("r2", 2),
    ("r2_valid", 1), ("host_valid", 1), ("schnorr", 1), ("bip340", 1),
)
HALVES = ("d1a", "d1b", "d2a", "d2b")
FIELDS = ("qx", "qy", "r1", "r2")


def digits_base16(v: int) -> list[int]:
    """WINDOWS base-16 digits of a nonnegative int, most significant first."""
    return [(v >> (4 * (K.WINDOWS - 1 - i))) & 0xF for i in range(K.WINDOWS)]


def ints_to_limbs_np(vals: list[int]) -> np.ndarray:
    """Vectorized ``F.to_limbs``: 256-bit ints -> (len, NLIMBS) int32."""
    n = len(vals)
    buf = b"".join(v.to_bytes(32, "little") for v in vals)
    words = np.frombuffer(buf, dtype="<u8").reshape(n, 4)
    out = np.zeros((n, F.NLIMBS), dtype=np.int32)
    for i in range(F.NLIMBS):
        w, off = divmod(F.RADIX * i, 64)
        lo = words[:, w] >> np.uint64(off)
        if off > 64 - F.RADIX and w + 1 < 4:  # limb straddles a word edge
            lo = lo | (words[:, w + 1] << np.uint64(64 - off))
        out[:, i] = (lo & np.uint64(F.MASK)).astype(np.int32)
    return out


def ints_to_digits_np(vals: list[int]) -> np.ndarray:
    """Vectorized ``digits_base16``: ints < 2^132 -> (len, WINDOWS) int32,
    MSB-first (4-bit digits never straddle 64-bit word edges)."""
    n = len(vals)
    buf = b"".join(v.to_bytes(24, "little") for v in vals)
    words = np.frombuffer(buf, dtype="<u8").reshape(n, 3)
    out = np.zeros((n, K.WINDOWS), dtype=np.int32)
    for j in range(K.WINDOWS):
        w, off = divmod(4 * (K.WINDOWS - 1 - j), 64)
        out[:, j] = ((words[:, w] >> np.uint64(off)) & np.uint64(0xF)).astype(
            np.int32
        )
    return out


def column_ints(buf: np.ndarray, row: int, nwords: int) -> list[int]:
    """The int each column holds in rows ``row .. row + nwords``."""
    words = np.ascontiguousarray(buf[row:row + nwords].T).astype("<u4")
    return [int.from_bytes(col.tobytes(), "little") for col in words]


def pack(halves: dict, fields: dict, flags: dict, size: int) -> np.ndarray:
    """A wire buffer from per-column ints and masks (lists of equal length
    <= ``size``; names as in DEVICE_FIELDS; what is left out stays zero)."""
    buf = np.zeros((K.ROWS, size), dtype=np.int32)

    def put(row, nwords, vals):
        for i, v in enumerate(vals):
            buf[row:row + nwords, i] = np.frombuffer(
                v.to_bytes(4 * nwords, "little"), dtype="<i4")

    for j, name in enumerate(HALVES):
        put(j * K.HALF_WORDS, K.HALF_WORDS, halves.get(name, ()))
    for j, name in enumerate(FIELDS):
        put(K.FIELD_ROW0 + j * K.FIELD_WORDS, K.FIELD_WORDS,
            fields.get(name, ()))
    for name, mask in flags.items():
        bit = 1 << K.FLAG_NAMES.index(name)
        buf[K.FLAGS_ROW, :len(mask)] |= np.where(mask, bit, 0).astype(np.int32)
    return buf


def flag(buf: np.ndarray, name: str) -> np.ndarray:
    """The ``(B,)`` bool mask ``name`` (one of kernel.FLAG_NAMES)."""
    return (buf[K.FLAGS_ROW] >> K.FLAG_NAMES.index(name)) & 1 != 0


def expand_np(buf: np.ndarray) -> dict:
    """The sixteen arrays by name: digit arrays ``(WINDOWS, B)`` and limb
    arrays ``(NLIMBS, B)`` int32, masks ``(B,)`` bool."""
    out = {}
    for j, name in enumerate(HALVES):
        vals = column_ints(buf, j * K.HALF_WORDS, K.HALF_WORDS)
        out[name] = np.ascontiguousarray(ints_to_digits_np(vals).T)
    for j, name in enumerate(FIELDS):
        vals = column_ints(buf, K.FIELD_ROW0 + j * K.FIELD_WORDS, K.FIELD_WORDS)
        out[name] = np.ascontiguousarray(ints_to_limbs_np(vals).T)
    for name in K.FLAG_NAMES:
        out[name] = flag(buf, name)
    return out


def device_args_np(buf: np.ndarray) -> tuple:
    """``expand_np`` in verify_core's argument order."""
    arrays = expand_np(buf)
    return tuple(arrays[name] for name, _ in DEVICE_FIELDS)
