"""ctypes binding to the native C++ secp256k1 verifier.

The CPU baseline / fallback engine (native/secp256k1/secp256k1.cpp) — the
framework's equivalent of the reference's libsecp256k1 dependency
(reference stack.yaml:5,9; SURVEY.md C9).  Builds on demand with ``make -C
native`` when the shared library is missing.
"""

from __future__ import annotations

import ctypes
import os

import numpy as np
from typing import Optional, Sequence

from .ecdsa_cpu import Point

__all__ = ["NativeVerifier", "load_native_verifier"]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_LIB_PATH = os.path.join(_REPO_ROOT, "native", "build", "libsecp_cpu.so")

# Rows of a lane's wire buffer as secp_prepare_batch writes it (PREP_ROWS in
# secp256k1.cpp; kernel.ROWS on the jax side, which this module stays free of).
LANE_ROWS = 53


def _ensure_built() -> str:
    from ..native import ensure_native_lib

    return ensure_native_lib(_LIB_PATH, "secp256k1")


class NativeVerifier:
    """Batch ECDSA verification through the C++ engine."""

    def __init__(self, lib_path: Optional[str] = None):
        path = lib_path or _ensure_built()
        self._lib = ctypes.CDLL(path)
        self._lib.secp_verify_batch.restype = ctypes.c_int
        self._lib.secp_verify_batch.argtypes = [
            ctypes.c_char_p,  # px
            ctypes.c_char_p,  # py
            ctypes.c_char_p,  # z (digest or schnorr challenge)
            ctypes.c_char_p,  # r
            ctypes.c_char_p,  # s
            ctypes.c_char_p,  # present/algo (None = all ecdsa)
            ctypes.c_int,  # count
            ctypes.c_char_p,  # out
        ]
        self._lib.secp_verify_batch_mt.restype = ctypes.c_int
        self._lib.secp_verify_batch_mt.argtypes = (
            self._lib.secp_verify_batch.argtypes + [ctypes.c_int]  # nthreads
        )
        from numpy.ctypeslib import ndpointer

        self._lib.secp_prepare_rows.restype = ctypes.c_int
        self._lib.secp_prepare_rows.argtypes = []
        if self._lib.secp_prepare_rows() != LANE_ROWS:
            raise RuntimeError("libsecp_cpu.so writes another lane layout")
        self._lib.secp_prepare_batch.restype = ctypes.c_int
        self._lib.secp_prepare_batch.argtypes = [
            ctypes.c_char_p,  # px
            ctypes.c_char_p,  # py
            ctypes.c_char_p,  # z
            ctypes.c_char_p,  # r
            ctypes.c_char_p,  # s
            ctypes.c_char_p,  # present
            ctypes.c_int,  # count
            ctypes.c_int,  # size
            ndpointer(np.int32, ndim=2, flags="C_CONTIGUOUS"),  # lane buffer
            ctypes.c_int,  # nthreads
        ]

    def prepare_lane(
        self,
        px: bytes,
        py: bytes,
        z: bytes,
        r: bytes,
        s: bytes,
        present: bytes,
        count: int,
        size: int,
        nthreads: int = 0,
    ) -> np.ndarray:
        """Fill one lane's ``(LANE_ROWS, size)`` int32 wire buffer natively
        (layout: kernel.py, "the lane's wire form"; kernel.prepare_batch's
        fast path).  Raises on a GLV bound violation (structurally
        impossible for in-range scalars; nonzero means a bug, never a bad
        signature)."""
        buf = np.zeros((LANE_ROWS, size), np.int32)
        bad = self._lib.secp_prepare_batch(
            px, py, z, r, s, present, count, size, buf, nthreads
        )
        if bad:
            raise ValueError(
                f"native prep: {bad} GLV half-scalars out of range"
            )
        return buf

    def verify_batch(self, items: Sequence[tuple]) -> list[bool]:
        """items: (pubkey|None, z, r, s) ECDSA tuples or 5-tuples tagged
        "schnorr" (z = precomputed challenge) — same shape as the oracle's
        ``verify_batch_cpu``.  ``None`` pubkeys are auto-invalid (matching
        the oracle and kernel.prepare_batch's host_valid mask)."""
        n = len(items)
        if n == 0:
            return []
        # Range checks on the ORIGINAL ints happen in pack_items: r/s from
        # lax DER can exceed 2^256, and truncating them mod 2^256 could
        # alias a hostile value onto a valid one — the oracle/TPU paths
        # reject such items, so this backend must too (never pack-then-
        # check).  pack_items zeroes those rows with present=0.
        from .raw import pack_items

        return self.verify_raw(pack_items(items))

    def verify_raw(self, raw, nthreads: int = 1) -> list[bool]:
        """Verify a packed :class:`tpunode.verify.raw.RawBatch` — the
        zero-copy path from the native extractor.  ``present`` carries the
        per-row algorithm (0 absent, 1 ecdsa, 2 schnorr) straight into the
        C engine.  ``nthreads`` != 1 splits rows across OS threads (0 =
        hardware concurrency) — the engine passes VerifyConfig.cpu_threads
        so multi-core hosts scale the fallback path."""
        n = len(raw)
        if n == 0:
            return []
        out = ctypes.create_string_buffer(n)
        present = np.ascontiguousarray(raw.present, dtype=np.uint8)
        if nthreads == 1:
            self._lib.secp_verify_batch(
                raw.px.tobytes(), raw.py.tobytes(), raw.z.tobytes(),
                raw.r.tobytes(), raw.s.tobytes(), present.tobytes(), n, out,
            )
        else:
            self._lib.secp_verify_batch_mt(
                raw.px.tobytes(), raw.py.tobytes(), raw.z.tobytes(),
                raw.r.tobytes(), raw.s.tobytes(), present.tobytes(), n, out,
                nthreads,
            )
        return [bool(raw.present[i]) and out.raw[i] == 1 for i in range(n)]


_cached: Optional[NativeVerifier] = None
_load_failed = False


def load_native_verifier() -> Optional[NativeVerifier]:
    """Build+load the native verifier; None if the toolchain is unavailable.
    Failure is cached so a broken toolchain costs one ``make`` attempt per
    process, not one per batch on the hot prep path."""
    global _cached, _load_failed
    if _cached is None and not _load_failed:
        try:
            _cached = NativeVerifier()
        except Exception:
            _load_failed = True
    return _cached
