"""256-bit modular arithmetic for the secp256k1 field on TPU.

TPUs have no wide integers, so field elements are vectors of NLIMBS=24 limbs
of RADIX=11 bits in int32 lanes.  **Layout is limb-major**: an element batch
has shape ``(NLIMBS, B)`` — the limb axis is axis 0 (sublanes: 24 = 3x8,
zero padding) and the batch axis is minor-most (lanes: B a multiple of 128
tiles perfectly).  The transposed layout ``(B, NLIMBS)`` would pad the
24-limb minor dim to 128 lanes (~19% utilization); limb-major is the single
biggest throughput lever on this kernel.

Everything is a fixed-shape, branch-free jnp program — what XLA fuses and
tiles best.  Constants are shape ``(NLIMBS, 1)`` so they broadcast over the
trailing batch axis.

Key design points (bounds are load-bearing):

* **Loose limbs.** Between operations limbs may be loose — up to the
  per-function input contracts (``mul`` admits |non-top limb| <= 2**19,
  |top limb| <= 2**15; ``mul_t`` requires every |limb| <= 2**13 — see their
  docstrings, which are the load-bearing bounds) — and possibly negative:
  two's-complement ``& MASK`` / arithmetic ``>> RADIX`` keep carry rounds
  exact for negatives, which makes subtraction free (no borrow chains).
* **Multiplication** internally tightens both inputs with one carry round
  (bringing limbs to ``< 2**12``), then does the 24x24 limb convolution in
  direct shift-add form (partials < 2**24, anti-diagonal sums of <= 24 terms
  < 2**28.6 — far inside int32), then folds limbs >= 24 back using the
  sparse prime: 2^264 ≡ 256*(2^32+977) (mod p).
* **No value is ever dropped**: carry rounds preserve the top limb's
  overflow in place instead of discarding it, and every buffer that carries a
  fat top limb is padded first.
* **Canonicalization** (exact value in [0, p)) is only needed at equality
  checks — once per verification, not per operation.

Host<->device speaks Python ints via ``to_limbs``/``from_limbs``.

**One limb-product formulation**: the shift-add convolution (everything
on the VPU), with a dedicated **half-product squaring** (~300 partial
products instead of 576, exploiting a_i*a_j symmetry) used by the pow
ladders and doubling formulas.  The alternatives (a ``dot_general``
contraction, squaring through ``mul``) were read on the chip and deleted
(PERF.md, PR 29).

This replaces the capability the reference gets from libsecp256k1's field
module (reference stack.yaml:5,9; SURVEY.md C9), redesigned for vector/matrix
units rather than translated from the C.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

__all__ = [
    "RADIX",
    "NLIMBS",
    "P",
    "N",
    "to_limbs",
    "from_limbs",
    "mul",
    "mul_t",
    "sqr",
    "sqr_t",
    "mul_small_red",
    "mul_wide",
    "mul_t_wide",
    "sqr_wide",
    "sqr_t_wide",
    "acc_add",
    "reduce_wide",
    "reduce_wide_loose",
    "tighten",
    "canonical",
    "is_zero",
    "eq",
    "select",
    "ZERO",
    "ONE",
]

RADIX = 11
NLIMBS = 24
MASK = (1 << RADIX) - 1
TOTAL_BITS = RADIX * NLIMBS  # 264

P = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEFFFFFC2F
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141

FOLD_INT = (1 << TOTAL_BITS) % P  # 2^264 mod p = 256*(2^32+977), 4 limbs
C_INT = (1 << 256) % P  # 2^32 + 977
_FN = 4  # limb count of the fold constant


def _limbs_list(v: int, n: int) -> list[int]:
    return [(v >> (RADIX * i)) & MASK for i in range(n)]


def to_limbs(v: int, n: int = NLIMBS) -> np.ndarray:
    """Host: Python int -> little-endian limb vector (int32), shape (n,)."""
    return np.array(_limbs_list(v, n), dtype=np.int32)


def from_limbs(limbs) -> int:
    """Host: limb vector (loose/negative limbs fine) -> Python int.

    Accepts shape (L,) or (L, 1); the limb axis must be axis 0.
    """
    out = 0
    for i, l in enumerate(np.asarray(limbs).reshape(-1).tolist()):
        out += int(l) << (RADIX * i)
    return out


FOLD = jnp.array(_limbs_list(FOLD_INT, _FN), dtype=jnp.int32)
C_LIMBS = jnp.array(_limbs_list(C_INT, _FN), dtype=jnp.int32)
P_LIMBS = jnp.array(_limbs_list(P, NLIMBS), dtype=jnp.int32)[:, None]
ZERO = jnp.zeros((NLIMBS, 1), dtype=jnp.int32)
ONE = jnp.zeros((NLIMBS, 1), dtype=jnp.int32).at[0].set(1)


def _conv(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Limb convolution: (24, B) x (24, B) -> (47, B).

    Direct shift-add form (24 broadcast multiplies + static slice-adds):
    exactly the 24*24 partial products, nothing more — XLA fuses the
    whole chain into vector code with no materialized outer product.
    """
    out = jnp.zeros((2 * NLIMBS - 1,) + a.shape[1:], dtype=jnp.int32)
    for i in range(NLIMBS):
        out = out.at[i : i + NLIMBS].add(a[i] * b)
    return out


def _sqr_conv(a: jnp.ndarray) -> jnp.ndarray:
    """Half-product squaring, shift-add form: out[i+j] += (2-δij)·a_i·a_j
    over i <= j — ~300 partial products instead of 576.  Per-position sums
    equal _conv(a, a)'s exactly (same value, same bounds: the doubling
    only rebrackets 2 identical cross terms into one)."""
    out = jnp.zeros((2 * NLIMBS - 1,) + a.shape[1:], dtype=jnp.int32)
    d = a + a
    for i in range(NLIMBS):
        out = out.at[2 * i].add(a[i] * a[i])
        if i + 1 < NLIMBS:
            out = out.at[2 * i + 1 : i + NLIMBS].add(a[i] * d[i + 1 :])
    return out


def _carry(x: jnp.ndarray, rounds: int) -> jnp.ndarray:
    """Carry-save rounds.  Exact for negative limbs (arithmetic shift), and
    the top limb keeps its overflow in place — no value is ever dropped."""
    for _ in range(rounds):
        lo = x & MASK
        hi = x >> RADIX
        y = lo.at[1:].add(hi[:-1])
        x = y.at[-1].add(hi[-1] << RADIX)
    return x


def _pad(x: jnp.ndarray, n: int) -> jnp.ndarray:
    return jnp.concatenate(
        [x, jnp.zeros((n,) + x.shape[1:], dtype=jnp.int32)], axis=0
    )


def tighten(x: jnp.ndarray, rounds: int = 1) -> jnp.ndarray:
    """Re-tighten loose limbs (|limb| <= 2^17 -> < 2^12 after one round)."""
    return _carry(x, rounds)


def _fold_once(wide: jnp.ndarray) -> jnp.ndarray:
    """Fold limbs >= NLIMBS back via 2^264 ≡ FOLD (mod p).

    Contract: |limb| <= 2^15 (so partials hi*FOLD <= 2^26, 4-term sums
    <= 2^28).  Output: (NLIMBS, ...) with |limb| <= 2^28-ish (loose; callers
    carry right after).
    """
    lo = wide[:NLIMBS]
    hi = wide[NLIMBS:]
    k = hi.shape[0]
    out = _pad(lo, max(0, k + _FN - 1 - NLIMBS))
    for i in range(_FN):
        out = out.at[i : i + k].add(FOLD[i] * hi)
    if out.shape[0] > NLIMBS:
        out = _carry(_pad(out, 1), 2)
        return _fold_once(out)
    return out


def _fold_top(x: jnp.ndarray) -> jnp.ndarray:
    """Carry into a 25th limb, then fold it back via 2^264 ≡ FOLD (mod p):
    (NLIMBS, ...) in, (NLIMBS, ...) out with the top limb's overflow folded
    into the low _FN limbs.  The shared tail of _tight24 / mul /
    mul_small_red — the most bound-sensitive snippet in the module, so it
    lives in exactly one place."""
    x = _carry(_pad(x, 1), 1)
    hi = x[NLIMBS]
    x = x[:NLIMBS]
    return x.at[:_FN].add(FOLD[:, None] * hi[None])


def _tight24(a: jnp.ndarray) -> jnp.ndarray:
    """Bring EVERY limb (including the top one) under ~2^12 without losing
    value.  Needed because plain carry rounds preserve (never shrink) the
    top limb."""
    return _carry(_fold_top(a), 1)


def _reduce_wide(wide: jnp.ndarray) -> jnp.ndarray:
    """The shared reduction tail of every product: 47 loose product limbs
    -> 24 limbs, every |limb| <= 2^12.  Bounds as audited in mul's
    docstring (this is the exact op sequence the original mul inlined)."""
    wide = _carry(_pad(wide, 1), 2)  # 48 limbs, |v| <= 2^12 (top <= 2^15)
    x = _fold_once(wide)  # 24 limbs, loose <= 2^28
    x = _carry(x, 1)  # <= 2^12, top <= 2^17-ish
    return _carry(_fold_top(x), 1)  # fold residual top overflow; <= 2^12


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Modular multiply mod p (general loose inputs; see mul_t for the
    pre-tight fast path).

    Input contract (audited at every call site in curve.py/kernel.py):
    |non-top limbs| <= 2^19, |top limb| <= 2^15, and for the PAIR
    top(a)*top(b) <= 2^30.  One internal carry round then brings non-top
    limbs under 2^11.3 while preserving each top limb, so every
    anti-diagonal convolution sum stays below 2^31 (int32-exact):
    mid diagonals <= 24*2^22.6, the single top*top term <= 2^30, mixed
    top terms <= 2*2^15*2^11.3.  Output loose with |limb| <= 2^12, non-top
    <= 2^11.2, and value magnitude < 2^265.  Exact modulo p, sign-correct.

    (Operands that are sums of a few mul outputs satisfy this trivially:
    mul outputs have every limb <= 2^12.  The B3/8 scalings are the only
    spots that need care — see mul_small_red and the audit notes in
    curve.py.)
    """
    a = _carry(a, 1)
    b = _carry(b, 1)
    return _reduce_wide(_conv(a, b))  # sums < 2^28.6 (see contract)


def mul_t(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``mul`` for pre-tight operands: skips the two input carry rounds.

    Contract (stricter than mul's, audited per call site in curve.py):
    EVERY limb of both inputs |<= 2^13| — raw mul outputs (<= 2^12) and
    single point coordinates (sums of <= 2 mul outputs) qualify; wider sums
    and mul_small_red outputs do NOT.  Convolution bound: 24 * 2^13 * 2^13
    = 2^30.6 < 2^31.  Output identical contract to mul's.
    """
    return _reduce_wide(_conv(a, b))


def sqr(a: jnp.ndarray) -> jnp.ndarray:
    """Modular square — mul(a, a)'s contract, via the dedicated
    half-product path (the pow ladders spend most of their muls here).
    The pairwise top*top <= 2^30 condition reduces to |top limb| <= 2^15,
    which mul's contract already requires.  Bit-identical output to
    mul(a, a)."""
    a = _carry(a, 1)
    return _reduce_wide(_sqr_conv(a))


def sqr_t(a: jnp.ndarray) -> jnp.ndarray:
    """``sqr`` for pre-tight operands — mul_t's contract (every |limb|
    <= 2^13).  The doubled cross partials 2*a_i*a_j <= 2^27 and the
    per-position sums equal mul_t's convolution sums (< 2^30.6)."""
    return _reduce_wide(_sqr_conv(a))


def mul_small_red(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """Scale by a small constant AND reduce so the result is a valid
    ``mul`` input even though |value| grows past 2^268: carry into a 25th
    limb, fold it back via 2^264 ≡ FOLD (mod p).

    Contract: |a limbs| <= 2^15, |k| <= 32.  Output: value < 2^265 and
    |top limb| <= 2^12 always; non-top limbs <= 2^11 + 2^11*(value(a*k)>>264).
    At the actual call sites (a is a mul output: every limb <= 2^12; k = B3
    = 21) that is <= 2^16.6 — so 3-term sums of such outputs (<= 2^18.3)
    still sit inside mul's |non-top| <= 2^19 input contract (the pt_double
    audit relies on this).
    """
    return _fold_top(a * k)


# ---------- lazy-reduction wide-accumulator API ---------------------------
#
# A "wide" value is the unreduced 47-limb convolution of one product —
# exactly what _reduce_wide consumes.  Wides of the SAME expression may be
# summed limb-wise (acc_add) before the one shared reduction, eliminating
# the interior carry/fold rounds a reduce-per-product formula would pay.
# Wides are plain (47, ...) int32 arrays: negation and subtraction are
# ordinary elementwise arithmetic (value-exact, sign-correct).
#
# int32-safety of every accumulation chain is NOT argued here: the static
# bound tracker (tpunode.verify.bounds) replays each live formula over
# exact per-limb magnitude bounds and hard-fails at trace time if any
# anti-diagonal sum, accumulated wide, or reduction intermediate can
# exceed int32.  That audit — not these docstrings — is the contract.


def mul_wide(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``mul`` minus the reduction tail: one carry round per input, then
    the limb convolution.  Input contract identical to :func:`mul`'s;
    output is the (47, ...) wide for :func:`acc_add`/:func:`reduce_wide`.
    ``reduce_wide(mul_wide(a, b))`` is bit-identical to ``mul(a, b)``."""
    return _conv(_carry(a, 1), _carry(b, 1))


def mul_t_wide(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``mul_t`` minus the reduction tail (pre-tight operands, every
    |limb| <= 2^13 — :func:`mul_t`'s contract)."""
    return _conv(a, b)


def sqr_wide(a: jnp.ndarray) -> jnp.ndarray:
    """``sqr`` minus the reduction tail (mul's input contract)."""
    return _sqr_conv(_carry(a, 1))


def sqr_t_wide(a: jnp.ndarray) -> jnp.ndarray:
    """``sqr_t`` minus the reduction tail (mul_t's contract)."""
    return _sqr_conv(a)


def acc_add(*wides: jnp.ndarray) -> jnp.ndarray:
    """Sum unreduced wides limb-wise — the lazy accumulator.  Value-exact
    (int adds); the per-limb magnitude bound is the SUM of the operands'
    bounds, which the bound tracker checks against int32 at trace time."""
    out = wides[0]
    for w in wides[1:]:
        out = out + w
    return out


def reduce_wide(wide: jnp.ndarray) -> jnp.ndarray:
    """Public reduction tail: 47 loose product limbs (or an acc_add of a
    few) -> 24 limbs, every |limb| <= 2^12.  The one reduction a lazy
    expression pays."""
    return _reduce_wide(wide)


def reduce_wide_loose(wide: jnp.ndarray) -> jnp.ndarray:
    """``reduce_wide`` minus the final carry round (4 carry rounds + 2
    folds instead of 5 + 2): output limbs are LOOSE — |limb| <= ~2^12.3
    (bound-tracker-checked <= 2^13) instead of <= 2^12 — but that still
    satisfies every consumer the lazy formulas have (coordinate sums,
    mul_t_wide convolutions, mul_small_red).  The default reduction of
    the lazy pipeline: one carry round saved per product."""
    wide = _carry(_pad(wide, 1), 2)
    x = _fold_once(wide)
    x = _carry(x, 1)
    return _fold_top(x)


# ---------- exact canonicalization & comparisons ----------

# A comfortably large multiple of p added before canonicalizing so negative
# values become positive: loose values are bounded by |v| < 2^266.
_BIG_INT = ((1 << 267) // P + 1) * P
_BIG = jnp.array(_limbs_list(_BIG_INT, NLIMBS + 1), dtype=jnp.int32)[:, None]


def canonical(x: jnp.ndarray) -> jnp.ndarray:
    """Exact canonical representative in [0, p), as nonnegative limbs.

    Input: loose limbs (|limb| <= 2^13 -> |value| < 2^266).  Used only at
    equality checks (once per verification), so the long carry chains here
    are off the hot path.
    """
    x = _tight24(x)  # all limbs < ~2^12 -> |value| < 2^266
    wide = _pad(x, 1) + _BIG  # nonnegative, < 2^268
    wide = _carry(wide, NLIMBS + 4)  # canonical limbs (top limb <= 2^16)
    # fold value at the 2^256 boundary: bits 256+ are limb23>>3 and limb24
    hi = (wide[NLIMBS - 1] >> 3) + (wide[NLIMBS] << 8)
    lo = wide[:NLIMBS].at[NLIMBS - 1].set(wide[NLIMBS - 1] & 7)
    lo = lo.at[:_FN].add(C_LIMBS[:, None] * hi[None])  # += hi * (2^256 mod p)
    lo = _carry(lo, NLIMBS + 2)  # canonical, value < 2^256 + 2^47 < 2p
    for _ in range(2):
        ge_p = _ge(lo, P_LIMBS)
        lo = lo - jnp.where(ge_p, P_LIMBS, 0)
        lo = _carry(lo, NLIMBS + 1)  # resolve borrows (result nonnegative)
    return lo


def _ge(a: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    """Lexicographic >= over canonical (nonnegative, in-range) limb vectors."""
    diff = a - m
    nz = diff != 0
    idx = (NLIMBS - 1) - jnp.argmax(nz[::-1], axis=0)
    top = jnp.take_along_axis(diff, idx[None], axis=0)[0]
    return jnp.where(jnp.any(nz, axis=0), top > 0, True)


def is_zero(x: jnp.ndarray) -> jnp.ndarray:
    """value ≡ 0 (mod p)?  Exact."""
    return jnp.all(canonical(x) == 0, axis=0)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a ≡ b (mod p)?  Exact."""
    return is_zero(a - b)


def select(mask: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Branch-free ``mask ? a : b`` (mask (B,) broadcasts over the leading
    limb axis)."""
    return jnp.where(mask, a, b)
