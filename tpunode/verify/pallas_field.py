"""Mosaic-friendly secp256k1 field arithmetic for the Pallas verify kernel.

Same radix-11 / 24-limb representation, bounds, and contracts as
:mod:`field` (see its docstrings — they are the load-bearing audit), but
expressed in the subset of jnp that Pallas/Mosaic lowers well inside a TPU
kernel:

* no ``.at[...]`` dynamic-update-slices — limb shifts are static
  ``concatenate`` of row slices (sublane shifts in hardware);
* no broadcast-from-(L, 1) constants — constant columns are built with
  ``jnp.full`` rows (folded at compile time);
* fold constants are Python scalars, not device arrays.

Why it exists (the round-3 performance finding): under plain XLA the
verify kernel is per-op-overhead/HBM bound — a chained field mul costs
~430 us at batch 8192 (~0.5% VPU utilization) because every one of its
~80 small (24, B) ops round-trips through HBM.  Inside one Pallas program
the whole MSM loop runs out of VMEM/registers, so these same formulas
compile to straight-line vector code with no per-op dispatch.

Functions mirror :mod:`field`'s API (``mul``/``mul_t``/``mul_small_red``/
``sqr``/``sqr_t``/``canonical``/``is_zero``/``eq``) so :mod:`curve`'s
audited RCB formulas can be reused unchanged via their ``F=`` parameter
(one formulation: shift-add convolution, half-product squaring).
Exactness is pinned against Python ints and :mod:`field` in
tests/test_pallas_kernel.py.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import field as F

RADIX = F.RADIX
NLIMBS = F.NLIMBS
MASK = F.MASK

_FOLD = [int(x) for x in F.FOLD]  # 2^264 mod p, 4 limbs, as Python ints
_C = [int(x) for x in F.C_LIMBS]  # 2^256 mod p, 4 limbs
_FN = F._FN
_P_LIMBS = [int(x) for x in F.P_LIMBS[:, 0]]
_BIG_LIMBS = [int(x) for x in F._BIG[:, 0]]  # 25 limbs


def _z(rows: int, b: int) -> jnp.ndarray:
    return jnp.zeros((rows, b), jnp.int32)


def _cat(*parts: jnp.ndarray) -> jnp.ndarray:
    """Sublane concatenate, dropping zero-row segments (Mosaic requires
    positive vector sizes; a (0, B) operand is a lowering error)."""
    live = [p for p in parts if p.shape[0] > 0]
    return live[0] if len(live) == 1 else jnp.concatenate(live, axis=0)


def const_col(ints, b: int) -> jnp.ndarray:
    """Constant limb column broadcast over ``b`` lanes, shape (len, b)."""
    return jnp.concatenate(
        [jnp.full((1, b), int(v), jnp.int32) for v in ints], axis=0
    )


def _carry(x: jnp.ndarray, rounds: int) -> jnp.ndarray:
    """field._carry in concatenate form: exact for negative limbs, top
    limb keeps its overflow in place."""
    b = x.shape[-1]
    for _ in range(rounds):
        lo = x & MASK
        hi = x >> RADIX
        y = lo + _cat(_z(1, b), hi[:-1])
        x = _cat(y[:-1], y[-1:] + (hi[-1:] << RADIX))
    return x


def tighten(x: jnp.ndarray, rounds: int = 1) -> jnp.ndarray:
    return _carry(x, rounds)


def _tree_sum(terms: list) -> jnp.ndarray:
    while len(terms) > 1:  # balanced reduction: short dependency chains
        terms = [
            terms[j] + terms[j + 1] if j + 1 < len(terms) else terms[j]
            for j in range(0, len(terms), 2)
        ]
    return terms[0]


def _conv(a: jnp.ndarray, b_: jnp.ndarray) -> jnp.ndarray:
    """Limb convolution (24, B) x (24, B) -> (47, B) as a tree sum of 24
    sublane-shifted broadcast products (same partials as field._conv)."""
    b = a.shape[-1]
    terms = []
    for i in range(NLIMBS):
        t = a[i : i + 1] * b_  # (NLIMBS, B): row-broadcast multiply
        terms.append(_cat(_z(i, b), t, _z(NLIMBS - 1 - i, b)))
    return _tree_sum(terms)


def _sqr_conv(a: jnp.ndarray) -> jnp.ndarray:
    """field._sqr_conv in concatenate form: out[i+j] += (2-δij)·a_i·a_j
    over i <= j — ~half the partial products, per-position sums identical
    to _conv(a, a)'s."""
    b = a.shape[-1]
    d = a + a
    terms = []
    for i in range(NLIMBS):
        row = a[i : i + 1]
        t = row * (_cat(row, d[i + 1 :]) if i + 1 < NLIMBS else row)
        terms.append(_cat(_z(2 * i, b), t, _z(NLIMBS - 1 - i, b)))
    return _tree_sum(terms)


def _pad(x: jnp.ndarray, n: int) -> jnp.ndarray:
    return _cat(x, _z(n, x.shape[-1]))


def _fold_once(wide: jnp.ndarray) -> jnp.ndarray:
    """field._fold_once with scalar fold constants (same bounds)."""
    b = wide.shape[-1]
    lo = wide[:NLIMBS]
    hi = wide[NLIMBS:]
    k = hi.shape[0]
    width = max(NLIMBS, k + _FN - 1)
    out = _pad(lo, width - NLIMBS)
    for i in range(_FN):
        out = out + _cat(_z(i, b), _FOLD[i] * hi, _z(width - i - k, b))
    if out.shape[0] > NLIMBS:
        out = _carry(_pad(out, 1), 2)
        return _fold_once(out)
    return out


def _fold_top(x: jnp.ndarray) -> jnp.ndarray:
    """field._fold_top: carry into a 25th limb, fold it back via
    2^264 ≡ FOLD (mod p)."""
    b = x.shape[-1]
    x = _carry(_pad(x, 1), 1)
    hi = x[NLIMBS : NLIMBS + 1]  # (1, B)
    x = x[:NLIMBS]
    fold_rows = _cat(*[_FOLD[i] * hi for i in range(_FN)])
    return x + _cat(fold_rows, _z(NLIMBS - _FN, b))


def _tight24(a: jnp.ndarray) -> jnp.ndarray:
    return _carry(_fold_top(a), 1)


def _reduce_wide(wide: jnp.ndarray) -> jnp.ndarray:
    """field._reduce_wide: the shared 47-limb -> 24-limb reduction tail."""
    wide = _carry(_pad(wide, 1), 2)
    x = _fold_once(wide)
    x = _carry(x, 1)
    return _carry(_fold_top(x), 1)


def mul(a: jnp.ndarray, b_: jnp.ndarray) -> jnp.ndarray:
    """Modular multiply — identical contract to field.mul."""
    a = _carry(a, 1)
    b_ = _carry(b_, 1)
    return _reduce_wide(_conv(a, b_))


def mul_t(a: jnp.ndarray, b_: jnp.ndarray) -> jnp.ndarray:
    """field.mul_t: pre-tight operands (every |limb| <= 2^13)."""
    return _reduce_wide(_conv(a, b_))


def sqr(a: jnp.ndarray) -> jnp.ndarray:
    """field.sqr: half-product squaring."""
    a = _carry(a, 1)
    return _reduce_wide(_sqr_conv(a))


def sqr_t(a: jnp.ndarray) -> jnp.ndarray:
    """field.sqr_t: squaring for pre-tight operands (mul_t's contract)."""
    return _reduce_wide(_sqr_conv(a))


def mul_small_red(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """field.mul_small_red: scale by small constant and fold the top."""
    return _fold_top(a * k)


# ---------- lazy-reduction wide-accumulator API ---------------------------
# Mirrors field.py's wide API in concatenate form so curve.py's
# formula bodies run unchanged inside the Pallas kernel (the same ``F=``
# seam).  Safety: identical op sequences, identical bounds — the ONE
# bound-tracker audit (tpunode.verify.bounds) covers both namespaces.


def mul_wide(a: jnp.ndarray, b_: jnp.ndarray) -> jnp.ndarray:
    """field.mul_wide: mul minus the reduction tail -> (47, B) wide."""
    return _conv(_carry(a, 1), _carry(b_, 1))


def mul_t_wide(a: jnp.ndarray, b_: jnp.ndarray) -> jnp.ndarray:
    """field.mul_t_wide: pre-tight operands, bare convolution."""
    return _conv(a, b_)


def sqr_wide(a: jnp.ndarray) -> jnp.ndarray:
    """field.sqr_wide."""
    return _sqr_conv(_carry(a, 1))


def sqr_t_wide(a: jnp.ndarray) -> jnp.ndarray:
    """field.sqr_t_wide."""
    return _sqr_conv(a)


def acc_add(*wides: jnp.ndarray) -> jnp.ndarray:
    """field.acc_add: limb-wise sum of unreduced wides."""
    out = wides[0]
    for w in wides[1:]:
        out = out + w
    return out


def reduce_wide(wide: jnp.ndarray) -> jnp.ndarray:
    """field.reduce_wide: the one reduction a lazy expression pays."""
    return _reduce_wide(wide)


def reduce_wide_loose(wide: jnp.ndarray) -> jnp.ndarray:
    """field.reduce_wide_loose: the reduction tail minus its final carry
    round — the lazy pipeline's default reduction."""
    wide = _carry(_pad(wide, 1), 2)
    x = _fold_once(wide)
    x = _carry(x, 1)
    return _fold_top(x)


# ---------- exact canonicalization & comparisons ----------


def canonical(x: jnp.ndarray) -> jnp.ndarray:
    """field.canonical in Mosaic-friendly form (same algorithm/bounds)."""
    b = x.shape[-1]
    x = _tight24(x)
    wide = _pad(x, 1) + const_col(_BIG_LIMBS, b)
    wide = _carry(wide, NLIMBS + 4)
    hi = (wide[NLIMBS - 1 : NLIMBS] >> 3) + (wide[NLIMBS : NLIMBS + 1] << 8)
    top = wide[NLIMBS - 1 : NLIMBS] & 7
    lo = _cat(wide[: NLIMBS - 1], top)
    c_rows = _cat(*[_C[i] * hi for i in range(_FN)])
    lo = lo + _cat(c_rows, _z(NLIMBS - _FN, b))
    lo = _carry(lo, NLIMBS + 2)
    p_col = const_col(_P_LIMBS, b)
    for _ in range(2):
        ge_p = _ge_p(lo)  # (1, B) bool
        lo = lo - jnp.where(ge_p, p_col, 0)
        lo = _carry(lo, NLIMBS + 1)
    return lo


def _ge_p(a: jnp.ndarray) -> jnp.ndarray:
    """Lexicographic a >= p over canonical nonnegative limbs -> (1, B)."""
    gt = jnp.zeros((1, a.shape[-1]), jnp.bool_)
    eq = jnp.ones((1, a.shape[-1]), jnp.bool_)
    for i in range(NLIMBS - 1, -1, -1):
        ai = a[i : i + 1]
        gt = gt | (eq & (ai > _P_LIMBS[i]))
        eq = eq & (ai == _P_LIMBS[i])
    return gt | eq


def is_zero(x: jnp.ndarray) -> jnp.ndarray:
    """value ≡ 0 (mod p)?  Exact.  Returns (1, B) bool."""
    c = canonical(x)
    return jnp.sum(jnp.abs(c), axis=0, keepdims=True) == 0


def eq(a: jnp.ndarray, b_: jnp.ndarray) -> jnp.ndarray:
    return is_zero(a - b_)
