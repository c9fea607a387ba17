"""secp256k1 group operations on TPU: complete projective formulas.

Points are projective ``(X : Y : Z)`` triples of limb vectors, stored as one
array of shape ``(3, NLIMBS, B)`` — limb-major layout (see field.py): the
batch axis is minor-most so it lands in TPU lanes.  Infinity is
``(0 : 1 : 0)``, shape ``(3, NLIMBS, 1)``, broadcasting over the batch.

We use the Renes–Costello–Batina *complete* addition/doubling formulas for
prime-order short-Weierstrass curves with a = 0 (RCB'16, Algorithms 7 and 9,
b3 = 3*b = 21 for secp256k1).  Complete formulas are branch-free and correct
for EVERY input pair — including infinity and P = ±Q — which is exactly what
a jit-compiled, batched, consensus-critical verifier wants: no data-dependent
control flow, no exceptional-case equality tests in the hot loop, bit-exact
results.

This replaces the group layer of libsecp256k1 (SURVEY.md C9) with a design
chosen for XLA rather than a port: libsecp256k1 uses branchy Jacobian
formulas + constant-time tricks; here completeness does that job for free.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import field as F

__all__ = [
    "B3",
    "INFINITY",
    "pt_add",
    "pt_double",
    "make_point",
    "is_infinity",
]

B3 = 21  # 3 * b for y^2 = x^3 + 7


def make_point(x: jnp.ndarray, y: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    return jnp.stack([x, y, z], axis=0)


def _mk(F_ns):
    """The point constructor for a formula's namespace: the namespace's
    own ``make_point`` when it has one (the bound tracker builds plain
    lists), :func:`make_point` otherwise (jnp stacking for the real
    field namespaces)."""
    return getattr(F_ns, "make_point", make_point)


INFINITY = make_point(F.ZERO, F.ONE, F.ZERO)


def is_infinity(p: jnp.ndarray) -> jnp.ndarray:
    """Z ≡ 0 (mod p) — exact; a finite point can never have Z ≡ 0."""
    return F.is_zero(p[2])


def pt_add(p: jnp.ndarray, q: jnp.ndarray, F=F) -> jnp.ndarray:
    """Complete addition (RCB'16 Algorithm 7, a = 0): 12 muls, no exceptions.

    ``F`` is the field-arithmetic namespace (field.py's wide API:
    mul_wide/mul_t_wide/acc_add/reduce_wide_loose/tighten/mul_small_red
    with field.py's contracts); the Pallas kernel passes its
    Mosaic-friendly implementation so both device paths share this one
    body.  Lazy reduction, three fused carry/fold levers —

    * the three output coordinates, each a ±-sum of two products,
      accumulate as unreduced 47-limb wides and pay ONE reduction each
      (3 reductions saved over a reduce-per-product body);
    * every reduction is the LOOSE tail (``reduce_wide_loose``: one
      carry round cheaper; outputs <= ~2^12.3, inside every consumer's
      contract);
    * shared tail operands get ONE hoisted carry round each instead of
      a fresh pair inside every full mul (6 rounds instead of 12).

    int32 safety and the 2^13 coordinate closure (inputs are sums of at
    most two reduced products; outputs must fit back in) are CHECKED at
    trace time by tpunode.verify.bounds, not argued here."""
    X1, Y1, Z1 = p[0], p[1], p[2]
    X2, Y2, Z2 = q[0], q[1], q[2]
    rw = F.reduce_wide_loose

    t0 = rw(F.mul_t_wide(X1, X2))
    t1 = rw(F.mul_t_wide(Y1, Y2))
    t2 = rw(F.mul_t_wide(Z1, Z2))
    t3 = rw(F.mul_wide(X1 + Y1, X2 + Y2))
    t3 = t3 - (t0 + t1)  # = X1*Y2 + X2*Y1
    t4 = rw(F.mul_wide(Y1 + Z1, Y2 + Z2))
    t4 = t4 - (t1 + t2)
    t5 = rw(F.mul_wide(X1 + Z1, X2 + Z2))
    t5 = t5 - (t0 + t2)  # = X1*Z2 + X2*Z1
    t2_b3 = F.mul_small_red(t2, B3)
    # hoisted carry rounds: each shared operand tightens ONCE, then
    # every product below is a bare convolution (mul_t_wide)
    t3 = F.tighten(t3)
    t4 = F.tighten(t4)
    t0_3 = F.tighten(t0 + t0 + t0)  # 3*X1*X2
    z3s = F.tighten(t1 + t2_b3)
    t1m = F.tighten(t1 - t2_b3)
    y3r = F.tighten(F.mul_small_red(t5, B3))  # b3*(X1*Z2 + X2*Z1)
    x3 = rw(F.mul_t_wide(t3, t1m) - F.mul_t_wide(t4, y3r))
    y3 = rw(F.acc_add(F.mul_t_wide(t1m, z3s), F.mul_t_wide(y3r, t0_3)))
    z3 = rw(F.acc_add(F.mul_t_wide(z3s, t4), F.mul_t_wide(t0_3, t3)))
    return _mk(F)(x3, y3, z3)


def pt_double(p: jnp.ndarray, F=F) -> jnp.ndarray:
    """Complete doubling (RCB'16 Algorithm 9, a = 0): 6 muls + 2 squarings.

    ``F`` as in :func:`pt_add`.  The two squarings (Y^2, Z^2) go through
    the half-product ``F.sqr_t_wide``.  The interior ``b3·Z²·8Y²``
    product never materializes reduced — it fuses into y3's accumulation
    (one reduction saved) — and the three shared operands (8Y², the
    b3·Z² scaling, and the t0 - 3·t2 difference) each get ONE hoisted
    carry round instead of per-mul input carries."""
    X, Y, Z = p[0], p[1], p[2]
    rw = F.reduce_wide_loose

    t0 = rw(F.sqr_t_wide(Y))
    z8 = F.tighten(t0 * 8)  # 8Y^2: tightened once, feeds two products
    t1 = rw(F.mul_t_wide(Y, Z))
    t2 = F.tighten(F.mul_small_red(rw(F.sqr_t_wide(Z)), B3))  # b3*Z^2
    y3s = t0 + t2
    t0m = F.tighten(t0 - (t2 + t2 + t2))
    z3 = rw(F.mul_t_wide(t1, z8))
    y3 = rw(F.acc_add(F.mul_t_wide(t2, z8), F.mul_t_wide(t0m, y3s)))
    t1b = rw(F.mul_t_wide(X, Y))
    x3 = rw(F.mul_t_wide(t0m, t1b))
    x3 = x3 + x3
    return _mk(F)(x3, y3, z3)
