"""Static per-limb bound tracker for the field pipeline.

field.py's int32-safety story used to live in docstrings ("every
anti-diagonal sum stays below 2^31", "|non-top limb| <= 2^19", ...) and
hand audits in curve.py.  This module turns that argument into CHECKED
code: :class:`BVal` carries an exact worst-case per-limb magnitude bound
(Python ints — no device work), :class:`BoundField` mirrors every field
op's real op sequence in bound space (the same carries, folds, and
convolutions, including the lazy wide-accumulator API), and every
multiply/accumulate asserts int32 headroom as it happens.

:func:`audit_formulas` replays the live RCB formulas (curve.pt_add /
pt_double — via their ``F=`` namespace parameter, the same
seam the Pallas kernel and the roofline counter use) from the window
loop's input bounds and additionally checks CLOSURE: output coordinate
bounds must fit back inside the input contract, because the MSM feeds
them back in every window.  :func:`assert_formulas_safe` is the
trace-time hook — kernel.verify_core and the Pallas kernel call it (it
is cached and costs microseconds), so a formula edit
that violates int32 headroom fails the very first trace with a
:class:`BoundOverflow` naming the op, not a silent wrong verdict on
device.

Bound semantics: a bound B means |value| <= B for every program input
allowed by the contracts.  Magnitudes only (signs are free in this
representation — subtraction is addition of magnitudes), interval steps
are conservative but exact integer arithmetic:

* ``x & MASK``   -> bound MASK (a negative x masks to up to MASK);
* ``x >> RADIX`` -> bound (B + MASK) >> RADIX (arithmetic shift of a
  negative rounds toward -inf);
* convolution    -> exact anti-diagonal sums of pairwise bound products
  (the full and the half-product squaring convolutions compute the same
  sums; the half-product's doubled partials are checked besides).
"""

from __future__ import annotations

import numpy as np

from . import field as F

__all__ = [
    "BoundOverflow",
    "BVal",
    "BoundField",
    "audit_formulas",
    "assert_formulas_safe",
    "COORD_BOUND",
]

_INT32_MAX = (1 << 31) - 1
_MASK = F.MASK
_RADIX = F.RADIX
_NLIMBS = F.NLIMBS
_FOLD = np.asarray(F.FOLD).tolist()  # numpy: importable inside a trace
_FN = F._FN

# The window loop's input contract (audited in curve.py's docstrings and
# now CHECKED here): accumulator/table point coordinates are sums of at
# most two reduced products — every |limb| <= 2^13.
COORD_BOUND = 1 << 13


class BoundOverflow(AssertionError):
    """A tracked chain can exceed int32 (or a documented output contract)
    for some contract-legal input."""


def _ck(v: int, what: str) -> int:
    if v > _INT32_MAX:
        raise BoundOverflow(
            f"{what}: worst-case |value| {v} = 2^{v.bit_length() - 1}.x "
            f"exceeds int32 (2^31 - 1)"
        )
    return v


class BVal:
    """A field value known only by per-limb magnitude bounds."""

    __slots__ = ("b",)

    def __init__(self, bounds):
        self.b = tuple(int(x) for x in bounds)

    @classmethod
    def uniform(cls, bound: int, n: int = _NLIMBS) -> "BVal":
        return cls((bound,) * n)

    @property
    def width(self) -> int:
        return len(self.b)

    def max(self) -> int:
        return max(self.b)

    # -- arithmetic the formulas use directly on values/wides ------------
    def __add__(self, other: "BVal") -> "BVal":
        if not isinstance(other, BVal):
            return NotImplemented
        assert len(self.b) == len(other.b), "width mismatch in add"
        return BVal(_ck(a + c, "add") for a, c in zip(self.b, other.b))

    __radd__ = __add__

    def __sub__(self, other: "BVal") -> "BVal":
        return self.__add__(other)  # magnitudes: |a - b| <= |a| + |b|

    __rsub__ = __sub__

    def __neg__(self) -> "BVal":
        return self

    def __mul__(self, k: int) -> "BVal":
        if not isinstance(k, int):
            return NotImplemented
        return BVal(_ck(x * abs(k), "scale") for x in self.b)

    __rmul__ = __mul__


def _carry(x: BVal, rounds: int) -> BVal:
    """field._carry in bound space: lo = x & MASK, hi = x >> RADIX, the
    top limb keeps its overflow in place."""
    b = list(x.b)
    for _ in range(rounds):
        lo = [_MASK if v else 0 for v in b]
        hi = [(v + _MASK) >> _RADIX for v in b]
        y = [lo[0]] + [
            _ck(lo[i] + hi[i - 1], "carry add") for i in range(1, len(b))
        ]
        # top limb: lo[-1] + (hi[-1] << RADIX) reconstructs the old top
        # EXACTLY ((x & MASK) + (x >> R << R) == x), so its bound is the
        # old bound itself — only the neighbor's carry-in adds.
        y[-1] = _ck(b[-1] + (hi[-2] if len(b) > 1 else 0), "carry top")
        b = y
    return BVal(b)


def _pad(x: BVal, n: int) -> BVal:
    return BVal(x.b + (0,) * n)


def _conv(a: BVal, b: BVal, sqr: bool = False) -> BVal:
    """Anti-diagonal sums of pairwise bound products.  ``sqr``
    additionally checks the half-product path's DOUBLED cross partials
    (2*a_i*a_j must fit int32 individually, not just the sums)."""
    n = len(a.b)
    out = [0] * (2 * n - 1)
    for i in range(n):
        for j in range(n):
            p = _ck(a.b[i] * b.b[j], "conv partial")
            if sqr and i != j:
                _ck(2 * p, "sqr doubled partial")
            out[i + j] = _ck(out[i + j] + p, "conv sum")
    return BVal(out)


def _fold_once(wide: BVal) -> BVal:
    lo = BVal(wide.b[:_NLIMBS])
    hi = wide.b[_NLIMBS:]
    k = len(hi)
    out = list(_pad(lo, max(0, k + _FN - 1 - _NLIMBS)).b)
    for i in range(_FN):
        for j in range(k):
            out[i + j] = _ck(
                out[i + j] + _ck(_FOLD[i] * hi[j], "fold partial"),
                "fold sum",
            )
    o = BVal(out)
    if o.width > _NLIMBS:
        return _fold_once(_carry(_pad(o, 1), 2))
    return o


def _fold_top(x: BVal) -> BVal:
    x = _carry(_pad(x, 1), 1)
    hi = x.b[_NLIMBS]
    b = list(x.b[:_NLIMBS])
    for i in range(_FN):
        b[i] = _ck(b[i] + _ck(_FOLD[i] * hi, "fold_top partial"), "fold_top")
    return BVal(b)


def _reduce_wide(wide: BVal) -> BVal:
    """field._reduce_wide in bound space, asserting its DOCUMENTED output
    contract (every |limb| <= 2^12) — the bound comment at
    field.py's _reduce_wide, now enforced."""
    w = _carry(_pad(wide, 1), 2)
    x = _fold_once(w)
    x = _carry(x, 1)
    out = _carry(_fold_top(x), 1)
    if out.max() > (1 << 12):
        raise BoundOverflow(
            f"reduce_wide output bound {out.max()} exceeds the documented "
            f"|limb| <= 2^12 contract"
        )
    return out


class BoundField:
    """field.py's namespace API over :class:`BVal` — drop-in for the
    ``F=`` parameter of curve.py's formulas.  Every op replays the real
    implementation's op sequence on bounds and int32-checks each step."""

    RADIX = _RADIX
    NLIMBS = _NLIMBS
    MASK = _MASK

    def mul(self, a: BVal, b: BVal) -> BVal:
        return _reduce_wide(_conv(_carry(a, 1), _carry(b, 1)))

    def mul_t(self, a: BVal, b: BVal) -> BVal:
        return _reduce_wide(_conv(a, b))

    def sqr(self, a: BVal) -> BVal:
        a = _carry(a, 1)
        return _reduce_wide(_conv(a, a, sqr=True))

    def sqr_t(self, a: BVal) -> BVal:
        return _reduce_wide(_conv(a, a, sqr=True))

    def mul_small_red(self, a: BVal, k: int) -> BVal:
        return _fold_top(a * k)

    def mul_wide(self, a: BVal, b: BVal) -> BVal:
        return _conv(_carry(a, 1), _carry(b, 1))

    def mul_t_wide(self, a: BVal, b: BVal) -> BVal:
        return _conv(a, b)

    def sqr_wide(self, a: BVal) -> BVal:
        a = _carry(a, 1)
        return _conv(a, a, sqr=True)

    def sqr_t_wide(self, a: BVal) -> BVal:
        return _conv(a, a, sqr=True)

    def acc_add(self, *wides: BVal) -> BVal:
        out = wides[0]
        for w in wides[1:]:
            out = out + w
        return out

    def reduce_wide(self, w: BVal) -> BVal:
        return _reduce_wide(w)

    def reduce_wide_loose(self, w: BVal) -> BVal:
        """field.reduce_wide_loose: same tail minus the final carry;
        output must stay under the COORD closure bound."""
        x = _carry(_pad(w, 1), 2)
        x = _fold_once(x)
        x = _carry(x, 1)
        out = _fold_top(x)
        if out.max() > COORD_BOUND:
            raise BoundOverflow(
                f"reduce_wide_loose output bound {out.max()} exceeds the "
                f"documented loose |limb| <= 2^13 contract"
            )
        return out

    def tighten(self, x: BVal, rounds: int = 1) -> BVal:
        return _carry(x, rounds)

    # points stay plain lists so formula bodies can build/index them
    # without jnp (curve.py fetches make_point off the namespace when
    # the namespace provides one)
    def make_point(self, x: BVal, y: BVal, z: BVal) -> list:
        return [x, y, z]


def _coord_point(bound: int = COORD_BOUND) -> list:
    c = BVal.uniform(bound)
    return [c, c, c]


def audit_formulas() -> dict:
    """Replay the live pt_add / pt_double bodies from the window loop's
    input bounds; raise :class:`BoundOverflow` if any step can exceed
    int32 or an output coordinate bound escapes the COORD_BOUND closure
    the MSM relies on.  Returns the per-formula peak output bounds."""
    from .curve import pt_add, pt_double

    bf = BoundField()
    p = _coord_point()
    out = {}
    for name, res in (
        ("pt_add", pt_add(p, p, F=bf)),
        ("pt_double", pt_double(p, F=bf)),
    ):
        peak = max(c.max() for c in res)
        if peak > COORD_BOUND:
            raise BoundOverflow(
                f"{name} output coordinate bound {peak} escapes the "
                f"window loop's |limb| <= 2^13 closure"
            )
        out[name] = peak
    return out


_AUDITED: dict = {}


def assert_formulas_safe() -> None:
    """Trace-time hook: audit the live formulas once (a cached no-op
    after the first call).  Raises BoundOverflow — failing the trace —
    when a formula edit breaks int32 headroom."""
    if not _AUDITED:
        _AUDITED.update(audit_formulas())
