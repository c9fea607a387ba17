"""The TPU batch ECDSA verification kernel.

Verifies B signatures at once: for each signature ``(Q, z, r, s)`` compute
``R = u1*G + u2*Q`` (``u1 = z/s``, ``u2 = r/s`` mod n) and accept iff
``R != O`` and ``x(R) ≡ r (mod n)`` — the capability of libsecp256k1's
``secp256k1_ecdsa_verify`` (SURVEY.md C9), redesigned TPU-first:

* **Host prep** (cheap, Python ints): range checks, pubkey decode, one
  Montgomery batch inversion of every ``s`` in the batch, **GLV scalar
  decomposition** (secp256k1's cube-root endomorphism ``φ(x,y) = (βx, y)
  = λ·(x,y)``): each 256-bit scalar splits into two signed ~128-bit
  halves, so the device loop runs 33 windows instead of 64 — a ~1.4x cut
  in point operations for the cost of two extra table selects per window.
* **Device MSM** (the FLOPs): Shamir's trick over 33 interleaved 4-bit
  windows of the four half-scalars — ``lax.scan`` over windows, each step
  4 complete doublings + 4 complete additions with branch-free select-tree
  table selects (no gathers with data-dependent control flow, no
  recompilation: shapes are static).  Scalar signs are folded in by
  conditionally negating the selected table entry's Y (branch-free
  select).  Per-signature 16-entry
  tables of Q and λQ multiples are built on device (λQ's table is Q's
  with X scaled by β — the endomorphism is additive); the G and λG tables
  are compile-time constants.
* **Layout**: limb-major / batch-minor everywhere (see field.py) so the
  batch dim lands in TPU lanes with zero padding.
* **No inversions on device**: the affine check ``x(R) = r`` is done
  projectively as ``X ≡ r_cand * Z (mod p)`` for the (at most two) valid
  candidates ``r`` and ``r + n``.

Everything is exact integer math; results are bit-identical to the CPU
oracle (tested property-style in tests/test_kernel.py).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..metrics import metrics
from ..trace import span
from . import bounds as _bounds
from . import field as F
from .curve import INFINITY, make_point, pt_add, pt_double
from .ecdsa_cpu import CURVE_N, CURVE_P, GENERATOR, Point

__all__ = [
    "WINDOWS",
    "WINDOW_BITS",
    "LAMBDA",
    "BETA",
    "glv_split",
    "kernel_modes",
    "prepare_batch",
    "expand_lane",
    "verify_core",
    "verify_device",
    "verify_batch_tpu",
    "dispatch_batch_tpu",
    "collect_verdicts",
    "PreparedBatch",
]


# ONE formulation (PR 29 read every alternative on the chip and deleted the
# losers — PERF.md §6): shift-add limb products, half-product squaring,
# lazy reduction, projective tables, select-tree table selects, lax.scan
# pow ladders, 4-bit windows.
WINDOW_BITS = 4
# GLV half-scalars are bounded by ~2^129 (checked per item in
# prepare_batch): 33 windows cover 132 bits at 4-bit width.
WINDOWS = 33


def kernel_modes() -> tuple:
    """HOW a batch was verified, as serve receipts bind it (receipts.py):
    the one formulation's tuple, a literal kept byte-identical to what
    receipts carried while the formulation was selectable, so old and new
    receipts audit alike.  It keys no cache."""
    return ("shift_add", "half", "lazy", "projective", "tree", "scan", 4)


# --- the secp256k1 endomorphism (standard public constants) ---------------
# φ(x, y) = (β·x, y) equals scalar multiplication by λ; λ³ ≡ 1 (mod n),
# β³ ≡ 1 (mod p).  The lattice basis (a1, b1), (a2, b2) below spans the
# kernel of (k1, k2) -> k1 + k2·λ (mod n) and has ~128-bit entries.
LAMBDA = 0x5363AD4CC05C30E0A5261C028812645A122E22EA20816678DF02967C1B23BD72
BETA = 0x7AE96A2B657C07106E64479EAC3434E99CF0497512F58995C1396C28719501EE
_A1 = 0x3086D221A7D46BCDE86C90E49284EB15
_B1 = -0xE4437ED6010E88286F547FA90ABFE4C3
_A2 = 0x114CA50F7A8E2F3F657C1108D9D44CFD8
_B2 = _A1

assert pow(LAMBDA, 3, CURVE_N) == 1
assert pow(BETA, 3, CURVE_P) == 1
assert (_A1 + _B1 * LAMBDA) % CURVE_N == 0
assert (_A2 + _B2 * LAMBDA) % CURVE_N == 0

_SEVEN = jnp.array(F.to_limbs(7))[:, None]
_BETA_L = jnp.array(F.to_limbs(BETA))[:, None]


# Barrett reciprocals: round(2^384 * b2 / n) and round(2^384 * |b1| / n).
# c_i = round(k * G_i / 2^384) equals the exact round((b*k + n/2) / n) in
# practice (and ANY c rounding keeps the decomposition exact: k1 + λ·k2 ≡ k
# holds structurally); the native prep (secp_prepare_batch) uses the same
# formula so both paths emit bit-identical digits.
_G1 = ((_B2 << 384) + CURVE_N // 2) // CURVE_N
_G2 = ((-_B1 << 384) + CURVE_N // 2) // CURVE_N


def glv_split(k: int) -> tuple[int, int]:
    """Decompose ``k`` (mod n) as ``k1 + k2·λ`` with |k1|, |k2| < ~2^129."""
    k %= CURVE_N
    c1 = (k * _G1 + (1 << 383)) >> 384
    c2 = (k * _G2 + (1 << 383)) >> 384
    k1 = k - c1 * _A1 - c2 * _A2
    k2 = -c1 * _B1 - c2 * _B2
    return k1, k2


def _table_np(base: Point) -> np.ndarray:
    """Constant table [O, P, 2P, ..., 15P] as projective limb points."""
    from .ecdsa_cpu import INFINITY as OINF, point_add

    table = np.zeros((16, 3, F.NLIMBS), dtype=np.int32)
    table[0, 1, 0] = 1  # (0 : 1 : 0)
    acc = OINF
    for k in range(1, 16):
        acc = point_add(acc, base)
        table[k, 0] = F.to_limbs(acc.x)
        table[k, 1] = F.to_limbs(acc.y)
        table[k, 2, 0] = 1
    return table


# Kept as NUMPY: a numpy constant lifts cleanly into whichever jit trace
# uses it (a jnp value first created inside a trace would be that trace's
# tracer).
G_TABLE = _table_np(GENERATOR)  # (16, 3, NLIMBS)
LG_TABLE = _table_np(
    Point(BETA * GENERATOR.x % CURVE_P, GENERATOR.y)
)  # table of λG = φ(G)

# --- the lane's wire form ---------------------------------------------------
# One C-contiguous int32 buffer ``(ROWS, B)`` a lane, batch minor: written by
# host prep (native or numpy), handed to the device in one transfer, expanded
# to digits, limbs and masks by the jitted programs (:func:`expand_lane`).
# Rows are little-endian 32-bit words down a column: HALF_WORDS for each GLV
# half-scalar magnitude |u1a|, |u1b|, |u2a|, |u2b| (132 bits used), then
# FIELD_WORDS for each of qx, qy, r1, r2, then one row of flag bits.
HALF_WORDS = 5
FIELD_WORDS = 8
FIELD_ROW0 = 4 * HALF_WORDS
FLAGS_ROW = FIELD_ROW0 + 4 * FIELD_WORDS
ROWS = FLAGS_ROW + 1
# bit positions in the flag row: the half-scalars' signs, then verify_core's
# four masks (`schnorr` / `bip340`: the lane's algorithm instead of ECDSA)
FLAG_NAMES = (
    "n1a", "n1b", "n2a", "n2b", "r2_valid", "host_valid", "schnorr", "bip340"
)
_HOST_VALID = 1 << FLAG_NAMES.index("host_valid")
_ALGO_FLAGS = 1 << FLAG_NAMES.index("schnorr") | 1 << FLAG_NAMES.index("bip340")


class PreparedBatch:
    """One host-prepared lane: ``buf`` is the ``(ROWS, B)`` int32 wire
    buffer (layout above), ``count`` the items it holds before padding."""

    __slots__ = ("buf", "count")

    def __init__(self, buf: np.ndarray, count: int):
        self.buf = buf
        self.count = count

    @property
    def schnorr_free(self) -> bool:
        """No lane carries a Schnorr/BIP340 flag: the batch may use the
        program variants with the jacobi/parity acceptance pows pruned.
        The ONE derivation every dispatch site must use — a wrong True
        would accept jacobi/parity forgeries."""
        return not np.any(self.buf[FLAGS_ROW] & _ALGO_FLAGS)


def _batch_inverse_mod_n(values: list[int]) -> list[int]:
    """Montgomery batch inversion mod n: one pow() for the whole batch.

    B == 1 short-circuits to the bare pow (ISSUE 8 bugfix sweep): the
    general path builds the prefix/suffix machinery around the same
    single pow, which is pure overhead for the singleton batches the
    mempool's per-tx admission path submits."""
    if not values:
        return []
    if len(values) == 1:
        return [pow(values[0], -1, CURVE_N)]
    prefix = []
    run = 1
    for v in values:
        run = run * v % CURVE_N
        prefix.append(run)
    inv = pow(run, -1, CURVE_N)
    out = [0] * len(values)
    for i in range(len(values) - 1, -1, -1):
        before = prefix[i - 1] if i > 0 else 1
        out[i] = inv * before % CURVE_N
        inv = inv * values[i] % CURVE_N
    return out


def _pack_words(vals: list[int], nwords: int) -> np.ndarray:
    """Nonnegative ints below 2^(32·nwords) -> ``(nwords, len)`` int32:
    each int's little-endian 32-bit words down a column."""
    buf = b"".join(v.to_bytes(4 * nwords, "little") for v in vals)
    return np.frombuffer(buf, dtype="<i4").reshape(len(vals), nwords).T


def _item_algo(item: tuple) -> Optional[str]:
    """The VerifyItem tuple's algorithm tag ("schnorr" / "bip340") or None
    for plain ECDSA."""
    if len(item) >= 5 and item[4] in ("schnorr", "bip340"):
        return item[4]
    return None


def prepare_batch(
    items: Sequence[tuple],
    pad_to: Optional[int] = None,
    native: Optional[bool] = None,
) -> PreparedBatch:
    """Host-side preparation: (pubkey|None, z, r, s[, "schnorr"]) -> the
    lane's wire buffer.  ECDSA items carry the sighash in ``z``; Schnorr
    items carry the PRECOMPUTED challenge ``e`` (u1 = s, u2 = n - e — no
    inversion).

    Invalid-by-inspection entries (bad ranges, missing/infinite pubkey) are
    masked out host-side (no ``host_valid`` bit); their columns stay zero so
    shapes stay static.  ``pad_to`` pads the batch to a fixed size to avoid
    recompilation across batches.

    ``native=None`` auto-selects the C++ fast path (secp_prepare_batch
    in native/secp256k1 — batch inversion, GLV split, word packing; a
    byte-identical buffer, ~10x the Python rate) when the library loads;
    ``native=False`` forces the pure-Python reference path.
    """
    if native is not False:
        prep = _prepare_batch_native(items, pad_to)
        if prep is not None or native is True:
            if prep is None:
                raise RuntimeError("native prep requested but unavailable")
            return prep
    count = len(items)
    size = pad_to or count
    assert size >= count
    buf = np.zeros((ROWS, size), dtype=np.int32)
    flags = [0] * count
    s_vals = []
    s_idx = []
    for i, item in enumerate(items):
        q, z, r, s = item[:4]
        if q is None or q.infinity:
            continue
        tag = _item_algo(item)
        if tag is not None:
            if not (0 <= r < CURVE_P and 0 <= s < CURVE_N):
                continue
            flags[i] = _HOST_VALID | 1 << FLAG_NAMES.index(tag)
        else:
            if not (0 < r < CURVE_N and 0 < s < CURVE_N):
                continue
            flags[i] = _HOST_VALID
            s_vals.append(s)
            s_idx.append(i)
    with span("verify.batch_inv", cpu=True):
        s_inv = _batch_inverse_mod_n(s_vals) if s_vals else []
    inv_by_idx = dict(zip(s_idx, s_inv))

    bound = 1 << (WINDOW_BITS * WINDOWS)
    # Gather per-valid-lane scalars, then pack in bulk with numpy.
    idxs: list[int] = []
    half_abs: tuple[list[int], ...] = ([], [], [], [])
    fields: tuple[list[int], ...] = ([], [], [])  # qx, qy, r1
    r2_idx: list[int] = []
    r2_vals: list[int] = []
    for i, item in enumerate(items):
        if not flags[i]:
            continue
        q, z, r, s = item[:4]
        idxs.append(i)
        ecdsa = flags[i] == _HOST_VALID
        if ecdsa:
            w = inv_by_idx[i]
            u1 = (z % CURVE_N) * w % CURVE_N
            u2 = r * w % CURVE_N
        else:
            u1 = s % CURVE_N
            u2 = (CURVE_N - z % CURVE_N) % CURVE_N
        for j, k in enumerate(glv_split(u1) + glv_split(u2)):
            if abs(k) >= bound:  # not assert: -O must not strip a consensus guard
                raise ValueError(
                    f"GLV half-scalar out of window range: |{k}| >= 2^"
                    f"{WINDOW_BITS * WINDOWS} (item {i}, half {j})"
                )
            flags[i] |= (k < 0) << j  # FLAG_NAMES[j]: n1a, n1b, n2a, n2b
            half_abs[j].append(abs(k))
        for col, v in zip(fields, (q.x, q.y, r)):
            col.append(v)
        if ecdsa and r + CURVE_N < CURVE_P:
            flags[i] |= 1 << FLAG_NAMES.index("r2_valid")
            r2_idx.append(i)
            r2_vals.append(r + CURVE_N)
    if idxs:
        ii = np.array(idxs)
        for j, vals in enumerate(half_abs):
            row = j * HALF_WORDS
            buf[row:row + HALF_WORDS, ii] = _pack_words(vals, HALF_WORDS)
        for j, vals in enumerate(fields):
            row = FIELD_ROW0 + j * FIELD_WORDS
            buf[row:row + FIELD_WORDS, ii] = _pack_words(vals, FIELD_WORDS)
    if r2_idx:
        row = FIELD_ROW0 + 3 * FIELD_WORDS
        buf[row:row + FIELD_WORDS, np.array(r2_idx)] = _pack_words(
            r2_vals, FIELD_WORDS
        )
    buf[FLAGS_ROW, :count] = flags
    return PreparedBatch(buf, count)


def _prepare_batch_native(
    items: Sequence[tuple[Optional[Point], int, int, int]],
    pad_to: Optional[int],
) -> Optional[PreparedBatch]:
    """C++ fast path for prepare_batch (None if the library is missing).

    Python packs fixed-width byte columns and prechecks ranges (so every
    packed int fits 32 bytes); the native side redoes the r/s range checks,
    then does the heavy big-int work per item and writes the wire buffer.
    """
    from .cpu_native import load_native_verifier

    nv = load_native_verifier()
    if nv is None:
        return None
    count = len(items)
    size = pad_to or count
    assert size >= count
    zero32 = b"\x00" * 32
    px, py, zs, rs, ss, present = [], [], [], [], [], bytearray(count)
    for i, item in enumerate(items):
        q, z, r, s = item[:4]
        tag = _item_algo(item)
        if q is not None and not q.infinity and (
            (0 <= r < CURVE_P and 0 <= s < CURVE_N)
            if tag is not None
            else (0 < r < CURVE_N and 0 < s < CURVE_N)
        ):
            present[i] = 1 if tag is None else (2 if tag == "schnorr" else 3)
            px.append(q.x.to_bytes(32, "big"))
            py.append(q.y.to_bytes(32, "big"))
            zs.append((z % CURVE_N).to_bytes(32, "big"))
            rs.append(r.to_bytes(32, "big"))
            ss.append(s.to_bytes(32, "big"))
        else:
            px.append(zero32)
            py.append(zero32)
            zs.append(zero32)
            rs.append(zero32)
            ss.append(zero32)
    buf = nv.prepare_lane(
        b"".join(px),
        b"".join(py),
        b"".join(zs),
        b"".join(rs),
        b"".join(ss),
        bytes(present),
        count,
        size,
    )
    return PreparedBatch(buf, count)


def prepare_batch_raw(raw, pad_to: Optional[int] = None) -> PreparedBatch:
    """Host prep from a packed :class:`tpunode.verify.raw.RawBatch` — the
    zero-Python-int path from the native extractor straight into
    ``secp_prepare_batch`` (which redoes all range checks on the raw rows).
    Falls back to the tuple path when the native library is unavailable."""
    from .cpu_native import load_native_verifier

    nv = load_native_verifier()
    if nv is None:
        return prepare_batch(raw.to_tuples(), pad_to=pad_to, native=False)
    count = len(raw)
    size = pad_to or count
    assert size >= count
    buf = nv.prepare_lane(
        raw.px.tobytes(),
        raw.py.tobytes(),
        raw.z.tobytes(),
        raw.r.tobytes(),
        raw.s.tobytes(),
        raw.present.tobytes(),
        count,
        size,
    )
    return PreparedBatch(buf, count)


def expand_lane(buf: jnp.ndarray) -> tuple:
    """The wire buffer ``(ROWS, B)`` -> :func:`verify_core`'s sixteen
    arguments, in its order: four ``(WINDOWS, B)`` MSB-first base-16 digit
    arrays, four ``(B,)`` sign masks, four ``(NLIMBS, B)`` radix-2^11 limb
    arrays, four ``(B,)`` masks.  The first operations of both jitted
    programs: shifts and masks over ~230 int32 rows, once a lane."""
    u = lax.bitcast_convert_type(buf, jnp.uint32)
    size = buf.shape[1]
    # a word's eight digits, most significant first
    shifts = np.arange(28, -1, -4, dtype=np.uint32)[None, :, None]

    def digits(row):
        # words most significant first -> (40, B), less the seven digits
        # above bit 131 (zero: prep bounds |k| < 2^132)
        w = u[row:row + HALF_WORDS][::-1]
        d = (w[:, None, :] >> shifts) & 0xF
        return d.reshape(8 * HALF_WORDS, size)[-WINDOWS:].astype(jnp.int32)

    def limbs(row):
        out = []
        for i in range(F.NLIMBS):
            w, off = divmod(F.RADIX * i, 32)
            lo = u[row + w] >> off
            if off > 32 - F.RADIX and w + 1 < FIELD_WORDS:  # straddles words
                lo = lo | (u[row + w + 1] << (32 - off))
            out.append(lo & F.MASK)
        return jnp.stack(out).astype(jnp.int32)

    flags = [(u[FLAGS_ROW] >> b) & 1 != 0 for b in range(len(FLAG_NAMES))]
    return (
        *(digits(j * HALF_WORDS) for j in range(4)),
        *flags[:4],
        *(limbs(FIELD_ROW0 + j * FIELD_WORDS) for j in range(4)),
        *flags[4:],
    )


def _build_q_table(qx: jnp.ndarray, qy: jnp.ndarray) -> jnp.ndarray:
    """Per-signature table [O, Q, 2Q, ..., 15Q], shape (16, 3, L, B):
    a sequential lax.scan of complete additions."""
    q1 = make_point(qx, qy, jnp.broadcast_to(F.ONE, qx.shape))
    inf = jnp.broadcast_to(INFINITY, q1.shape)

    def step(acc, _):
        nxt = pt_add(acc, q1)
        return nxt, nxt

    _, multiples = lax.scan(step, q1, None, length=14)  # 2Q .. 15Q
    return jnp.concatenate([inf[None], q1[None], multiples], axis=0)


def _lambda_table(q_table: jnp.ndarray) -> jnp.ndarray:
    """Table of λQ multiples from the Q table: the endomorphism is additive
    (φ(kQ) = k·φ(Q)), so scaling each entry's X by β is all it takes —
    16 field muls instead of another 14 point additions."""
    xs = q_table[:, 0]  # (16, L, B)
    lxs = jax.vmap(lambda x: F.mul(x, _BETA_L))(xs)
    return q_table.at[:, 0].set(lxs)


def select_tree16(entries: list, digits: jnp.ndarray) -> jnp.ndarray:
    """THE balanced binary select-tree fold: 15 wheres over the 16 table
    entries, level ``i`` resolving digit bit ``i`` — no integer multiplies,
    no accumulate adds.  ``entries`` are the table entries (arrays or
    VMEM-ref reads), ``digits`` any digit array that broadcasts against
    them under ``jnp.where``.  Shared by the XLA select below AND the
    Pallas ``_select16`` so the two device paths cannot diverge (one
    fold, the same way curve.py's formulas are shared via the ``F=``
    namespace)."""
    level = list(entries)
    depth = (len(level) - 1).bit_length()
    assert len(level) == 1 << depth, "select tree needs 2^k entries"
    for i in range(depth):
        bit = ((digits >> i) & 1) == 1
        level = [
            jnp.where(bit, level[2 * j + 1], level[2 * j])
            for j in range(len(level) // 2)
        ]
    return level[0]


def _select_entry(table: jnp.ndarray, digits: jnp.ndarray) -> jnp.ndarray:
    """Digit-indexed window-table select: table (16, C, L, B) or constant
    (16, C, L), digits (B,) -> (C, L, B)."""
    if table.ndim == 3:  # constant table: broadcast over lanes
        table = table[..., None]
    # digits (B,) broadcasts over each (C, L, B) entry
    return select_tree16(
        [table[t] for t in range(int(table.shape[0]))], digits
    )


def _signed(entry: jnp.ndarray, neg: jnp.ndarray) -> jnp.ndarray:
    """Negate the point iff ``neg`` (per-lane): -P = (X, -Y, Z)."""
    return entry.at[1].set(jnp.where(neg, -entry[1], entry[1]))


# Constant-exponent digit tables (64 MSB-first 4-bit digits each) for the
# two fixed powers the acceptance tests need — compile-time constants, so
# the windowed pow needs no data-dependent digit extraction.
_EULER_DIGITS = np.array(
    [((CURVE_P - 1) // 2 >> (4 * (63 - i))) & 0xF for i in range(64)],
    dtype=np.int32,
)  # Euler's criterion: jacobi via t^((p-1)/2)
_PM2_DIGITS = np.array(
    [((CURVE_P - 2) >> (4 * (63 - i))) & 0xF for i in range(64)],
    dtype=np.int32,
)  # Fermat inverse: z^(p-2)


def _pow_const(t: jnp.ndarray, digits: np.ndarray) -> jnp.ndarray:
    """Windowed 4-bit pow by a COMPILE-TIME exponent for a (L, B) limb
    column, paid once per batch for every lane uniformly (branch-free
    SPMD): a sequential lax.scan ladder over the 64 digits."""
    one = jnp.broadcast_to(F.ONE, t.shape)

    def tstep(acc, _):
        nxt = F.mul(acc, t)
        return nxt, nxt

    _, mults = lax.scan(tstep, t, None, length=14)  # t^2 .. t^15
    table = jnp.concatenate([one[None], t[None], mults], axis=0)

    def step(acc, d):
        acc = F.sqr(F.sqr(F.sqr(F.sqr(acc))))
        sel = jnp.einsum(
            "t,tlb->lb", jax.nn.one_hot(d, 16, dtype=jnp.int32), table
        )
        return F.mul(acc, sel), None

    acc, _ = lax.scan(step, one, jnp.asarray(digits))
    return acc


def _euler_is_one(t: jnp.ndarray) -> jnp.ndarray:
    """Legendre symbol check ``t^((p-1)/2) ≡ 1 (mod p)`` — the jacobi(y)
    acceptance test of BCH Schnorr."""
    return F.eq(_pow_const(t, _EULER_DIGITS), jnp.broadcast_to(F.ONE, t.shape))


def verify_core(
    d1a: jnp.ndarray,  # (33, B) int32, MSB-first base-16 digits of |u1a|
    d1b: jnp.ndarray,  # (33, B)  |u1b|  (λ half of u1)
    d2a: jnp.ndarray,  # (33, B)  |u2a|
    d2b: jnp.ndarray,  # (33, B)  |u2b|  (λ half of u2)
    n1a: jnp.ndarray,  # (B,) bool: u1a < 0
    n1b: jnp.ndarray,  # (B,) bool
    n2a: jnp.ndarray,  # (B,) bool
    n2b: jnp.ndarray,  # (B,) bool
    qx: jnp.ndarray,  # (L, B)
    qy: jnp.ndarray,  # (L, B)
    r1: jnp.ndarray,  # (L, B)
    r2: jnp.ndarray,  # (L, B)
    r2_valid: jnp.ndarray,  # (B,) bool
    host_valid: jnp.ndarray,  # (B,) bool
    schnorr: jnp.ndarray,  # (B,) bool: lane verifies BCH Schnorr
    bip340: jnp.ndarray,  # (B,) bool: lane verifies BIP340 (taproot)
) -> jnp.ndarray:
    """The device program (un-jitted: reused by the shard_map multi-chip
    wrapper in multichip.py): returns a (B,) bool validity vector.

    One program, three signature algorithms (same dual-scalar MSM):
    per-lane flags select the acceptance test — ECDSA checks
    ``x(R) ∈ {r, r+n} (mod p)``; BCH Schnorr checks ``x(R) = r`` AND
    ``jacobi(y(R)) = 1``; BIP340 checks ``x(R) = r`` AND ``y(R)`` even
    (host prep already folded ``u1 = s``, ``u2 = n - e`` into the digit
    arrays for both Schnorr variants).

    """
    # Trace-time int32 safety audit of the live formulas: cached
    # pure-Python bound replay — a formula edit that breaks headroom
    # fails HERE, not on device.
    _bounds.assert_formulas_safe()

    q_table = _build_q_table(qx, qy)  # (16, 3, L, B)
    acc0 = jnp.broadcast_to(INFINITY, (3, F.NLIMBS, qx.shape[1]))
    lq_table = _lambda_table(q_table)

    def window_step(acc, digits):
        da, db, dc, dd = digits
        for _ in range(WINDOW_BITS):
            acc = pt_double(acc)
        acc = pt_add(acc, _signed(_select_entry(G_TABLE, da), n1a))
        acc = pt_add(acc, _signed(_select_entry(LG_TABLE, db), n1b))
        acc = pt_add(acc, _signed(_select_entry(q_table, dc), n2a))
        acc = pt_add(acc, _signed(_select_entry(lq_table, dd), n2b))
        return acc, None

    acc, _ = lax.scan(window_step, acc0, (d1a, d1b, d2a, d2b))

    X, Y, Z = acc[0], acc[1], acc[2]
    not_inf = ~F.is_zero(Z)
    m1 = F.eq(X, F.mul(r1, Z))
    m2 = F.eq(X, F.mul(r2, Z)) & r2_valid
    # The two acceptance pows below are ~19% of the program's field-mul
    # budget (2 × ~335 muls vs ~3500 total) but only matter to lanes of
    # their algorithm — and real batches are often single-algorithm (BTC
    # mainnet carries no BCH Schnorr; IBD-era blocks carry no taproot).
    # Gate each on a batch-level any() with lax.cond: XLA compiles both
    # branches once, runtime executes one, and the placeholder lanes are
    # never selected by the algo_ok where() below, so results are
    # bit-identical to the ungated program.
    true_col = jnp.ones(qx.shape[1], dtype=bool)
    # jacobi(y(R)) for the BCH Schnorr lanes: y = Y/Z, and jacobi(Y/Z) =
    # jacobi(Y·Z) since the symbol is multiplicative and squares vanish
    jac_ok = lax.cond(
        jnp.any(schnorr),
        lambda: _euler_is_one(F.mul(Y, Z)),
        lambda: true_col,
    )
    # y(R) parity for the BIP340 lanes: affine y via a Fermat inverse
    # (z^(p-2)), then the canonical representative's low bit
    even_ok = lax.cond(
        jnp.any(bip340),
        lambda: (
            F.canonical(F.mul(Y, _pow_const(Z, _PM2_DIGITS)))[0] & 1
        ) == 0,
        lambda: true_col,
    )
    # pubkey must satisfy the curve equation: qy^2 = qx^3 + 7
    on_curve = F.eq(F.sqr(qy), F.mul(F.sqr(qx), qx) + _SEVEN)
    algo_ok = jnp.where(
        bip340, m1 & even_ok, jnp.where(schnorr, m1 & jac_ok, m1 | m2)
    )
    return host_valid & on_curve & not_inf & algo_ok


@jax.jit
def _verify_device_jit(buf):
    return verify_core(*expand_lane(buf))


# The jitted XLA program over a lane's wire buffer (:func:`verify_core`
# behind :func:`expand_lane`).  The jitted function keeps its private name:
# it names the lowered module (so the persistent compile cache's key) and
# chip_smoke.py reads its cache size.
verify_device = _verify_device_jit


def _pallas_usable(batch: int) -> bool:
    """Program choice from what the code can observe: the Pallas/Mosaic
    kernel (pallas_kernel.py) on a TPU when the padded batch tiles into its
    lane blocks, the portable XLA program otherwise (CPU, tests).  On a TPU
    a Mosaic error means the kernel does not compile: it propagates."""
    from .pallas_kernel import BLOCK

    return batch % BLOCK == 0 and jax.devices()[0].platform == "tpu"


def count_transfer(buf: np.ndarray) -> None:
    """One host-to-device call of one lane's buffer (every dispatch site
    counts it where it makes the call)."""
    metrics.inc("verify.transfers")
    metrics.inc("verify.transfer_bytes", buf.nbytes)


def _dispatch_prep(prep: PreparedBatch) -> tuple[jnp.ndarray, int]:
    # host->device transfer and kernel enqueue are separate spans (both
    # are async under JAX dispatch: these time the enqueue, the blocking
    # tail shows up in verify.readback)
    with span("verify.transfer", cpu=True):
        buf = jnp.asarray(prep.buf)
    count_transfer(prep.buf)
    if _pallas_usable(buf.shape[-1]):
        from .pallas_kernel import verify_blocked

        # STATIC program choice from the host-side flags: an ECDSA-only
        # batch (the common real shape) selects the variant with the
        # jacobi/parity acceptance pows pruned at trace time.  The XLA
        # program below gets the same effect at runtime via lax.cond.
        with span("verify.kernel", cpu=True):
            return (
                verify_blocked(buf, schnorr_free=prep.schnorr_free),
                prep.count,
            )
    with span("verify.kernel", cpu=True):
        return verify_device(buf), prep.count


def dispatch_batch_tpu(
    items: Sequence[tuple[Optional[Point], int, int, int]],
    pad_to: Optional[int] = None,
) -> tuple[jnp.ndarray, int]:
    """Host prep + ASYNC device dispatch: returns (device verdict array,
    item count) without blocking on the result.  JAX dispatch is
    asynchronous, so the caller can prep the next chunk while this one
    computes — the overlap that keeps the device saturated during IBD
    (SURVEY.md §7 hard part 5).  Collect with :func:`collect_verdicts`."""
    with span("verify.prepare", cpu=True):
        prep = prepare_batch(items, pad_to=pad_to)
    return _dispatch_prep(prep)


def dispatch_batch_tpu_raw(raw, pad_to: Optional[int] = None) -> tuple[jnp.ndarray, int]:
    """:func:`dispatch_batch_tpu` over a packed RawBatch (native-extract
    fast path): same async dispatch, no Python-int round trip."""
    with span("verify.prepare", cpu=True):
        prep = prepare_batch_raw(raw, pad_to=pad_to)
    return _dispatch_prep(prep)


def collect_verdicts(out: jnp.ndarray, count: int) -> list[bool]:
    """Block on a :func:`dispatch_batch_tpu` result and return verdicts."""
    with span("verify.readback", cpu=True):
        return [bool(b) for b in np.asarray(out)[:count]]


def verify_batch_tpu(
    items: Sequence[tuple[Optional[Point], int, int, int]],
    pad_to: Optional[int] = None,
) -> list[bool]:
    """End-to-end: host prep + device verify.  Same item shape as the CPU
    engines: (pubkey, z, r, s).  Dispatches to the Pallas kernel on TPU
    (block-aligned batches), else the portable XLA program."""
    if not items:
        return []
    return collect_verdicts(*dispatch_batch_tpu(items, pad_to=pad_to))
