"""The Pallas/Mosaic batch ECDSA verify kernel: the whole MSM in VMEM.

Same mathematics as :mod:`kernel` (GLV + Shamir over 33 interleaved 4-bit
windows, complete RCB point formulas via :mod:`curve` with the
Mosaic-friendly field ops of :mod:`pallas_field`), but compiled as ONE TPU
program per batch block:

* the per-signature Q/λQ multiple tables live in VMEM scratch;
* the accumulator and every field-op intermediate stay in vector
  registers/VMEM — zero HBM round-trips inside the window loop;
* table entries are selected by 16-way compare-accumulate (no gathers,
  no one-hot einsums);
* the grid walks fixed-size lane blocks of the batch, Pallas
  double-buffering the block DMAs.

Why: under plain XLA the same math is per-op dispatch/HBM bound (~41k
sigs/s ceiling at batch 8k on one v5e chip — measured round 3); in a
single Mosaic program the arithmetic runs from VMEM at VPU rate.

Inputs/outputs match :func:`kernel.verify_core` (same PreparedBatch host
prep, same verdict vector), pinned against the CPU oracle in
tests/test_pallas_kernel.py.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import field as F
from . import pallas_field as PF
from .curve import point_form, pt_add, pt_add_mixed, pt_double
from .kernel import (
    _EULER_DIGITS,
    _PM2_DIGITS,
    BETA,
    G_TABLE,
    G_TABLE_AFF,
    LG_TABLE,
    LG_TABLE_AFF,
    select_mode,
    select_tree16,
    structure_modes,
    window_bits,
    window_tables,
)

__all__ = ["verify_blocked", "verify_blocked_impl", "BLOCK"]

# Lanes per grid step: 2 scratch tables x 1.2 MB VMEM + the two constant
# tables + headroom.  All four default programs compile on a v5e under the
# default 16 MiB scoped-VMEM limit, so no vmem_limit_bytes is set (PERF.md
# "On the chip").
BLOCK = 256

_BETA_LIMBS = [int(x) for x in F.to_limbs(BETA)]
_SEVEN_LIMBS = [7] + [0] * (F.NLIMBS - 1)

# Constant G / λG tables as host numpy, shape (16, 3, NLIMBS) — and their
# 2-coordinate affine views (16, 2, NLIMBS) for the affine point form:
# broadcast over lanes at trace time (compile-time constants in-kernel).
# The 5-bit window mode fetches its 32-entry tables from
# kernel.window_tables() instead (see _const_table).
_G_NP = np.asarray(G_TABLE)
_LG_NP = np.asarray(LG_TABLE)
_G_AFF_NP = np.asarray(G_TABLE_AFF)
_LG_AFF_NP = np.asarray(LG_TABLE_AFF)


def _const_table(tab_np: np.ndarray, b: int) -> jnp.ndarray:
    """Constant window table operand.  4-bit windows keep the proven r3
    layout: the (16, C, L) table broadcast over all ``b`` lanes.  5-bit
    windows (ISSUE 12) pass ONE shared copy — shape (32, C, L, 1) — and
    let the in-kernel selects broadcast it against the per-lane digit
    rows: the per-lane duplication is pure VMEM waste, and at 32 entries
    it would double a cost that was already ~1.2 MB per table."""
    if window_bits() == 5:
        return jnp.asarray(tab_np[:, :, :, None])
    return jnp.asarray(
        np.broadcast_to(tab_np[:, :, :, None], tab_np.shape + (b,))
    )


def _select16(table, digit_row):
    """Branch-free 16-way select over window-table entries.

    ``table``: (16, C, L, B) value or VMEM ref (C = 3 projective / 2
    affine); ``digit_row``: (1, B).  Two formulations behind the
    TPUNODE_SELECT16 knob (kernel.select_mode(), read at trace time):

    * ``tree`` (default, ISSUE 8 lever 3): balanced 4-level binary
      select tree — 15 wheres, each level resolving one digit bit; half
      the one-hot form's data movement and no accumulate adds.
    * ``onehot``: the r3 compare-accumulate (16 wheres + 15 adds).

    Entry 0 is the infinity point — under the projective form the
    complete RCB formulas make adding it a no-op; the affine window loop
    handles digit 0 with a keep-accumulator select instead.

    Entry count follows the table's leading axis (16 at 4-bit windows,
    32 at 5-bit — ISSUE 12).  A shared constant table with a 1-lane
    trailing axis broadcasts against the digit row inside each where.
    """
    ent_n = int(table.shape[0])
    if select_mode() == "onehot":
        out = None
        for t in range(ent_n):
            m = digit_row == t  # (1, B), broadcasts over (C, L, B)
            contrib = jnp.where(m, table[t], 0)
            out = contrib if out is None else out + contrib
        return out
    # the ONE shared fold (kernel.select_tree16): digit_row (1, B)
    # broadcasts over each (C, L, B) entry exactly like the XLA path's
    return select_tree16([table[t] for t in range(ent_n)], digit_row)


def _signed(entry: jnp.ndarray, neg_row: jnp.ndarray) -> jnp.ndarray:
    """Negate the point iff ``neg_row`` (1, B): -P = (X, -Y[, Z]) — works
    on projective (3, L, B) and affine (2, L, B) entries alike."""
    y = jnp.where(neg_row != 0, -entry[1], entry[1])
    parts = [entry[0:1], y[None]]
    if entry.shape[0] == 3:
        parts.append(entry[2:3])
    return jnp.concatenate(parts, axis=0)


def _kernel(
    g_ref,  # (16, 3, L, B) constant G table, same block every step
    lg_ref,  # (16, 3, L, B) constant λG table
    d1a_ref,
    d1b_ref,
    d2a_ref,
    d2b_ref,
    negs_ref,  # (4, B) int32
    qx_ref,
    qy_ref,
    r1_ref,
    r2_ref,
    flags_ref,  # (4, B) int32: [r2_valid, host_valid, schnorr, bip340]
    # remaining refs depend on the STATIC variant (pallas passes inputs,
    # then outputs, then scratch, positionally):
    #   projective full:         euler_ref, out_ref, qtab, lqtab, powtab
    #   projective schnorr_free: out_ref, qtab, lqtab  (no digits/pow)
    #   affine (either):         euler_ref, out_ref, qtab(2-coord),
    #                            lqtab(2-coord), ztab, ptab, powtab
    #   (affine always carries the digits + pow scratch: the batch
    #   inversion's Fermat ladder needs the _PM2 digit row even when the
    #   acceptance pows are pruned)
    *rest,
    schnorr_free: bool = False,
    point_form: str = "projective",
):
    affine = point_form == "affine"
    if affine:
        (euler_ref, out_ref, qtab_ref, lqtab_ref, ztab_ref, ptab_ref,
         powtab_ref) = rest
    elif schnorr_free:
        euler_ref = powtab_ref = None
        out_ref, qtab_ref, lqtab_ref = rest
    else:
        euler_ref, out_ref, qtab_ref, lqtab_ref, powtab_ref = rest
    b = out_ref.shape[-1]
    # MSM structure from the ref shapes (ISSUE 12): table entries and
    # window width off the Q-table scratch, window rounds off the digit
    # stream — so ONE kernel body serves both widths.
    ent_n = int(qtab_ref.shape[0])
    wbits = (ent_n - 1).bit_length()
    nwin = int(d1a_ref.shape[0])
    L = F.NLIMBS
    zero = jnp.zeros((L, b), jnp.int32)
    one = jnp.concatenate(
        [jnp.ones((1, b), jnp.int32), jnp.zeros((L - 1, b), jnp.int32)], axis=0
    )
    inf = jnp.stack([zero, one, zero], axis=0)

    qx = qx_ref[:]
    qy = qy_ref[:]

    # ---- windowed pow machinery (shared by the affine batch inversion
    # and the jacobi/parity acceptance pows): 16-entry power table of
    # ``t`` in powtab, then 64 4-bit windows with digits from SMEM row
    # ``row`` of euler_ref.  fori_loop bodies (one mul each) instead of
    # unrolled chains: the straight-line form dominated Mosaic compile
    # time (the r3 finding).
    def pow_build_table(t):
        powtab_ref[0] = one
        powtab_ref[1] = t

        def pow_build(k, carry):
            powtab_ref[pl.ds(k, 1)] = PF.mul(
                powtab_ref[pl.ds(k - 1, 1)][0], t
            )[None]
            return carry

        lax.fori_loop(2, 16, pow_build, 0)

    def pow_window_for(row):
        def pow_window(w, pacc):
            pacc = PF.sqr(PF.sqr(PF.sqr(PF.sqr(pacc))))
            d = euler_ref[row, w]
            sel = None
            for tv in range(16):
                contrib = jnp.where(d == tv, powtab_ref[tv], 0)
                sel = contrib if sel is None else sel + contrib
            return PF.mul(pacc, sel)

        return pow_window

    # ---- per-signature Q table: [O, Q, 2Q, ..., 15Q] ----------------------
    # fori_loop bodies (one pt_add / one mul) instead of unrolled chains:
    # the straight-line table build dominated Mosaic compile time otherwise.
    # Projective: 3-coordinate entries straight into qtab.  Affine (ISSUE
    # 8): X/Y into the 2-coordinate qtab, Z into ztab, then one
    # Montgomery-trick batch inversion per lane (prefix products in ptab,
    # ONE shared Fermat Z^(p-2) ladder, suffix pass) normalizes every
    # entry to affine in place.
    q1 = jnp.stack([qx, qy, one], axis=0)
    if affine:
        qtab_ref[0] = jnp.stack([zero, one], axis=0)
        qtab_ref[1] = q1[0:2]

        def build_step(k, acc):
            nxt = pt_add(acc, q1, F=PF)
            qtab_ref[pl.ds(k, 1)] = nxt[0:2][None]
            ztab_ref[pl.ds(k, 1)] = nxt[2][None]
            return nxt

        lax.fori_loop(2, ent_n, build_step, q1)

        # prefix products ptab[k] = z_2 * ... * z_k (ptab[1] = 1)
        ptab_ref[1] = one
        ptab_ref[2] = ztab_ref[2]

        def prefix_step(k, carry):
            ptab_ref[pl.ds(k, 1)] = PF.mul(
                ptab_ref[pl.ds(k - 1, 1)][0], ztab_ref[pl.ds(k, 1)][0]
            )[None]
            return carry

        lax.fori_loop(3, ent_n, prefix_step, 0)

        # one shared Fermat ladder: (z_2 ... z_{ent_n-1})^(p-2)
        pow_build_table(ptab_ref[ent_n - 1])
        inv = lax.fori_loop(0, 64, pow_window_for(1), one)

        # suffix pass: entering k, run = (z_2 ... z_k)^-1
        def suffix_step(i, run):
            k = ent_n - 1 - i
            zinv = PF.mul(run, ptab_ref[pl.ds(k - 1, 1)][0])
            e = qtab_ref[pl.ds(k, 1)][0]
            qtab_ref[pl.ds(k, 1)] = jnp.stack(
                [PF.mul(e[0], zinv), PF.mul(e[1], zinv)], axis=0
            )[None]
            return PF.mul(run, ztab_ref[pl.ds(k, 1)][0])

        lax.fori_loop(0, ent_n - 2, suffix_step, inv)
    else:
        qtab_ref[0] = inf
        qtab_ref[1] = q1

        def build_step(k, acc):
            nxt = pt_add(acc, q1, F=PF)
            qtab_ref[pl.ds(k, 1)] = nxt[None]
            return nxt

        lax.fori_loop(2, ent_n, build_step, q1)

    # ---- λQ table: the endomorphism is additive, so scale each X by β ----
    beta = PF.const_col(_BETA_LIMBS, b)

    def lam_step(k, carry):
        e = qtab_ref[pl.ds(k, 1)][0]
        lx = PF.mul(e[0], beta)
        lqtab_ref[pl.ds(k, 1)] = jnp.concatenate([lx[None], e[1:]], axis=0)[
            None
        ]
        return carry

    lax.fori_loop(0, ent_n, lam_step, 0)

    g_tab = g_ref[:]
    lg_tab = lg_ref[:]

    n1a = negs_ref[0:1]
    n1b = negs_ref[1:2]
    n2a = negs_ref[2:3]
    n2b = negs_ref[3:4]

    # ---- Shamir/GLV window loop ------------------------------------------
    if affine:
        # mixed additions against 2-coordinate tables; digit 0 (the
        # infinity entry, unrepresentable in affine) keeps the
        # accumulator through a branch-free select
        def window(w, acc):
            for _ in range(wbits):
                acc = pt_double(acc, F=PF)
            for tab, dref, neg in (
                (g_tab, d1a_ref, n1a),
                (lg_tab, d1b_ref, n1b),
                (qtab_ref, d2a_ref, n2a),
                (lqtab_ref, d2b_ref, n2b),
            ):
                d = dref[pl.ds(w, 1)]
                sel = _signed(_select16(tab, d), neg)
                nxt = pt_add_mixed(acc, sel, F=PF)
                acc = jnp.where(d == 0, acc, nxt)
            return acc

    else:
        def window(w, acc):
            for _ in range(wbits):
                acc = pt_double(acc, F=PF)
            da = d1a_ref[pl.ds(w, 1)]
            db = d1b_ref[pl.ds(w, 1)]
            dc = d2a_ref[pl.ds(w, 1)]
            dd = d2b_ref[pl.ds(w, 1)]
            acc = pt_add(acc, _signed(_select16(g_tab, da), n1a), F=PF)
            acc = pt_add(acc, _signed(_select16(lg_tab, db), n1b), F=PF)
            acc = pt_add(acc, _signed(_select16(qtab_ref, dc), n2a), F=PF)
            acc = pt_add(acc, _signed(_select16(lqtab_ref, dd), n2b), F=PF)
            return acc

    acc = lax.fori_loop(0, nwin, window, inf)

    # ---- projective check x(R) ∈ {r, r+n} and curve membership ------------
    X, Y, Z = acc[0], acc[1], acc[2]
    not_inf = ~PF.is_zero(Z)
    m1 = PF.eq(X, PF.mul(r1_ref[:], Z))
    m2 = PF.eq(X, PF.mul(r2_ref[:], Z)) & (flags_ref[0:1] != 0)
    seven = PF.const_col(_SEVEN_LIMBS, b)
    on_curve = PF.eq(PF.sqr(qy), PF.mul(PF.sqr(qx), qx) + seven)

    # ---- per-lane acceptance ----------------------------------------------
    # Mask algebra, not jnp.where: a select whose OPERANDS are bool vectors
    # is the one construct here Mosaic refuses (jax 0.9.0 / libtpu 0.0.34
    # on v5e: "Unsupported target bitwidth for truncation", i8 -> i1).
    is_ecdsa = (flags_ref[2:3] == 0) & (flags_ref[3:4] == 0)
    algo_ok = is_ecdsa & (m1 | m2)
    # ``schnorr_free`` (STATIC, set by the dispatcher when no lane in the
    # batch carries a Schnorr/BIP340 flag — the common real shape: BTC
    # mainnet has no BCH Schnorr, IBD-era blocks no taproot) prunes BOTH
    # acceptance pows at trace time; a flagged lane that reached this
    # variant anyway fails closed.
    if not schnorr_free:
        # jacobi(y(R)) for the BCH Schnorr lanes: y = Y/Z so jacobi(y) =
        # jacobi(Y·Z); Euler pow t^((p-1)/2) == 1 as a windowed 4-bit
        # exponentiation (digit row 0), rebuilding the power table (the
        # affine variant used it for the inversion)
        pow_build_table(PF.mul(Y, Z))
        pacc = lax.fori_loop(0, 64, pow_window_for(0), one)
        jac_ok = PF.eq(pacc, one)

        # BIP340 evenness: affine y = Y/Z via Fermat inverse Z^(p-2)
        # (digit row 1), then the canonical representative's low bit
        pow_build_table(Z)
        zinv = lax.fori_loop(0, 64, pow_window_for(1), one)
        y_aff = PF.mul(Y, zinv)
        even_ok = (PF.canonical(y_aff)[0:1] & 1) == 0

        # bip340 wins over schnorr, as in kernel.verify_core
        is_b340 = flags_ref[3:4] != 0
        is_sch = (flags_ref[2:3] != 0) & (flags_ref[3:4] == 0)
        algo_ok = algo_ok | (m1 & ((is_b340 & even_ok) | (is_sch & jac_ok)))
    valid = (flags_ref[1:2] != 0) & on_curve & not_inf & algo_ok
    out_ref[:] = valid.astype(jnp.int32)


def verify_blocked_impl(
    d1a,
    d1b,
    d2a,
    d2b,
    n1a,
    n1b,
    n2a,
    n2b,
    qx,
    qy,
    r1,
    r2,
    r2_valid,
    host_valid,
    schnorr,
    bip340,
    *,
    interpret: bool = False,
    block: int = BLOCK,
    schnorr_free: bool = False,
    point_form: "str | None" = None,
) -> jnp.ndarray:
    """Un-jitted kernel body — reused inside shard_map by multichip.py
    (a jitted callee cannot be shard_mapped).  See :func:`verify_blocked`.

    ``schnorr_free`` statically prunes the jacobi/parity acceptance pows
    (see _kernel) — only set it when NO lane carries a schnorr/bip340
    flag; verdicts are bit-identical for such batches.  ``point_form``
    selects the projective or affine MSM variant (None = the process
    global, curve.point_form()); verdicts are bit-identical across
    forms."""
    if point_form is None:
        point_form = _active_point_form()
    # Trace-time int32 bound audit of the live formulas (ISSUE 12): the
    # Pallas and XLA programs share curve.py's bodies, so the one cached
    # pure-Python replay covers this path too.
    from . import bounds as _bounds

    _bounds.assert_formulas_safe()
    affine = point_form == "affine"
    blk = block
    bsz = qx.shape[-1]
    if bsz % blk != 0:
        raise ValueError(f"batch {bsz} not a multiple of BLOCK={blk}")
    grid = bsz // blk
    nwin = int(d1a.shape[0])
    wb = window_bits()
    ent_n = 1 << wb
    from .kernel import windows as _windows

    # data/mode consistency (same guard as the XLA path): digit rows
    # prepped at one window width under another width's global would
    # produce silently wrong verdicts, not a shape error.
    if nwin != _windows():
        raise RuntimeError(
            f"digit arrays carry {nwin} window rows but the active "
            f"window_bits={wb} needs {_windows()}: re-prepare the "
            "batch under the active mode"
        )
    # Constant G/λG tables for the active width: 4-bit keeps the module
    # constants; 5-bit fetches the 32-entry tables (ONE shared VMEM copy
    # — see _const_table).
    if wb == 4:
        g_np = _G_AFF_NP if affine else _G_NP
        lg_np = _LG_AFF_NP if affine else _LG_NP
    else:
        g_full, lg_full, g_aff, lg_aff = window_tables()
        g_np = np.asarray(g_aff if affine else g_full)
        lg_np = np.asarray(lg_aff if affine else lg_full)
    tab_lanes = 1 if wb == 5 else blk

    negs = jnp.stack(
        [a.astype(jnp.int32) for a in (n1a, n1b, n2a, n2b)], axis=0
    )
    flags = jnp.stack(
        [
            r2_valid.astype(jnp.int32),
            host_valid.astype(jnp.int32),
            schnorr.astype(jnp.int32),
            bip340.astype(jnp.int32),
        ],
        axis=0,
    )

    def col(rows):  # BlockSpec for a (rows, B) input walked along lanes
        return pl.BlockSpec((rows, blk), lambda i: (0, i))

    coords = 2 if affine else 3
    tab_spec = pl.BlockSpec(
        (ent_n, coords, F.NLIMBS, tab_lanes), lambda i: (0, 0, 0, 0)
    )
    in_specs = [
        tab_spec,
        tab_spec,
        col(nwin),
        col(nwin),
        col(nwin),
        col(nwin),
        col(4),
        col(F.NLIMBS),
        col(F.NLIMBS),
        col(F.NLIMBS),
        col(F.NLIMBS),
        col(4),
    ]
    operands = [
        _const_table(g_np, blk),
        _const_table(lg_np, blk),
        d1a.astype(jnp.int32),
        d1b.astype(jnp.int32),
        d2a.astype(jnp.int32),
        d2b.astype(jnp.int32),
        negs,
        qx,
        qy,
        r1,
        r2,
        flags,
    ]
    scratch = [
        pltpu.VMEM((ent_n, coords, F.NLIMBS, blk), jnp.int32),
        pltpu.VMEM((ent_n, coords, F.NLIMBS, blk), jnp.int32),
    ]
    if affine or not schnorr_free:
        # Exponent digits live in SMEM: the kernel reads them with
        # dynamic scalar indices inside the window fori_loop, which is
        # scalar memory's canonical job.  The projective schnorr_free variant
        # omits the digits AND the (16, L, blk) pow-table scratch
        # entirely; the affine variants always need both (the batch
        # inversion's Fermat ladder reads the _PM2 digit row).
        in_specs.append(
            pl.BlockSpec((2, 64), lambda i: (0, 0), memory_space=pltpu.SMEM)
        )
        operands.append(
            jnp.stack(
                [jnp.asarray(_EULER_DIGITS), jnp.asarray(_PM2_DIGITS)],
                axis=0,
            )
        )
    if affine:
        # Z column + prefix-product tables for the batch inversion: the
        # 2-coordinate main tables free exactly 2 x (ent, L, blk) planes,
        # so the affine variant's VMEM high-water stays ~level with the
        # projective one's.
        scratch.append(pltpu.VMEM((ent_n, F.NLIMBS, blk), jnp.int32))
        scratch.append(pltpu.VMEM((ent_n, F.NLIMBS, blk), jnp.int32))
    if affine or not schnorr_free:
        # pow-ladder table: ALWAYS 16 entries (the constant-exponent
        # ladders stay 4-bit regardless of the MSM window width)
        scratch.append(pltpu.VMEM((16, F.NLIMBS, blk), jnp.int32))
    out = pl.pallas_call(
        partial(_kernel, schnorr_free=schnorr_free, point_form=point_form),
        out_shape=jax.ShapeDtypeStruct((1, bsz), jnp.int32),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=col(1),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return out[0].astype(jnp.bool_)


def _active_point_form() -> str:
    return point_form()


@partial(
    jax.jit,
    static_argnames=(
        "interpret", "block", "schnorr_free", "point_form", "field_modes",
    ),
)
def _verify_blocked_jit(*args, interpret: bool = False, block: int = BLOCK,
                        schnorr_free: bool = False, point_form=None,
                        field_modes=None):
    # ``field_modes`` is only a jit-cache key (kernel.structure_modes():
    # field formulation + select/ladder shape — the point form rides the
    # EXPLICIT static arg, so including the global form here too would
    # double-encode it): the knobs are process globals read at trace
    # time, so a flip must force a retrace instead of reusing the stale
    # executable.
    del field_modes
    return verify_blocked_impl(*args, interpret=interpret, block=block,
                               schnorr_free=schnorr_free,
                               point_form=point_form)


def verify_blocked(*args, interpret: bool = False, block: int = BLOCK,
                   schnorr_free: bool = False,
                   point_form: "str | None" = None):
    """Drop-in replacement for :func:`kernel.verify_core` (same argument
    order — PreparedBatch.device_args) running the Pallas kernel over
    lane blocks of ``block`` (default BLOCK; tests use small blocks in
    interpret mode).  Batch size must be a multiple of the block size
    (prepare_batch pads to the engine's fixed shape).  ``schnorr_free``
    selects the ECDSA-only program variant (acceptance pows pruned at
    trace time) — callers must only set it when no lane carries a
    schnorr/bip340 flag (kernel._dispatch_prep derives it from the
    prepared batch).  ``point_form`` selects the projective/affine MSM
    (None = the process-global curve.point_form()).  Jit-cached per
    explicit point form + kernel.structure_modes()."""
    if point_form is None:
        point_form = _active_point_form()
    return _verify_blocked_jit(*args, interpret=interpret, block=block,
                               schnorr_free=schnorr_free,
                               point_form=point_form,
                               field_modes=structure_modes())
