"""The Pallas/Mosaic batch ECDSA verify kernel: the whole MSM in VMEM.

Same mathematics as :mod:`kernel` (GLV + Shamir over 33 interleaved 4-bit
windows, complete RCB point formulas via :mod:`curve` with the
Mosaic-friendly field ops of :mod:`pallas_field`), but compiled as ONE TPU
program per batch block:

* the per-signature Q/λQ multiple tables live in VMEM scratch;
* the accumulator and every field-op intermediate stay in vector
  registers/VMEM — zero HBM round-trips inside the window loop;
* table entries are selected by a 15-where binary select tree (no
  gathers, no one-hot einsums);
* the grid walks fixed-size lane blocks of the batch, Pallas
  double-buffering the block DMAs.

Why: under plain XLA the same math is per-op dispatch/HBM bound (~41k
sigs/s ceiling at batch 8k on one v5e chip — measured round 3); in a
single Mosaic program the arithmetic runs from VMEM at VPU rate.

Inputs/outputs match :func:`kernel.verify_device` (same PreparedBatch host
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
from . import bounds as _bounds
from .curve import pt_add, pt_double
from .kernel import (
    _EULER_DIGITS,
    _PM2_DIGITS,
    BETA,
    G_TABLE,
    LG_TABLE,
    WINDOW_BITS,
    expand_lane,
    select_tree16,
)

__all__ = ["verify_blocked", "verify_blocked_impl", "BLOCK"]

# Lanes per grid step: 2 scratch tables x 1.2 MB VMEM + the two constant
# tables + headroom.  All four default programs compile on a v5e under the
# default 16 MiB scoped-VMEM limit, so no vmem_limit_bytes is set (PERF.md
# "On the chip").
BLOCK = 256

_BETA_LIMBS = [int(x) for x in F.to_limbs(BETA)]
_SEVEN_LIMBS = [7] + [0] * (F.NLIMBS - 1)


def _const_table(tab_np: np.ndarray, b: int) -> jnp.ndarray:
    """Constant (16, 3, L) window table broadcast over all ``b`` lanes
    (a compile-time constant in-kernel)."""
    return jnp.asarray(
        np.broadcast_to(tab_np[:, :, :, None], tab_np.shape + (b,))
    )


def _select16(table, digit_row):
    """Branch-free 16-way select over window-table entries.

    ``table``: (16, 3, L, B) value or VMEM ref; ``digit_row``: (1, B).
    The ONE shared fold (kernel.select_tree16): a balanced 4-level binary
    select tree — 15 wheres, each level resolving one digit bit;
    digit_row broadcasts over each (3, L, B) entry exactly like the XLA
    path's.  Entry 0 is the infinity point — the complete RCB formulas
    make adding it a no-op.
    """
    return select_tree16(
        [table[t] for t in range(int(table.shape[0]))], digit_row
    )


def _signed(entry: jnp.ndarray, neg_row: jnp.ndarray) -> jnp.ndarray:
    """Negate the point iff ``neg_row`` (1, B): -P = (X, -Y, Z)."""
    y = jnp.where(neg_row != 0, -entry[1], entry[1])
    return jnp.concatenate([entry[0:1], y[None], entry[2:3]], axis=0)


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
    #   full:         euler_ref, out_ref, qtab, lqtab, powtab
    #   schnorr_free: out_ref, qtab, lqtab  (no digits/pow)
    *rest,
    schnorr_free: bool = False,
):
    if schnorr_free:
        euler_ref = powtab_ref = None
        out_ref, qtab_ref, lqtab_ref = rest
    else:
        euler_ref, out_ref, qtab_ref, lqtab_ref, powtab_ref = rest
    b = out_ref.shape[-1]
    nwin = int(d1a_ref.shape[0])
    L = F.NLIMBS
    zero = jnp.zeros((L, b), jnp.int32)
    one = jnp.concatenate(
        [jnp.ones((1, b), jnp.int32), jnp.zeros((L - 1, b), jnp.int32)], axis=0
    )
    inf = jnp.stack([zero, one, zero], axis=0)

    qx = qx_ref[:]
    qy = qy_ref[:]

    # ---- windowed pow machinery (the jacobi/parity acceptance pows):
    # 16-entry power table of ``t`` in powtab, then 64 4-bit windows with
    # digits from SMEM row ``row`` of euler_ref.  fori_loop bodies (one
    # mul each) instead of unrolled chains: the straight-line form
    # dominated Mosaic compile time (the r3 finding).
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
    q1 = jnp.stack([qx, qy, one], axis=0)
    qtab_ref[0] = inf
    qtab_ref[1] = q1

    def build_step(k, acc):
        nxt = pt_add(acc, q1, F=PF)
        qtab_ref[pl.ds(k, 1)] = nxt[None]
        return nxt

    lax.fori_loop(2, 16, build_step, q1)

    # ---- λQ table: the endomorphism is additive, so scale each X by β ----
    beta = PF.const_col(_BETA_LIMBS, b)

    def lam_step(k, carry):
        e = qtab_ref[pl.ds(k, 1)][0]
        lx = PF.mul(e[0], beta)
        lqtab_ref[pl.ds(k, 1)] = jnp.concatenate([lx[None], e[1:]], axis=0)[
            None
        ]
        return carry

    lax.fori_loop(0, 16, lam_step, 0)

    g_tab = g_ref[:]
    lg_tab = lg_ref[:]

    n1a = negs_ref[0:1]
    n1b = negs_ref[1:2]
    n2a = negs_ref[2:3]
    n2b = negs_ref[3:4]

    # ---- Shamir/GLV window loop ------------------------------------------
    def window(w, acc):
        for _ in range(WINDOW_BITS):
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
        # exponentiation (digit row 0)
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
) -> jnp.ndarray:
    """Un-jitted kernel body — reused inside shard_map by multichip.py
    (a jitted callee cannot be shard_mapped).  See :func:`verify_blocked`.

    ``schnorr_free`` statically prunes the jacobi/parity acceptance pows
    (see _kernel) — only set it when NO lane carries a schnorr/bip340
    flag; verdicts are bit-identical for such batches."""
    # Trace-time int32 bound audit of the live formulas: the Pallas and
    # XLA programs share curve.py's bodies, so the one cached pure-Python
    # replay covers this path too.
    _bounds.assert_formulas_safe()
    blk = block
    bsz = qx.shape[-1]
    if bsz % blk != 0:
        raise ValueError(f"batch {bsz} not a multiple of BLOCK={blk}")
    grid = bsz // blk
    nwin = int(d1a.shape[0])

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

    tab_spec = pl.BlockSpec(
        (16, 3, F.NLIMBS, blk), lambda i: (0, 0, 0, 0)
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
        _const_table(G_TABLE, blk),
        _const_table(LG_TABLE, blk),
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
        pltpu.VMEM((16, 3, F.NLIMBS, blk), jnp.int32),
        pltpu.VMEM((16, 3, F.NLIMBS, blk), jnp.int32),
    ]
    if not schnorr_free:
        # Exponent digits live in SMEM: the kernel reads them with
        # dynamic scalar indices inside the window fori_loop, which is
        # scalar memory's canonical job.  The schnorr_free variant omits
        # the digits AND the (16, L, blk) pow-table scratch entirely.
        in_specs.append(
            pl.BlockSpec((2, 64), lambda i: (0, 0), memory_space=pltpu.SMEM)
        )
        operands.append(
            jnp.stack(
                [jnp.asarray(_EULER_DIGITS), jnp.asarray(_PM2_DIGITS)],
                axis=0,
            )
        )
        scratch.append(pltpu.VMEM((16, F.NLIMBS, blk), jnp.int32))
    out = pl.pallas_call(
        partial(_kernel, schnorr_free=schnorr_free),
        out_shape=jax.ShapeDtypeStruct((1, bsz), jnp.int32),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=col(1),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return out[0].astype(jnp.bool_)


@partial(jax.jit, static_argnames=("interpret", "block", "schnorr_free"))
def _verify_blocked_jit(buf, *, interpret: bool = False, block: int = BLOCK,
                        schnorr_free: bool = False):
    """Drop-in replacement for :func:`kernel.verify_device` (one argument:
    the lane's wire buffer, expanded here by :func:`kernel.expand_lane`)
    running the Pallas kernel over lane blocks of ``block`` (default BLOCK;
    tests use small blocks in interpret mode).  Batch size must be a
    multiple of the block size (prepare_batch pads to the engine's fixed
    shape).  ``schnorr_free`` selects the ECDSA-only program variant
    (acceptance pows pruned at trace time) — callers must only set it when
    no lane carries a schnorr/bip340 flag (kernel._dispatch_prep derives it
    from the prepared batch)."""
    return verify_blocked_impl(*expand_lane(buf), interpret=interpret,
                               block=block, schnorr_free=schnorr_free)


# The jitted function keeps its private name: it names the lowered module
# and the device trace's kernel events, which the benchmark's trace
# reduction and chip_smoke.py read.
verify_blocked = _verify_blocked_jit
