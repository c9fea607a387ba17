"""Roofline / MFU model for the batch verify kernel, derived from the
LIVE kernel (ISSUE 4 tentpole (a)).

Answers the question VERDICT r5 said the perf story was missing: not
"faster than one CPU core" but **what fraction of the chip** the measured
rates use, and which resource bounds each program.  Three layers, each
derived from the code it describes (no hand-maintained constants that can
drift):

1. **Field-op counts per verify, per algorithm** — the audited RCB
   formulas (`curve.pt_add` / `curve.pt_double`) are executed with a
   counting field namespace, and the per-program totals are assembled
   from `verify/kernel.py`'s actual structure (WINDOWS, the half-scalar
   count from `_DEVICE_FIELDS`, table lengths `2**WINDOW_BITS`, the
   64-digit constant-exponent pow ladders).

2. **Limb ops per field op** — MAC counts come from `field.py`'s live
   pair tables (`len(_MUL_PAIRS)` = 576 for mul, `len(_SQR_PAIRS)` = 300
   for the dedicated sqr), and TOTAL integer vector ops (muls + adds +
   shifts + masks, i.e. what the VPU actually executes including every
   carry/fold round) come from an independent jaxpr walk of the live
   field functions — the structural model cannot drift from the code.

3. **Chip model** — peak numbers for the target part (v5e by default:
   394 int8 TOPS on the MXUs is the datasheet number; the VPU int32 peak
   is an ESTIMATE from lanes x clock x issue width, labeled as such) give
   ideal rates; measured rates divide into utilization.

Run (CPU-only; tracing only, no compiles):

    JAX_PLATFORMS=cpu python -m benchmarks.roofline            # JSON
    JAX_PLATFORMS=cpu python -m benchmarks.roofline --markdown # PERF.md tables

Tested in tests/test_benchmarks.py (op counts pinned against the RCB
paper's 12M for addition and the jaxpr cross-check).
"""

from __future__ import annotations

import collections
import json
import math
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# ---------------------------------------------------------------------------
# Layer 1: field-op counts from the live formulas
# ---------------------------------------------------------------------------


class CountingField:
    """Field namespace that counts mul/sqr calls while delegating to the
    real implementation — `curve`'s formulas take the namespace as their
    ``F=`` parameter, so the counts come from executing the audited code,
    not from reading it.

    The ISSUE 12 lazy pipeline adds the wide-accumulator ops: WIDE_OPS
    are limb convolutions (mul-like work, same MACs as their eager
    twins), TAIL_OPS are the carry/fold machinery (reductions, hoisted
    tighten rounds, wide sums — zero MACs, all carry/fold vector ops)."""

    OPS = ("mul", "mul_t", "sqr", "sqr_t", "mul_small_red")
    WIDE_OPS = ("mul_wide", "mul_t_wide", "sqr_wide", "sqr_t_wide")
    TAIL_OPS = ("reduce_wide", "reduce_wide_loose", "tighten", "acc_add")
    ALL_OPS = OPS + WIDE_OPS + TAIL_OPS

    def __init__(self, base):
        self._base = base
        self.counts = collections.Counter()

    def __getattr__(self, name):
        attr = getattr(self._base, name)
        if name in self.ALL_OPS:
            def counted(*a, _attr=attr, _name=name, **kw):
                self.counts[_name] += 1
                return _attr(*a, **kw)

            return counted
        return attr


def _point_op_counts():
    """(pt_add, pt_double, pt_add_mixed) counts by running the live
    formulas — the mixed add (RCB'16 Algorithm 8, ISSUE 8) is the affine
    window loop's addition; its 11M+2 must pin one full mul under the
    projective add's 12M+2."""
    import jax.numpy as jnp

    from tpunode.verify import field as F
    from tpunode.verify.curve import pt_add, pt_add_mixed, pt_double

    one = jnp.asarray(F.ONE)
    p = jnp.stack([one, one, one], axis=0)
    q2 = jnp.stack([one, one], axis=0)
    cf = CountingField(F)
    pt_add(p, p, F=cf)
    add_counts = dict(cf.counts)
    cf = CountingField(F)
    pt_double(p, F=cf)
    dbl_counts = dict(cf.counts)
    cf = CountingField(F)
    pt_add_mixed(p, q2, F=cf)
    mixed_counts = dict(cf.counts)
    return add_counts, dbl_counts, mixed_counts


def _batch_inversion_counts():
    """Field-op counts of the affine Q-table batch normalization
    (kernel._normalize_q_table: prefix/suffix products + per-entry X/Y
    scaling), by EXECUTING the live helper with a counting namespace at
    the ACTIVE table size (2^window_bits entries).  The shared Fermat
    ladder is counted separately (`_pow_ladder_model`) — the stub
    pow_const here contributes zero ops."""
    import jax.numpy as jnp

    from tpunode.verify import field as F
    from tpunode.verify import kernel as K

    one = jnp.asarray(F.ONE)
    qt = jnp.stack(
        [jnp.stack([one, one, one], axis=0)] * (1 << K.window_bits()),
        axis=0,
    )
    cf = CountingField(F)
    K._normalize_q_table(qt, F=cf, pow_const=lambda t, d: t)
    return dict(cf.counts)


def _pow_ladder_model(digits) -> collections.Counter:
    """Field-op counts of one constant-exponent pow ladder under the
    ACTIVE ladder mode (kernel.pow_ladder_mode()).

    ``scan``: 14 sequential table muls, then per digit window 4
    squarings + 1 table mul.  ``unroll`` (de-scanned, ISSUE 8 lever 2):
    log-depth table build (7 sqr + 7 mul), the MSB window seeds the
    accumulator for free, zero digits skip their mul."""
    from tpunode.verify import kernel as K

    tab_entries = 1 << K.WINDOW_BITS
    n = len(digits)
    if K.pow_ladder_mode() == "scan":
        return collections.Counter(
            {"mul": (tab_entries - 2) + n, "sqr": K.WINDOW_BITS * n}
        )
    c = collections.Counter()
    for k in range(2, tab_entries):
        c["sqr" if k % 2 == 0 else "mul"] += 1
    c["sqr"] += K.WINDOW_BITS * (n - 1)
    c["mul"] += sum(1 for d in list(digits)[1:] if int(d))
    return c


def _q_table_build_model(add_c: dict, dbl_c: dict) -> collections.Counter:
    """Field-op counts of the on-device Q-table build under the ACTIVE
    ladder mode and window width: ``scan`` = 2^wb - 2 sequential
    complete adds; ``unroll`` = a log-depth double-and-add chain (fewer
    muls AND a much shorter critical path)."""
    from tpunode.verify import kernel as K

    tab_entries = 1 << K.window_bits()
    if K.pow_ladder_mode() == "scan":
        return _scale(add_c, tab_entries - 2)
    c = collections.Counter()
    for k in range(2, tab_entries):
        c.update(dbl_c if k % 2 == 0 else add_c)
    return c


def _scale(counts: dict, k: int) -> collections.Counter:
    return collections.Counter({op: n * k for op, n in counts.items()})


def field_op_model(
    point_form: "str | None" = None,
    field_reduce: "str | None" = None,
    window_bits: "int | None" = None,
) -> dict:
    """Per-verify (per lane) field-op counts for each signature algorithm,
    assembled from kernel.py's structure under the ACTIVE formulation
    modes (or ``point_form``/``field_reduce``/``window_bits`` explicitly
    — the A/B comparisons the ISSUE 8/12 acceptances want stated side by
    side; explicit modes are applied process-wide for the duration of
    the call and restored after)."""
    from tpunode.verify import curve as C
    from tpunode.verify import field as Fm
    from tpunode.verify import kernel as K

    prev_f = Fm.field_modes()
    prev_wb = K.window_bits()
    try:
        if field_reduce is not None:
            Fm.set_field_modes(reduce=field_reduce)
        if window_bits is not None:
            K.set_kernel_modes(window_bits=window_bits)
        form = point_form or C.point_form()
        add_c, dbl_c, mixed_c = _point_op_counts()
        tab_entries = 1 << K.window_bits()  # 16 at 4-bit, 32 at 5-bit
        wb = K.window_bits()
        nwin = K.windows()
        halves = sum(
            1
            for name, nd in K._DEVICE_FIELDS
            if nd == 2 and name.startswith("d")
        )  # the 4 GLV half-scalar digit streams
        pow_digits = len(K._EULER_DIGITS)  # 64 4-bit windows
        assert len(K._PM2_DIGITS) == pow_digits

        pow_ladder = _pow_ladder_model(K._PM2_DIGITS)
        euler_ladder = _pow_ladder_model(K._EULER_DIGITS)
        q_table = _q_table_build_model(add_c, dbl_c)
        lambda_table = collections.Counter(
            {"mul": tab_entries}
        )  # β·X per entry

        # per window round: wb doublings + one add per half-scalar
        msm = _scale(dbl_c, nwin * wb)
        batch_inv = collections.Counter()
        if form == "affine":
            # mixed additions against the batch-normalized 2-coordinate
            # tables (ISSUE 8): one Montgomery-trick inversion per lane —
            # prefix/suffix/normalize muls counted by executing the live
            # helper, plus ONE shared Fermat ladder over the whole table.
            msm += _scale(mixed_c, nwin * halves)
            batch_inv = collections.Counter(_batch_inversion_counts())
            batch_inv += pow_ladder
        else:
            msm += _scale(add_c, nwin * halves)

        accept_ecdsa = collections.Counter({"mul": 2})  # m1, m2 checks
        on_curve = collections.Counter({"mul": 1, "sqr": 2})  # qy²=qx³+7

        base = (
            msm + q_table + batch_inv + lambda_table + accept_ecdsa
            + on_curve
        )
        ecdsa = base
        # BCH Schnorr: + jacobi(Y·Z) Euler pow (1 mul + ladder)
        schnorr = base + collections.Counter({"mul": 1}) + euler_ladder
        # BIP340: + Fermat inverse Z^(p-2) (ladder) + y = Y·Z⁻¹ (1 mul)
        bip340 = base + collections.Counter({"mul": 1}) + pow_ladder

        def flat(c: collections.Counter) -> dict:
            d = {op: int(c.get(op, 0)) for op in CountingField.ALL_OPS}
            mul_like = CountingField.OPS + CountingField.WIDE_OPS
            d["total_mul_like"] = sum(d[op] for op in mul_like)
            d["squarings"] = (
                d["sqr"] + d["sqr_t"] + d["sqr_wide"] + d["sqr_t_wide"]
            )
            d["reductions"] = (
                sum(d[op] for op in CountingField.OPS)
                + d["reduce_wide"]
                + d["reduce_wide_loose"]
            )
            return d

        return {
            "pt_add": dict(add_c),
            "pt_double": dict(dbl_c),
            "pt_add_mixed": dict(mixed_c),
            "point_form": form,
            "structure": {
                "windows": nwin,
                "window_bits": wb,
                "field_reduce": Fm.reduce_mode(),
                "half_scalars": halves,
                "table_entries": tab_entries,
                "pow_digits": pow_digits,
                "pow_ladder": K.pow_ladder_mode(),
                "select16": K.select_mode(),
                "batch_inversion": flat(batch_inv) if batch_inv else None,
            },
            "per_verify": {
                "ecdsa": flat(ecdsa),
                "schnorr": flat(schnorr),
                "bip340": flat(bip340),
            },
        }
    finally:
        Fm.set_field_modes(mul=prev_f[0], sqr=prev_f[1], reduce=prev_f[2])
        K.set_kernel_modes(window_bits=prev_wb)


# ---------------------------------------------------------------------------
# Layer 2: limb ops per field op (MACs from live pair tables, total int
# vector ops from a jaxpr walk)
# ---------------------------------------------------------------------------

_INT_OP_CLASSES = {
    "mul": "mul",
    "add": "add",
    "sub": "add",
    "and": "bitwise",
    "or": "bitwise",
    "xor": "bitwise",
    "shift_right_arithmetic": "shift",
    "shift_right_logical": "shift",
    "shift_left": "shift",
}


def _walk_jaxpr(jaxpr, counter: collections.Counter, mult: int,
                branch_mode: str = "min") -> None:
    import numpy as np

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "scan":
            _walk_jaxpr(eqn.params["jaxpr"].jaxpr, counter,
                        mult * eqn.params["length"], branch_mode)
        elif prim == "cond":
            subs = []
            for br in eqn.params["branches"]:
                c = collections.Counter()
                _walk_jaxpr(br.jaxpr, c, mult, branch_mode)
                subs.append(c)
            pick = min if branch_mode == "min" else max
            chosen = pick(subs, key=lambda c: sum(c.values()))
            counter.update(chosen)
        elif prim in ("pjit", "closed_call", "core_call", "remat", "checkpoint"):
            inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if inner is not None:
                _walk_jaxpr(getattr(inner, "jaxpr", inner), counter, mult,
                            branch_mode)
        elif prim == "dot_general":
            lhs, _rhs = eqn.invars[0].aval, eqn.invars[1].aval
            (lc, _rc), _ = eqn.params["dimension_numbers"]
            contract = int(np.prod([lhs.shape[d] for d in lc]))
            out = int(np.prod(eqn.outvars[0].aval.shape))
            counter["mac"] += mult * out * contract
        elif prim in _INT_OP_CLASSES:
            out = eqn.outvars[0].aval
            if np.issubdtype(out.dtype, np.integer) or np.issubdtype(
                out.dtype, np.bool_
            ):
                counter[_INT_OP_CLASSES[prim]] += mult * int(np.prod(out.shape))


def count_int_ops(fn, *args, branch_mode: str = "min") -> dict:
    """Per-LANE integer vector op counts of ``fn`` traced on ``args``
    (trailing axis = batch): jaxpr walk, scans multiplied out, conds
    resolved per ``branch_mode`` ("min" = the skip path every lax.cond
    takes on an ECDSA-only batch, "max" = the pow path)."""
    import jax

    batch = int(args[-1].shape[-1]) if hasattr(args[-1], "shape") else 1
    # Trace through a FRESH wrapper: jax caches traces on the function
    # object, so re-tracing ``fn`` after a formulation-mode flip would
    # silently return the first mode's jaxpr (measured the hard way).
    jaxpr = jax.make_jaxpr(lambda *xs: fn(*xs))(*args)
    c: collections.Counter = collections.Counter()
    _walk_jaxpr(jaxpr.jaxpr, c, 1, branch_mode)
    return {k: v / batch for k, v in sorted(c.items())}


def field_leaf_costs(batch: int = 8) -> dict:
    """Per-lane integer op costs of the live field primitives (current
    formulation modes), via the jaxpr walk."""
    import jax.numpy as jnp
    import numpy as np

    from tpunode.verify import field as F

    a = jnp.asarray(np.ones((F.NLIMBS, batch), np.int32))
    b = jnp.asarray(np.full((F.NLIMBS, batch), 2, np.int32))
    w = jnp.asarray(np.ones((2 * F.NLIMBS - 1, batch), np.int32))
    costs = {
        "mul": count_int_ops(F.mul, a, b),
        "mul_t": count_int_ops(F.mul_t, a, b),
        "sqr": count_int_ops(F.sqr, a),
        "sqr_t": count_int_ops(F.sqr_t, a),
        "mul_small_red": count_int_ops(lambda x: F.mul_small_red(x, 21), a),
        # ISSUE 12 wide-accumulator primitives: the lazy pipeline's
        # convolutions (mul-like) and carry/fold machinery (tail)
        "mul_wide": count_int_ops(F.mul_wide, a, b),
        "mul_t_wide": count_int_ops(F.mul_t_wide, a, b),
        "sqr_wide": count_int_ops(F.sqr_wide, a),
        "sqr_t_wide": count_int_ops(F.sqr_t_wide, a),
        "reduce_wide": count_int_ops(F.reduce_wide, w),
        "reduce_wide_loose": count_int_ops(F.reduce_wide_loose, w),
        "tighten": count_int_ops(F.tighten, a),
        "acc_add": count_int_ops(lambda x, y: F.acc_add(x, y), w, w),
    }
    for op in costs:
        costs[op]["total"] = sum(costs[op].values())
    return costs


def mac_model() -> dict:
    """MACs per field op from field.py's live pair tables."""
    from tpunode.verify import field as F

    mul_macs = len(F._MUL_PAIRS)  # 576
    sqr_macs = (
        len(F._SQR_PAIRS) if F.sqr_mode() == "half" else mul_macs
    )  # 300 dedicated / 576 via mul
    return {
        "mul": mul_macs,
        "mul_t": mul_macs,
        "sqr": sqr_macs,
        "sqr_t": sqr_macs,
        "mul_small_red": F.NLIMBS + F._FN,  # a*k + the 4-limb top fold
        # ISSUE 12 wide ops: a wide product is the SAME convolution as
        # its eager twin (the reduction tail it skips has no MACs);
        # the tail ops are pure carry/fold vector work.
        "mul_wide": mul_macs,
        "mul_t_wide": mul_macs,
        "sqr_wide": sqr_macs,
        "sqr_t_wide": sqr_macs,
        "reduce_wide": 0,
        "reduce_wide_loose": 0,
        "tighten": 0,
        "acc_add": 0,
        # int8 MXU packing: an 11-bit limb splits into two <=6-bit halves,
        # so each int32 MAC becomes 4 int8 MACs (lo*lo, lo*hi, hi*lo,
        # hi*hi) accumulated in the MXU's int32 accumulators.
        "int8_split_factor": 4,
    }


# ---------------------------------------------------------------------------
# Layer 3: chip model and utilization
# ---------------------------------------------------------------------------

# Datasheet-anchored numbers for TPU v5e.  int8 TOPS and bf16 TFLOPS are published; the clock is derived
# from the bf16 number (197e12 / (2 ops/MAC * 4 MXUs * 128 * 128) ≈
# 1.5 GHz) — int8 runs the MXUs at DOUBLE rate, so deriving from 394
# int8 TOPS without that extra factor of 2 would double the clock and
# with it every VPU bound (the published v5e clock is ~1.7 GHz; ours is
# deliberately the conservative datasheet-implied one).  The VPU int32
# peak is an ESTIMATE: 8x128 vector lanes * clock * 2-wide issue —
# utilization numbers against it are order-of-magnitude, which is all a
# "what fraction of the chip" answer needs.
CHIPS = {
    "v5e": {
        "mxu_int8_tops": 394.0,
        "bf16_tflops": 197.0,
        "clock_ghz": 197.0e12 / (2 * 4 * 128 * 128) / 1e9,
        "vpu_lanes": 8 * 128,
        "vpu_issue": 2,
        "hbm_gbps": 819.0,
    }
}

# Measured rates to evaluate (sigs/s/chip) with provenance.  The r3 rows
# are the builders' on-device numbers for an OLDER program (PERF.md): the
# default formulation has changed twice since and its rate is not
# measured.  cpu-jax rows get no chip-utilization claim.
MEASURED = {
    "pallas@32768": {"rate": 210_900.0, "provenance": "PERF.md r3 table"},
    "pallas@8192": {"rate": 94_600.0, "provenance": "PERF.md r3 table"},
    "xla@8192": {"rate": 41_100.0, "provenance": "PERF.md r3 table"},
}


# Which bare convolution each product op embeds: the difference between
# an op's leaf cost and its bare convolution's IS its carry/fold work
# (input carry rounds + the reduction tail) — the ops the ISSUE 12 lazy
# pipeline removes.  Tail ops (reduce_wide/tighten/acc_add) are pure
# carry/fold; mul_small_red's convolution part is its scale multiply.
_CONV_OF = {
    "mul": "mul_t_wide",
    "mul_t": "mul_t_wide",
    "mul_wide": "mul_t_wide",
    "mul_t_wide": "mul_t_wide",
    "sqr": "sqr_t_wide",
    "sqr_t": "sqr_t_wide",
    "sqr_wide": "sqr_t_wide",
    "sqr_t_wide": "sqr_t_wide",
}


def _carry_fold_cost(op: str, leaf: dict) -> float:
    """Per-call carry/fold vector ops of ``op``: leaf total minus the
    embedded bare convolution (multiplies + anti-diagonal accumulation),
    which laziness never changes."""
    if op in _CONV_OF:
        return leaf[op]["total"] - leaf[_CONV_OF[op]]["total"]
    if op == "mul_small_red":  # conv part = the scale/fold multiplies
        return leaf[op]["total"] - leaf[op].get("mul", 0) - leaf[op].get(
            "mac", 0
        )
    return leaf[op]["total"]  # reduce_wide / tighten / acc_add


def _per_algo_work(ops: dict, macs: dict, leaf: dict) -> dict:
    per_algo = {}
    all_ops = CountingField.ALL_OPS
    for algo, counts in ops["per_verify"].items():
        mac_total = sum(counts[op] * macs[op] for op in all_ops)
        vec_total = sum(
            counts[op] * leaf[op]["total"] for op in all_ops
        )
        vec_mul = sum(
            counts[op] * (leaf[op].get("mul", 0) + leaf[op].get("mac", 0))
            for op in all_ops
        )
        carry_fold = sum(
            counts[op] * _carry_fold_cost(op, leaf) for op in all_ops
        )
        per_algo[algo] = {
            "field_muls": counts["total_mul_like"],
            "squarings": counts["squarings"],
            "reductions": counts["reductions"],
            "int32_macs": int(mac_total),
            "int8_macs_if_packed": int(mac_total * macs["int8_split_factor"]),
            # field ops only; the MSM's selects/einsums add ~20-30% more
            # (bench-measured, PERF.md) — this is the arithmetic floor
            "vector_int_ops": int(vec_total),
            "vector_mul_ops": int(vec_mul),
            # input-carry + reduction-tail ops only (convolution
            # accumulation excluded): the rounds ISSUE 12 fuses
            "carry_fold_vector_ops": int(carry_fold),
        }
    return per_algo


def roofline(chip: str = "v5e") -> dict:
    """The full model: op counts -> per-verify work -> ideal rates ->
    utilization of the measured rates — under the ACTIVE formulation
    modes, with a projective-vs-affine comparison block (ISSUE 8)."""
    from tpunode.verify import curve as C
    from tpunode.verify import field as F
    from tpunode.verify import kernel as K

    ch = CHIPS[chip]
    ops = field_op_model()
    macs = mac_model()
    leaf = field_leaf_costs()

    per_algo = _per_algo_work(ops, macs, leaf)

    vpu_ops_s = ch["vpu_lanes"] * ch["vpu_issue"] * ch["clock_ghz"] * 1e9
    mxu_macs_s = ch["mxu_int8_tops"] * 1e12 / 2  # TOPS counts mul+add
    bounds = {}
    for algo, w in per_algo.items():
        bounds[algo] = {
            # every op on the VPU (the shift-add formulation's bound)
            "vpu_bound_sigs_s": vpu_ops_s / w["vector_int_ops"],
            # MACs on the MXU at int8, carry/fold rounds still on the VPU
            # (the dot_general formulation's bound; VPU part dominates)
            "mxu_bound_sigs_s": 1.0 / (
                w["int8_macs_if_packed"] / mxu_macs_s
                + (w["vector_int_ops"] - w["vector_mul_ops"]) / vpu_ops_s
            ),
        }

    # Projective-vs-affine A/B at the arithmetic floor (ECDSA headline
    # workload): the affine form trades one batch inversion (one Fermat
    # ladder + ~67 muls per lane) for 132 cheaper window additions and a
    # third less select traffic — the FIELD-OP floor moves one way, the
    # non-arithmetic overhead the other; the measured step-time delta
    # (PERF.md) is the decider.
    form_compare = {}
    for form in C.POINT_FORMS:
        w = _per_algo_work(field_op_model(form), macs, leaf)["ecdsa"]
        form_compare[form] = {
            "field_muls": w["field_muls"],
            "vector_int_ops": w["vector_int_ops"],
            "vpu_bound_sigs_s": round(vpu_ops_s / w["vector_int_ops"]),
        }

    # Lazy-reduction x window-width A/B at the arithmetic floor (ISSUE
    # 12): the lazy model must remove a MEASURABLE share of the
    # carry/fold vector ops (the acceptance pin is >= 25% for the ECDSA
    # per-verify total, tested in test_benchmarks), and the 5-bit
    # windows cut rounds at the cost of bigger tables.
    reduce_compare = {}
    for red in ("eager", "lazy"):
        for wbits in K.WINDOW_BITS_MODES:
            w = _per_algo_work(
                field_op_model(field_reduce=red, window_bits=wbits),
                macs,
                leaf,
            )["ecdsa"]
            reduce_compare[f"{red}@w{wbits}"] = {
                "field_muls": w["field_muls"],
                "reductions": w["reductions"],
                "vector_int_ops": w["vector_int_ops"],
                "carry_fold_vector_ops": w["carry_fold_vector_ops"],
                "vpu_bound_sigs_s": round(vpu_ops_s / w["vector_int_ops"]),
            }

    # Bytes per lane over the PCIe/HBM boundary (device inputs + verdict):
    # 4 digit streams x windows() + 4 limb arrays + masks.
    in_bytes = 4 * K.windows() * 4 + 4 * F.NLIMBS * 4 + 6 * 1 + 4
    util = {}
    for label, m in MEASURED.items():
        algo = "ecdsa"  # the headline workload is ECDSA-only
        util[label] = {
            "rate": m["rate"],
            "provenance": m["provenance"],
            "vpu_utilization": m["rate"] / bounds[algo]["vpu_bound_sigs_s"],
            "of_mxu_bound": m["rate"] / bounds[algo]["mxu_bound_sigs_s"],
            "hbm_gbps_used": m["rate"] * in_bytes / 1e9,
        }

    return {
        "chip": chip,
        "chip_model": ch,
        "field_modes": {
            "mul": F.mul_mode(),
            "sqr": F.sqr_mode(),
            "reduce": F.reduce_mode(),
        },
        "kernel_modes": {
            "point_form": C.point_form(),
            "select16": K.select_mode(),
            "pow_ladder": K.pow_ladder_mode(),
            "window_bits": K.window_bits(),
        },
        "point_form_compare": form_compare,
        "reduce_window_compare": reduce_compare,
        "op_model": ops,
        "mac_model": macs,
        "leaf_costs": {k: {kk: round(vv, 1) for kk, vv in v.items()}
                       for k, v in leaf.items()},
        "per_verify": per_algo,
        "ideal_sigs_per_s": {
            k: {kk: round(vv) for kk, vv in v.items()}
            for k, v in bounds.items()
        },
        "device_bytes_per_verify": in_bytes,
        "utilization": {
            k: {kk: (round(vv, 4) if isinstance(vv, float) else vv)
                for kk, vv in v.items()}
            for k, v in util.items()
        },
    }


def _markdown(r: dict) -> str:
    """The PERF.md tables."""
    lines = []
    pv = r["per_verify"]
    lines.append("| algorithm | field muls | (of which sqr) | int32 MACs "
                 "| vector int ops (field only) |")
    lines.append("|---|---|---|---|---|")
    for algo in ("ecdsa", "schnorr", "bip340"):
        w = pv[algo]
        lines.append(
            f"| {algo} | {w['field_muls']} | {w['squarings']} "
            f"| {w['int32_macs']:,} | {w['vector_int_ops']:,} |"
        )
    lines.append("")
    lines.append("| measured program | sigs/s | VPU utilization "
                 "| of MXU-mapped bound | HBM GB/s (host I/O) |")
    lines.append("|---|---|---|---|---|")
    for label, u in r["utilization"].items():
        lines.append(
            f"| {label} | {u['rate']:,.0f} | {u['vpu_utilization']:.1%} "
            f"| {u['of_mxu_bound']:.1%} | {u['hbm_gbps_used']:.3f} |"
        )
    ideal = r["ideal_sigs_per_s"]["ecdsa"]
    lines.append("")
    lines.append(
        f"Ideal ECDSA rates on one {r['chip']}: "
        f"**{ideal['vpu_bound_sigs_s']:,} sigs/s** all-VPU (shift-add), "
        f"**{ideal['mxu_bound_sigs_s']:,} sigs/s** with the limb products "
        f"on the MXU at int8 (dot_general + packing; carry/fold stays on "
        f"the VPU and dominates that bound)."
    )
    lines.append("")
    lines.append("| point form (ecdsa) | field muls | vector int ops "
                 "| all-VPU bound (sigs/s) |")
    lines.append("|---|---|---|---|")
    for form, w in r["point_form_compare"].items():
        lines.append(
            f"| {form} | {w['field_muls']} | {w['vector_int_ops']:,} "
            f"| {w['vpu_bound_sigs_s']:,} |"
        )
    lines.append("")
    lines.append("| reduce@width (ecdsa) | field muls | reductions "
                 "| carry/fold vec ops | vector int ops "
                 "| all-VPU bound (sigs/s) |")
    lines.append("|---|---|---|---|---|---|")
    for key, w in r["reduce_window_compare"].items():
        lines.append(
            f"| {key} | {w['field_muls']} | {w['reductions']} "
            f"| {w['carry_fold_vector_ops']:,} | {w['vector_int_ops']:,} "
            f"| {w['vpu_bound_sigs_s']:,} |"
        )
    return "\n".join(lines)


def main() -> None:
    chip = "v5e"
    for a in sys.argv[1:]:
        if a.startswith("--chip="):
            chip = a.split("=", 1)[1]
    r = roofline(chip)
    if "--markdown" in sys.argv:
        print(_markdown(r))
    else:
        print(json.dumps(r))


if __name__ == "__main__":
    main()
