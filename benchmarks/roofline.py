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
   count from the lane layout, table lengths `2**WINDOW_BITS`, the
   64-digit constant-exponent pow ladders).

2. **Limb ops per field op** — MAC counts come from `field.py`'s limb
   count (24 x 24 = 576 partial products for mul, the 300 i <= j pairs
   for the half-product sqr), and TOTAL integer vector ops (muls + adds +
   shifts + masks, i.e. what the VPU actually executes including every
   carry/fold round) come from an independent jaxpr walk of the live
   field functions — the structural model cannot drift from the code.

3. **Chip model** — peak numbers for the target part (v5e by default;
   the VPU int32 peak is an ESTIMATE from lanes x clock x issue width,
   labeled as such) give ideal rates; measured rates divide into
   utilization.

One op count, for the one formulation the kernel has (PR 29 deleted the
affine, eager, 5-bit-window and dot_general models with the code they
modelled).

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

    The formulas run on the wide-accumulator ops: WIDE_OPS are limb
    convolutions (mul-like work, the MACs of mul / sqr), TAIL_OPS are
    the carry/fold machinery (reductions, hoisted
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
    """(pt_add, pt_double) counts by running the live formulas."""
    import jax.numpy as jnp

    from tpunode.verify import field as F
    from tpunode.verify.curve import pt_add, pt_double

    one = jnp.asarray(F.ONE)
    p = jnp.stack([one, one, one], axis=0)
    cf = CountingField(F)
    pt_add(p, p, F=cf)
    add_counts = dict(cf.counts)
    cf = CountingField(F)
    pt_double(p, F=cf)
    dbl_counts = dict(cf.counts)
    return add_counts, dbl_counts


def _pow_ladder_model(digits) -> collections.Counter:
    """Field-op counts of one constant-exponent pow ladder
    (kernel._pow_const): 14 sequential table muls, then per digit window
    4 squarings + 1 table mul."""
    from tpunode.verify import kernel as K

    tab_entries = 1 << K.WINDOW_BITS
    n = len(digits)
    return collections.Counter(
        {"mul": (tab_entries - 2) + n, "sqr": K.WINDOW_BITS * n}
    )


def _scale(counts: dict, k: int) -> collections.Counter:
    return collections.Counter({op: n * k for op, n in counts.items()})


def field_op_model() -> dict:
    """Per-verify (per lane) field-op counts for each signature algorithm,
    assembled from kernel.py's structure."""
    from tpunode.verify import kernel as K

    add_c, dbl_c = _point_op_counts()
    tab_entries = 1 << K.WINDOW_BITS
    wb = K.WINDOW_BITS
    nwin = K.WINDOWS
    halves = K.FIELD_ROW0 // K.HALF_WORDS  # the 4 GLV half-scalar digit streams
    pow_digits = len(K._EULER_DIGITS)  # 64 4-bit windows
    assert len(K._PM2_DIGITS) == pow_digits

    pow_ladder = _pow_ladder_model(K._PM2_DIGITS)
    euler_ladder = _pow_ladder_model(K._EULER_DIGITS)
    q_table = _scale(add_c, tab_entries - 2)  # sequential complete adds
    lambda_table = collections.Counter(
        {"mul": tab_entries}
    )  # β·X per entry

    # per window round: wb doublings + one add per half-scalar
    msm = _scale(dbl_c, nwin * wb) + _scale(add_c, nwin * halves)

    accept_ecdsa = collections.Counter({"mul": 2})  # m1, m2 checks
    on_curve = collections.Counter({"mul": 1, "sqr": 2})  # qy²=qx³+7

    ecdsa = msm + q_table + lambda_table + accept_ecdsa + on_curve
    # BCH Schnorr: + jacobi(Y·Z) Euler pow (1 mul + ladder)
    schnorr = ecdsa + collections.Counter({"mul": 1}) + euler_ladder
    # BIP340: + Fermat inverse Z^(p-2) (ladder) + y = Y·Z⁻¹ (1 mul)
    bip340 = ecdsa + collections.Counter({"mul": 1}) + pow_ladder

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
        "structure": {
            "windows": nwin,
            "window_bits": wb,
            "half_scalars": halves,
            "table_entries": tab_entries,
            "pow_digits": pow_digits,
        },
        "per_verify": {
            "ecdsa": flat(ecdsa),
            "schnorr": flat(schnorr),
            "bip340": flat(bip340),
        },
    }


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
    jaxpr = jax.make_jaxpr(fn)(*args)
    c: collections.Counter = collections.Counter()
    _walk_jaxpr(jaxpr.jaxpr, c, 1, branch_mode)
    return {k: v / batch for k, v in sorted(c.items())}


def field_leaf_costs(batch: int = 8) -> dict:
    """Per-lane integer op costs of the live field primitives, via the
    jaxpr walk."""
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
        # wide-accumulator primitives: the formulas' convolutions
        # (mul-like) and carry/fold machinery (tail)
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
    """MACs per field op from field.py's limb count."""
    from tpunode.verify import field as F

    mul_macs = F.NLIMBS * F.NLIMBS  # 576 partial products
    sqr_macs = F.NLIMBS * (F.NLIMBS + 1) // 2  # the 300 i <= j pairs
    return {
        "mul": mul_macs,
        "mul_t": mul_macs,
        "sqr": sqr_macs,
        "sqr_t": sqr_macs,
        "mul_small_red": F.NLIMBS + F._FN,  # a*k + the 4-limb top fold
        # a wide product is the SAME convolution as mul / sqr (the
        # reduction tail it skips has no MACs); the tail ops are pure
        # carry/fold vector work.
        "mul_wide": mul_macs,
        "mul_t_wide": mul_macs,
        "sqr_wide": sqr_macs,
        "sqr_t_wide": sqr_macs,
        "reduce_wide": 0,
        "reduce_wide_loose": 0,
        "tighten": 0,
        "acc_add": 0,
    }


# ---------------------------------------------------------------------------
# Layer 3: chip model and utilization
# ---------------------------------------------------------------------------

# Datasheet-anchored numbers for TPU v5e.  bf16 TFLOPS is published; the
# clock is derived from it (197e12 / (2 ops/MAC * 4 MXUs * 128 * 128) ≈
# 1.5 GHz; the published v5e clock is ~1.7 GHz — ours is deliberately the
# conservative datasheet-implied one).  The VPU int32
# peak is an ESTIMATE: 8x128 vector lanes * clock * 2-wide issue —
# utilization numbers against it are order-of-magnitude, which is all a
# "what fraction of the chip" answer needs.
CHIPS = {
    "v5e": {
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
# (input carry rounds + the reduction tail).  Tail ops (reduce_wide/tighten/acc_add) are pure
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
            # field ops only; the MSM's selects/einsums add ~20-30% more
            # (bench-measured, PERF.md) — this is the arithmetic floor
            "vector_int_ops": int(vec_total),
            "vector_mul_ops": int(vec_mul),
            # input-carry + reduction-tail ops only (convolution
            # accumulation excluded)
            "carry_fold_vector_ops": int(carry_fold),
        }
    return per_algo


def roofline(chip: str = "v5e") -> dict:
    """The full model: op counts -> per-verify work -> ideal rates ->
    utilization of the measured rates."""
    from tpunode.verify import field as F
    from tpunode.verify import kernel as K

    ch = CHIPS[chip]
    ops = field_op_model()
    macs = mac_model()
    leaf = field_leaf_costs()

    per_algo = _per_algo_work(ops, macs, leaf)

    vpu_ops_s = ch["vpu_lanes"] * ch["vpu_issue"] * ch["clock_ghz"] * 1e9
    # every op on the VPU: the kernel's bound
    bounds = {
        algo: {"vpu_bound_sigs_s": vpu_ops_s / w["vector_int_ops"]}
        for algo, w in per_algo.items()
    }

    # Bytes per lane over the PCIe/HBM boundary (device inputs + verdict):
    # 4 digit streams x WINDOWS + 4 limb arrays + masks.
    in_bytes = 4 * K.WINDOWS * 4 + 4 * F.NLIMBS * 4 + 6 * 1 + 4
    util = {}
    for label, m in MEASURED.items():
        algo = "ecdsa"  # the headline workload is ECDSA-only
        util[label] = {
            "rate": m["rate"],
            "provenance": m["provenance"],
            "vpu_utilization": m["rate"] / bounds[algo]["vpu_bound_sigs_s"],
            "hbm_gbps_used": m["rate"] * in_bytes / 1e9,
        }

    return {
        "chip": chip,
        "chip_model": ch,
        "formulation": list(K.kernel_modes()),
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
                 "| HBM GB/s (host I/O) |")
    lines.append("|---|---|---|---|")
    for label, u in r["utilization"].items():
        lines.append(
            f"| {label} | {u['rate']:,.0f} | {u['vpu_utilization']:.1%} "
            f"| {u['hbm_gbps_used']:.3f} |"
        )
    ideal = r["ideal_sigs_per_s"]["ecdsa"]
    lines.append("")
    lines.append(
        f"Ideal ECDSA rate on one {r['chip']}: "
        f"**{ideal['vpu_bound_sigs_s']:,} sigs/s** (every op on the VPU)."
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
