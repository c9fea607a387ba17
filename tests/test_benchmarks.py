"""Smoke tests for the benchmark harness (BASELINE configs).

Runs the CPU-fast configs in SMALL mode so the harness can't rot; the
device-heavy configs (2, 5) are exercised through their building blocks in
test_kernel/test_multichip instead (compile cost).
"""

import json
import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(config: str) -> dict:
    env = dict(os.environ)
    env.update(TPUNODE_BENCH_SMALL="1", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", config],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = out.stdout.strip().splitlines()[-1]
    return json.loads(line)


def test_config1_block_cpu_baseline():
    res = _run("config1")
    assert res["metric"] == "config1_block800k_cpu_verify"
    # mixed workload: sig count varies with the template mix, coverage must
    # clear the VERDICT r3 item 3 bar (config asserts it too)
    assert res["value"] > 0 and res["sigs"] > 0
    assert res["coverage"] >= 0.90
    assert res["candidates"] >= res["sigs"]  # multisig windows fan out


def test_config3_ibd_replay():
    res = _run("config3")
    assert res["metric"] == "config3_ibd_replay"
    assert res["blocks"] == 50
    assert res["txs"] == 50 * 3  # 2 mixed txs + coinbase per block
    assert res["sigs"] > 0 and res["sigs_per_sec"] > 0
    assert res["coverage"] >= 0.90


def test_config4_mempool_firehose():
    res = _run("config4")
    assert res["metric"] == "config4_mempool_firehose"
    assert res["tx_verdicts"] > 0 and res["sigs"] > 0


def test_txgen_chain_is_consensus_valid():
    import time

    from benchmarks.txgen import gen_chain
    from tpunode.headers import MemoryHeaderStore, connect_blocks
    from tpunode.params import BCH_REGTEST

    blocks = gen_chain(BCH_REGTEST, 5, 2, cache=None)
    store = MemoryHeaderStore(BCH_REGTEST)
    nodes, best = connect_blocks(
        store, BCH_REGTEST, int(time.time()), [b.header for b in blocks]
    )
    assert best.height == 5
    # every non-coinbase signature in the chain verifies
    from tpunode.txverify import extract_sig_items
    from tpunode.verify.ecdsa_cpu import verify_batch_cpu

    items = []
    for b in blocks:
        for tx in b.txs:
            its, _ = extract_sig_items(tx)
            items.extend((i.pubkey, i.z, i.r, i.s) for i in its)
    assert len(items) == 5 * 2 * 2
    assert verify_batch_cpu(items) == [True] * len(items)


def test_churn_soak_short():
    """30s of the churn soak (benchmarks/soak.py): remote deaths every
    ~10s, continuous verdict flow, flat task count / RSS at exit."""
    env = dict(os.environ)
    env.update(SOAK_SECONDS="30", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.soak"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=180,
    )
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
    assert "PASS" in out.stdout


# ---------- roofline model (ISSUE 4 tentpole) ------------------------------


def test_roofline_op_counts_match_rcb_and_structure():
    """The op model is DERIVED from the live kernel: the per-point-op
    counts must equal the RCB'16 paper's (12M for complete addition,
    6M + 2S for doubling), the fused reductions are pinned (counted by
    EXECUTING the live formulas, so a formula edit moves these on
    purpose or fails), and the per-verify totals must equal the
    structural assembly recomputed here from kernel.py's constants."""
    from benchmarks.roofline import CountingField, field_op_model
    from tpunode.verify.kernel import WINDOW_BITS, WINDOWS, _EULER_DIGITS

    m = field_op_model()
    add, dbl = m["pt_add"], m["pt_double"]
    # RCB Algorithm 7: 12 muls (+ 2 reduced small-constant scalings)
    assert add["mul_wide"] + add["mul_t_wide"] == 12
    assert add["mul_small_red"] == 2
    # RCB Algorithm 9: 6 muls + 2 squarings (+ 1 reduced scaling)
    assert dbl["mul_t_wide"] == 6
    assert dbl["sqr_t_wide"] == 2
    assert dbl["mul_small_red"] == 1

    # a reduction per product would be 14 and 9: the bodies fuse the
    # per-coordinate tails (mul_small_red's fold counts as its own)
    def reds(c):
        return sum(c.get(k, 0) for k in (
            "mul", "mul_t", "sqr", "sqr_t", "mul_small_red",
            "reduce_wide", "reduce_wide_loose"))
    assert reds(add) == 11
    assert reds(dbl) == 8

    mul_like = CountingField.OPS + CountingField.WIDE_OPS
    tab = 1 << WINDOW_BITS
    per_add = sum(add.get(op, 0) for op in mul_like)
    per_dbl = sum(dbl.get(op, 0) for op in mul_like)
    assert (per_add, per_dbl) == (14, 9)
    assert m["structure"] == {
        "windows": 33, "window_bits": 4, "half_scalars": 4,
        "table_entries": 16, "pow_digits": 64,
    }
    ecdsa = m["per_verify"]["ecdsa"]
    expect = (
        WINDOWS * 4 * (per_add + per_dbl)  # MSM: 4 dbl + 4 add per window
        + (tab - 2) * per_add              # Q table build
        + tab                              # λ table: β·X per entry
        + 2                                # m1/m2 projective checks
        + 3                                # on-curve qy² = qx³ + 7
    )
    assert ecdsa["total_mul_like"] == expect
    assert ecdsa["reductions"] < ecdsa["total_mul_like"]
    # the Schnorr/BIP340 lanes add one pow ladder + one mul each
    pow_muls = (tab - 2) + len(_EULER_DIGITS) + WINDOW_BITS * len(_EULER_DIGITS)
    for algo in ("schnorr", "bip340"):
        assert m["per_verify"][algo]["total_mul_like"] == expect + 1 + pow_muls


def test_roofline_full_model_runs():
    """End-to-end model: sane shapes, positive bounds, utilization < 1,
    and the dedicated-sqr MAC saving visible (300 < 576)."""
    from benchmarks.roofline import mac_model, roofline

    macs = mac_model()
    assert macs["mul"] == 576
    assert macs["sqr"] == 300  # the dedicated half-product path
    r = roofline()
    for algo in ("ecdsa", "schnorr", "bip340"):
        w = r["per_verify"][algo]
        assert w["int32_macs"] > 0
        assert w["vector_int_ops"] > w["int32_macs"]  # carries/folds exist
        assert r["ideal_sigs_per_s"][algo]["vpu_bound_sigs_s"] > 0
    for label, u in r["utilization"].items():
        assert 0.0 < u["vpu_utilization"] < 1.0, label
    from tpunode.verify.kernel import kernel_modes

    assert r["formulation"] == list(kernel_modes())


def test_roofline_jaxpr_walk_counts_scans():
    """The jaxpr walker multiplies scan bodies by their trip count (a
    wrong multiplier would silently corrupt every derived bound)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.roofline import count_int_ops

    def body(x):
        def step(c, _):
            return c * 2 + 1, None

        out, _ = jax.lax.scan(step, x, None, length=7)
        return out

    x = jnp.ones((4,), jnp.int32)
    c = count_int_ops(body, x)
    # per lane... batch = trailing dim 4: 7 muls + 7 adds per element
    assert c["mul"] == 7.0
    assert c["add"] == 7.0


# ---------- cpu baseline median-of-N ---------------------------------------


def test_cpu_single_core_stats_median_and_spread():
    from benchmarks.common import (
        cpu_single_core_bench,
        cpu_single_core_stats,
        make_triples,
    )

    sample = make_triples(16)
    stats = cpu_single_core_stats(sample, runs=3)
    assert stats["rate_min"] <= stats["rate"] <= stats["rate_max"]
    assert stats["rate_spread"] >= 0.0
    assert stats["runs"] in (1, 3)  # 1 when only the python oracle exists
    assert len(stats["verdicts"]) == len(sample)
    rate, engine, out = cpu_single_core_bench(sample, runs=3)
    assert rate > 0 and engine in ("native-cpp", "python-oracle")
    assert len(out) == len(sample)
