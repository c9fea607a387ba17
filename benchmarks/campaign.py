"""Kernel-vs-oracle adversarial campaign (reproducible harness).

Validates the XLA device program (``verify_device`` on cpu-jax — the
same program the TPU runs) against the C++ batch verifier (itself
pinned to the pure-Python consensus oracle in tests) on randomized
valid signatures plus adversarial shapes for all three algorithms:

* message bit-flips (z ^ 1) and signature bit-flips (s ^ 1);
* ``r = x + n`` aliasing (ECDSA accepts via the x+n branch — valid!);
* ``s -> n - s`` ECDSA twins (valid: low-s normalization ambiguity);
* boundary values ``r = p - 1``, ``s = n - 1``, ``r = 0``, ``s = 0``;
* absent / infinity / off-curve pubkeys;
* non-canonicalized-nonce Schnorr/BIP340 twins — x(R) matches, only
  jacobi/parity rejects (the shapes that pin the r5 gated acceptance
  pows at scale).

Run (CPU-only):

    JAX_PLATFORMS=cpu python -m benchmarks.campaign [unique_pool] [batch]
    JAX_PLATFORMS=cpu python -m benchmarks.campaign --pallas [pool] [batch]

``--pallas`` sends the same pool through the flagship Pallas program in
interpret mode (numpy semantics of the exact Mosaic program; block 32)
instead of the XLA program — both device paths validated by one
harness: a kernel change must produce ZERO mismatches on the full
adversarial pool before it is eligible for dispatch.  Prints one JSON
line: items compared, mismatches (MUST be 0) and the per-shape tally.
Replaces the one-off scripts behind PERF.md's r5 campaign notes with a
committed, re-runnable harness.
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def build_pool(n_base: int, rng: random.Random):
    """(items, shapes): adversarial pool of unique verify items, tagged
    with the shape that produced each (for the tally)."""
    from tpunode.verify.ecdsa_cpu import (
        CURVE_N,
        CURVE_P,
        GENERATOR,
        Point,
        bip340_challenge,
        jacobi,
        lift_x,
        point_mul,
        schnorr_challenge,
        sign,
        sign_bip340,
        sign_schnorr,
    )

    items, shapes, expects = [], [], []

    def add(item, shape, expect_valid):
        """``expect_valid`` is the shape's REQUIRED verdict: asserting it
        (not just device == oracle) catches a regression that weakens
        both lanes identically (e.g. shared host prep dropping the
        schnorr/bip340 flags so twins verify as plain ECDSA everywhere)."""
        items.append(item)
        shapes.append(shape)
        expects.append(expect_valid)

    def nonce_with(pred):
        while True:
            k = rng.getrandbits(256) % CURVE_N or 1
            R = point_mul(k, GENERATOR)
            if pred(R):
                return k, R

    for i in range(n_base):
        priv = rng.getrandbits(256) % CURVE_N or 1
        pub = point_mul(priv, GENERATOR)
        z = rng.getrandbits(256)
        algo = i % 3
        if algo == 0:  # ECDSA + mutations
            r, s = sign(priv, z, rng.getrandbits(256) % CURVE_N or 1)
            add((pub, z, r, s), "ecdsa-valid", True)
            add((pub, z ^ 1, r, s), "ecdsa-zflip", False)
            add((pub, z, r, s ^ 1), "ecdsa-sflip", False)
            add((pub, z, r, CURVE_N - s), "ecdsa-neg-s", True)  # valid twin
            if r + CURVE_N < CURVE_P:
                # requires x(R) < p - n (~2^-129 for random R): never
                # fires randomly; the m2/r2_valid branch is pinned by
                # synthetic unit tests instead
                add((pub, z, r + CURVE_N, s), "ecdsa-r-alias", True)
            add((pub, z, CURVE_P - 1, s), "ecdsa-r-boundary", False)
            add((pub, z, r, CURVE_N - 1), "ecdsa-s-boundary", False)
            add((pub, z, 0, s), "ecdsa-r0", False)
            add((pub, z, r, 0), "ecdsa-s0", False)
            add((None, z, r, s), "ecdsa-no-pub", False)
            add((Point(None, None), z, r, s), "ecdsa-inf-pub", False)
            add((Point(5, 7), z, r, s), "ecdsa-off-curve", False)
        elif algo == 1:  # BCH Schnorr + mutations
            r, s = sign_schnorr(priv, z, rng.getrandbits(256))
            e = schnorr_challenge(r, pub, z)
            add((pub, e, r, s, "schnorr"), "schnorr-valid", True)
            add((pub, e ^ 1, r, s, "schnorr"), "schnorr-eflip", False)
            add((pub, e, r, s ^ 1, "schnorr"), "schnorr-sflip", False)
            add((pub, e, r, CURVE_N - s, "schnorr"), "schnorr-neg-s", False)
            k, R = nonce_with(lambda R: jacobi(R.y) != 1)
            e2 = schnorr_challenge(R.x, pub, z)
            add((pub, e2, R.x, (k + e2 * priv) % CURVE_N, "schnorr"),
                "schnorr-jacobi-twin", False)
        else:  # BIP340 + mutations
            P0 = pub  # same point; the scalar mult is the pool's hot op
            d = priv if P0.y % 2 == 0 else CURVE_N - priv
            r, s = sign_bip340(priv, z, rng.getrandbits(256))
            e = bip340_challenge(r, P0.x, z)
            pub340 = lift_x(P0.x)
            add((pub340, e, r, s, "bip340"), "bip340-valid", True)
            add((pub340, e ^ 1, r, s, "bip340"), "bip340-eflip", False)
            add((pub340, e, r, s ^ 1, "bip340"), "bip340-sflip", False)
            add((pub340, e, r, CURVE_N - s, "bip340"), "bip340-neg-s", False)
            k, R = nonce_with(lambda R: R.y % 2 != 0)
            e2 = bip340_challenge(R.x, P0.x, z)
            add((pub340, e2, R.x, (k + e2 * d) % CURVE_N, "bip340"),
                "bip340-parity-twin", False)
    return items, shapes, expects


def run_campaign(n_base: int, batch: int, pallas: bool = False) -> dict:
    """Build the pool and compare the chosen device program against the
    C++ verifier AND each shape's required verdict.  Returns the result
    dict (``mismatches`` MUST be 0)."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    from tpunode.verify.cpu_native import load_native_verifier
    from tpunode.verify.ecdsa_cpu import verify_batch_cpu
    from tpunode.verify.engine import enable_compile_cache
    from tpunode.verify.kernel import verify_batch_tpu

    enable_compile_cache()
    if pallas:
        import jax.numpy as jnp

        from tpunode.verify.kernel import collect_verdicts, prepare_batch
        from tpunode.verify.pallas_kernel import verify_blocked

        def device_verify(chunk, pad_to):
            prep = prepare_batch(chunk, pad_to=pad_to)
            out = verify_blocked(
                jnp.asarray(prep.buf), interpret=True, block=32
            )
            return collect_verdicts(out, len(chunk))
    else:
        def device_verify(chunk, pad_to):
            return verify_batch_tpu(chunk, pad_to=pad_to)

    rng = random.Random(0xCA4)
    t0 = time.time()
    items, shapes, expects = build_pool(n_base, rng)
    gen_s = time.time() - t0

    native = load_native_verifier()
    oracle = (
        (lambda xs: native.verify_batch(xs))
        if native is not None else verify_batch_cpu
    )

    t0 = time.time()
    mismatches = []
    tally: dict[str, list[int]] = {}
    for lo in range(0, len(items), batch):
        chunk = items[lo:lo + batch]
        got = device_verify(chunk, batch)
        expect = oracle(chunk)
        for j, (g, e) in enumerate(zip(got, expect)):
            shape = shapes[lo + j]
            ok_n, n = tally.get(shape, [0, 0])
            tally[shape] = [ok_n + (1 if g else 0), n + 1]
            if g != e or g != expects[lo + j]:
                mismatches.append(
                    {"index": lo + j, "shape": shape, "device": g,
                     "oracle": e, "required": expects[lo + j]}
                )
    run_s = time.time() - t0
    return {
        "items": len(items),
        "mismatches": len(mismatches),
        "mismatch_detail": mismatches[:10],
        "kernel": "pallas-interpret" if pallas else "xla",
        "gen_s": round(gen_s, 1),
        "run_s": round(run_s, 1),
        "oracle": "native-cpp" if native is not None else "python",
        "tally": {k: {"accepted": v[0], "total": v[1]}
                  for k, v in sorted(tally.items())},
    }


def main() -> None:
    pallas = "--pallas" in sys.argv
    pos = [a for a in sys.argv[1:] if a != "--pallas"]
    n_base = int(pos[0]) if pos else (32 if pallas else 256)
    batch = int(pos[1]) if len(pos) > 1 else (256 if pallas else 2048)
    if pallas and batch % 32:
        sys.exit(f"--pallas batch must be a multiple of the 32-lane "
                 f"interpret block (got {batch})")
    res = run_campaign(n_base, batch, pallas=pallas)
    print(json.dumps(res))
    if res["mismatches"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
