"""The controls at a cell's own size: how many of the transactions a cell
offers each weakened verifier would misjudge.

    python3 chipbench/control.py --workload W --seeds 1,2,3 --seconds 30

For each seed it makes the cell's traffic exactly as a run of that length
would, then puts the plain reference, with one consensus check switched
off, in the program's place, over every adversarial transaction and a
seeded sample of the others.  A control that misjudges none would mean
``correct`` cannot see that fault.  Jax-free: it runs anywhere, and gives
the same counts everywhere.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROLS = {
    "accepts_everything": {"equation": False, "scalar_range": False,
                           "on_curve": False},
    "skips_range_checks": {"scalar_range": False},
    "skips_residue_check": {"residue": False},
}


async def one_seed(workload: str, seed: int, seconds: float) -> dict:
    import importlib

    from chipbench import harness

    bench, wl, config, traffic = harness.load_cell(workload)
    ctx = harness.Ctx(wl, bench, config, traffic, seed, seconds, False,
                      harness.Rehearsal(), time.monotonic())
    with harness.run_directory(ctx):
        harness.start_pool(ctx)
        try:
            driver = importlib.import_module(
                "chipbench.drivers." + traffic["driver"]).Driver(ctx)
            await driver.prepare()
            off = driver.offered
            odd = [t for t, v in off.expect.items() if t in off.raw and not all(v)]
            plain = [t for t in off.raw if all(off.expect[t])]
            some = odd + ctx.rng("control").sample(plain, min(2000, len(plain)))
            out = {"seed": seed, "txs_offered": len(off.raw),
                   "invalid_txs": len(odd), "compared": len(some)}
            for name, checks in CONTROLS.items():
                got = await harness.run_reference(ctx, off, some, checks)
                out[name] = sum(got[t] != off.expect[t] for t in some)
            return out
        finally:
            ctx.pool.terminate()
            ctx.pool.join()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    blind = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        row = asyncio.run(one_seed(args.workload, seed, args.seconds))
        print(json.dumps({"workload": args.workload, **row}), flush=True)
        blind += sum(row[name] == 0 for name in CONTROLS)
    return 1 if blind else 0


if __name__ == "__main__":
    sys.exit(main())
