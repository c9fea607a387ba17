"""chipbench: run one cell of BENCHMARK.json on the chip it asks for.

    python3 chipbench/run.py --workload <config>.<traffic> --seed n \
        --seconds s --trace 0|1

One process on the chip (its children make traffic and run the reference,
and never touch JAX).  It fails, and prints no
result, when JAX reports no TPU or another number of chips than the cell's.
The last line of stdout is the result object; everything above it is
detail that nobody judges.

Python's string hashing is seeded per process unless ``PYTHONHASHSEED`` is
set: the command line starts itself again with it fixed at 0, so that the
node's dicts and sets are laid out alike in every run.  ``setup_s`` still
counts from the first start.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STARTED = "CHIPBENCH_STARTED"  # "<pid>:<monotonic>" of the first start


def fixed_hash_seed() -> None:
    """Start again under ``PYTHONHASHSEED=0`` (same pid) unless it is so."""
    if os.environ.get("PYTHONHASHSEED") == "0":
        return
    env = dict(os.environ, PYTHONHASHSEED="0")
    env[STARTED] = f"{os.getpid()}:{T_START!r}"
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def first_start() -> float:
    pid, _, t = os.environ.get(STARTED, "").partition(":")
    return float(t) if pid == str(os.getpid()) else T_START


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    bench, workload, config, traffic = harness.load_cell(args.workload)
    ctx = harness.Ctx(workload, bench, config, traffic, args.seed,
                      args.seconds, bool(args.trace), None, first_start())
    result = asyncio.run(harness.run_cell(ctx))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    fixed_hash_seed()
    sys.exit(main())
