"""Several runs of one cell, one after another, and their spread.

    python3 chipbench/study.py --workload W --seeds 11,12,13 --sets 2 \
        --seconds 30 [--trace 0] [--out chiprun_out/W.jsonl]

The parent never touches JAX: each run is a fresh ``chipbench/run.py``
process, as the driver makes them.  Every run's result line, and the
detail lines above it, go to ``--out``; the end of stdout gives, for each
metric and each set, the median and the spread (the distance between the
quartiles of ``statistics.quantiles(values, n=4)`` over the median) that a
bound has to be set from.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values: list) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--stop-on-failure", action="store_true",
                    help="a run that gives no result ends the study")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = args.out or os.path.join("chiprun_out", args.workload + ".jsonl")
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    sets: list = []
    bad = 0
    with open(out, "a") as log:
        for k in range(args.sets):
            rows = []
            for seed in seeds:
                t0 = time.monotonic()
                p = subprocess.run(
                    [sys.executable, os.path.join(HERE, "run.py"),
                     "--workload", args.workload, "--seed", str(seed),
                     "--seconds", str(args.seconds), "--trace", str(args.trace)],
                    capture_output=True, text=True, timeout=1500)
                lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
                res = json.loads(lines[-1]) if p.returncode == 0 and lines else None
                row = {"set": k, "seed": seed, "rc": p.returncode,
                       "wall_s": time.monotonic() - t0, "result": res,
                       "lines": [json.loads(ln) for ln in lines[:-1]]}
                if res is None or not res.get("correct"):
                    bad += 1
                    row["stderr"] = p.stderr[-3000:]
                    print(p.stderr[-3000:], file=sys.stderr)
                log.write(json.dumps(row) + "\n")
                log.flush()
                brief = {m: v["value"] for m, v in (res or {}).get("metrics", {}).items()}
                print(json.dumps({"set": k, "seed": seed, "rc": p.returncode,
                                  "correct": (res or {}).get("correct"),
                                  "wall_s": round(row["wall_s"], 1), **brief}),
                      flush=True)
                if res is not None:
                    rows.append(brief)
                elif args.stop_on_failure:
                    return 1
            sets.append(rows)
    for name in sorted({m for rows in sets for r in rows for m in r}):
        for k, rows in enumerate(sets):
            vals = [r[name] for r in rows if name in r]
            if name == "setup_s":
                vals = vals[1:] if k == 0 else vals  # the first run compiles
            if len(vals) >= 2:
                print(json.dumps({
                    "metric": name, "set": k, "n": len(vals),
                    "median": statistics.median(vals),
                    "spread": spread(vals) if len(vals) >= 3 else None,
                    "min": min(vals), "max": max(vals)}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
