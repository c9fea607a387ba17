"""A cell at tiny size on the CPU: the C++ rung stands where the chip
would, so this debugs the harness and says nothing about a device.

What makes a mix tiny is data, in the traffic file's own ``rehearsal``
section: ``traffic`` is merged over the file and ``config`` over the
configuration, so a cell that arrives as new files rehearses without an
edit here."""

import asyncio
import time

from chipbench import harness

CPU = {"verify": {"backend": "cpu", "batch_size": 64, "device_batch": 256,
                  "mesh_devices": 0}}


def tiny(traffic: str) -> dict:
    """The ``rehearsal.traffic`` overrides of ``traffic/<traffic>.json``."""
    return harness.load_json(harness.ROOT, "chipbench", "traffic",
                             traffic + ".json")["rehearsal"]["traffic"]


def rehearse(cell: str, seconds: float = 3.0, seed: int = 7, trace=False,
             config=None, traffic=None) -> dict:
    bench, wl, cfg, tr = harness.load_cell(cell)
    small = tr.get("rehearsal", {})
    r = harness.Rehearsal(
        harness.deep_merge(harness.deep_merge(CPU, small.get("config", {})),
                           config or {}),
        small.get("traffic", {}) if traffic is None else traffic)
    ctx = harness.Ctx(wl, bench, harness.deep_merge(cfg, r.config),
                      harness.deep_merge(tr, r.traffic), seed, seconds, trace,
                      r, time.monotonic())
    return asyncio.run(harness.run_cell(ctx))


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(rehearse(sys.argv[1])))
