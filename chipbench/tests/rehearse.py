"""A cell at tiny size on the CPU: the C++ rung stands where the chip
would, so this debugs the harness and says nothing about a device."""

import asyncio
import time

from chipbench import harness

CPU = {"verify": {"backend": "cpu", "batch_size": 64, "device_batch": 256,
                  "mesh_devices": 0}}

# per traffic mix: numbers small enough for a test
TINY = {
    "ibd": {"backlog": {"parent_sigs_per_s": 6000, "min_blocks": 48},
            "ramp_seconds": 0.3, "reference_sample_txs": 60,
            "blocks_per_job": 16},
    "mempool": {"pool": {"parent_txs_per_s": 800, "extra_txs": 256},
                "ramp_seconds": 0.3, "reference_sample_txs": 60,
                "txs_per_job": 400},
    "blocks": {"txs_per_block": 512, "backlog": {"parent_blocks_per_s": 5},
               "reference_sample_txs": 60, "txs_per_job": 128},
}


def rehearse(cell: str, seconds: float = 3.0, seed: int = 7, trace=False,
             config=None, traffic=None) -> dict:
    bench, wl, cfg, tr = harness.load_cell(cell)
    r = harness.Rehearsal(harness.deep_merge(CPU, config or {}),
                          TINY[wl["traffic"]] if traffic is None else traffic)
    ctx = harness.Ctx(wl, bench, harness.deep_merge(cfg, r.config),
                      harness.deep_merge(tr, r.traffic), seed, seconds, trace,
                      r, time.monotonic())
    return asyncio.run(harness.run_cell(ctx))


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(rehearse(sys.argv[1])))
