"""chip_smoke.py on a box with no chip: the plain command must fail fast
without running (or compiling) anything, and ``--dryrun`` must walk every
leg at tiny size on the C++ rung — remote, chain, counters and JSON
debugged for free — while never claiming ``"ok": true``."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*flags: str, timeout: float = 300.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, as in the sandbox
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *flags],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_plain_command_fails_without_a_tpu():
    t0 = time.monotonic()
    proc = _smoke(timeout=120.0)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result line of any kind
    assert "not a TPU" in proc.stderr
    # jax import + device query only: no engine, no chain, no compile
    assert time.monotonic() - t0 < 60


def test_dryrun_walks_every_leg_and_never_says_ok():
    proc = _smoke("--dryrun")
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    final, legs = rows[-1], {r["leg"]: r for r in rows[:-1]}
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    # the last line holds exactly the two keys the chip check reads
    assert final == {"ok": False, "device": device}
    assert '"ok": true' not in proc.stdout
    assert list(legs) == ["env", "engine", "node", "four_chips", "summary"]
    for row in legs.values():  # every result line: device, jax, dryrun
        assert row["device"] == device
        assert row["jax"] and row["dryrun"] is True and row["passed"] is True
    assert legs["summary"]["legs"] == ["env", "engine", "node", "four_chips"]
    # every item of both legs on the one rung that was asked for
    for leg, submitted in (("engine", legs["engine"]["sigs"]),
                           ("node", legs["node"]["device_items"])):
        c = legs[leg]["served"]["counters"]
        assert c["verify.cpu_items"] == submitted > 0
        assert c["verify.tpu_items"] == c["verify.oracle_items"] == 0
        assert c["verify.failovers"] == c["verify.dispatch_errors"] == 0
    node = legs["node"]
    assert node["utxo_height"] == node["blocks"] == node["fetched_blocks"]
    assert node["refetches"] == 0 and node["coverage"] >= 0.90
    assert node["txs"] == node["blocks"] * 65  # 64 mixed txs + a coinbase
    assert 0 < node["mempool_invalid"] < node["mempool_txs"]
    assert legs["four_chips"]["ran"] is False
