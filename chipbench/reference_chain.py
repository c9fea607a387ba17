"""The plain reference of a chain that spends its own outputs.

``reference.py``'s verifier — the same parse, the same four templates, the
same ``secp`` — under a prevout oracle that knows three kinds of outpoint.
An output the chain itself made carries the amount and script its parent
transaction gave it: the driver cuts both out of the parent's raw bytes
with ``wirefmt.parse_tx`` and hands them over as ``outpoint -> (amount,
script)``; nothing of it comes from the program under test.  A bare-P2PK
entry of the snapshot is ``outpoint -> script``, its amount a function of
the outpoint; every other outpoint is the snapshot's and a function of
itself altogether (``gen.synth_amount`` / ``gen.synth_script``: what the
snapshot was made of).  This file imports nothing of ``tpunode/`` or
``native/``.

The UTXO set's own reference stays ``reference_utxo.py``.
"""

from __future__ import annotations

from chipbench import secp
from chipbench import wirefmt as w
from chipbench.gen import synth_amount, synth_script
from chipbench.reference import tx_verdicts


class Oracle:
    def __init__(self, table: dict):
        self.table = table

    def __call__(self, txid: bytes, vout: int):
        known = self.table.get(txid + vout.to_bytes(4, "little"))
        if isinstance(known, tuple):  # an output of the chain: as it was made
            return known
        return synth_amount(txid, vout), known or synth_script(txid)


def check_job(job: dict) -> list:
    """Worker entry: [(txid, verdicts)] for raw txs under the given table
    and checks."""
    oracle = Oracle(job["p2pk"])
    checks = secp.Checks(**job.get("checks", {}))
    return [(w.sha256d(raw), tx_verdicts(raw, oracle, checks))
            for raw in job["raw"]]
