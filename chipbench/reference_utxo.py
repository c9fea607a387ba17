"""The plain reference of the UTXO set: one Python dict.

Independent of the program (it imports nothing of ``tpunode``): the set is
``outpoint -> amount_le64 ++ scriptPubKey`` with an outpoint its 36 wire
bytes (``txid ++ vout_le32``), seeded with the very entries the snapshot
was made of, and every served block is applied in chain order from its
raw bytes through ``wirefmt.parse_tx`` — each input's outpoint deleted
(the coinbase's null outpoint spends nothing), each output added, the
coinbase's included.  A delete of an outpoint the set does not hold is
counted, never ignored: a chain that spends what the snapshot lacks is a
fault of the traffic, and the cell says so.

Signature verdicts are not this file's: they keep going through
``reference.py``.
"""

from __future__ import annotations

from chipbench import wirefmt as w

NULL_TXID = b"\x00" * 32


def entry(amount: int, script: bytes) -> bytes:
    return amount.to_bytes(8, "little") + script


class UtxoSet:
    def __init__(self):
        self.set: dict = {}
        self.spent: list = []  # outpoints deleted, in chain order
        self.created: list = []  # outpoints added by blocks, in chain order
        self.spent_absent = 0  # deletes of what was not there

    def seed(self, outpoints: list, values: list) -> None:
        """Snapshot entries: 36-byte outpoints and their values, in step."""
        self.set.update(zip(outpoints, values))

    def apply_block(self, raw: bytes) -> None:
        """One ``block`` payload: 80-byte header, tx count, txs."""
        n, off = w.read_varint(raw, 80)
        for _ in range(n):
            (_, ins, outs, _), end = w.parse_tx(raw, off)
            txid = w.sha256d(raw[off:end])
            off = end
            for vout, (value, script) in enumerate(outs):
                key = txid + vout.to_bytes(4, "little")
                self.set[key] = entry(value, script)
                self.created.append(key)
            for prev, index, _, _ in ins:
                if prev == NULL_TXID:
                    continue
                key = prev + index.to_bytes(4, "little")
                if self.set.pop(key, None) is None:
                    self.spent_absent += 1
                self.spent.append(key)
        if off != len(raw):
            raise ValueError("bytes left over after a block's last tx")

    def lookup(self, outpoint: bytes):
        """-> (amount, script) or None, as a node's own lookup answers."""
        raw = self.set.get(outpoint)
        if raw is None:
            return None
        return int.from_bytes(raw[:8], "little"), raw[8:]
