"""The BTC cells' prevout data: what the embedder's lookup answers.

Every answer is a function of the outpoint, so neither the node's process
nor a reference job holds a table of the millions of outputs a chain
spends.  The generator makes each outpoint *from* the output it stands
for: the txid carries the output's 32-byte program (a P2TR output key, a
P2WSH script hash) or its 20-byte hash followed by twelve seeded bytes
(P2WPKH, P2SH, P2PKH), and ``vout % 5`` names the form.  Such a txid is as
good as any other 32 bytes: no input of these cells spends an output of the
chain itself (``reduced: spend_age``).

This is data the three parties are *given* — generator (to sign over),
node (``NodeConfig.prevout_lookup``) and plain reference — not code under
test: it shares nothing with ``tpunode/``.
"""

from __future__ import annotations

FORMS = ("p2tr", "p2wpkh", "p2sh", "p2wsh", "p2pkh")


def synth_amount(txid: bytes, vout: int) -> int:
    """Satoshis of the output at ``(txid, vout)``: 1,000 .. 20,000,999."""
    return 1_000 + (int.from_bytes(txid[-6:], "little") ^ vout) % 20_000_000


def synth_script(txid: bytes, vout: int) -> bytes:
    """The scriptPubKey of the output at ``(txid, vout)``."""
    form = vout % 5
    if form == 0:
        return b"\x51\x20" + txid
    if form == 1:
        return b"\x00\x14" + txid[:20]
    if form == 2:
        return b"\xa9\x14" + txid[:20] + b"\x87"
    if form == 3:
        return b"\x00\x20" + txid
    return b"\x76\xa9\x14" + txid[:20] + b"\x88\xac"


class Oracle:
    """``(txid, vout) -> (amount, scriptPubKey)``: the extended form of
    ``NodeConfig.prevout_lookup``.  ``p2pk`` is the table the harness hands
    every reference job; it stays empty here."""

    def __init__(self):
        self.p2pk: dict = {}

    def __call__(self, txid: bytes, vout: int):
        return synth_amount(txid, vout), synth_script(txid, vout)
