"""Canned 15-block BCH-regtest chain fixture.

The wire bytes are ported from the reference test suite
(/root/reference/test/Haskoin/NodeSpec.hs:282-340 ``allBlocksBase64``) — they
are implementation-neutral serialized blocks mined on regtest, decoded here
with the production codec, exactly as the reference decodes them with its own.
"""

import os

from tpunode.util import Reader
from tpunode.wire import Block

_DATA = os.path.join(os.path.dirname(__file__), "data", "regtest_blocks.bin")


def all_blocks() -> list[Block]:
    with open(_DATA, "rb") as f:
        raw = f.read()
    r = Reader(raw)
    blocks = [Block.deserialize(r) for _ in range(15)]
    assert r.remaining() == 0
    return blocks


# ---- the Python reference a node's ingest is held to ------------------------


def tuples(verdicts) -> list:
    """``TxVerdict``s as the rows :func:`reference_verdicts` gives."""
    return [(v.txid, v.valid, tuple(v.verdicts), v.stats, v.error)
            for v in verdicts]


def reference_verdicts(txs, prevouts, bch: bool) -> list:
    """What a node must publish for ``txs`` (``wire.Tx``: one relayed tx,
    or a block's, in order), by the Python reference and nothing of
    ``Node``: ``txverify.intra_block_prevouts`` answers an in-block spend
    before ``prevouts`` is asked — ``(txid, vout) -> (amount, script)``, a
    plain amount or None, as ``NodeConfig.prevout_lookup``; None: no
    source — and that only for the inputs ``wants_amount`` marks; then
    ``extract_sig_items``, the Python verifier and ``combine_verdicts``.
    -> ``(txid, valid, per-signature verdicts, stats, None)`` a tx: the
    rows :func:`tuples` makes of a node's ``TxVerdict``s."""
    from tpunode.txverify import (
        combine_verdicts, extract_sig_items, intra_block_prevouts,
        wants_amount,
    )
    from tpunode.verify.ecdsa_cpu import verify_batch_cpu

    own = intra_block_prevouts(txs) if len(txs) > 1 else {}
    rows = []
    for tx in txs:
        amounts, scripts = {}, {}
        for idx, txin in enumerate(tx.inputs):
            key = (txin.prevout.txid, txin.prevout.index)
            res = own.get(key)
            if res is None and prevouts is not None and wants_amount(
                    tx, idx, bch):
                res = prevouts(*key)
            amount, script = res if isinstance(res, tuple) else (res, None)
            if amount is not None:
                amounts[idx] = amount
            if script is not None:
                scripts[idx] = script
        items, stats = extract_sig_items(
            tx, prevout_amounts=amounts or None, bch=bch,
            prevout_scripts=scripts or None)
        per_sig = tuple(combine_verdicts(
            items, verify_batch_cpu([i.verify_item for i in items])
        )) if items else ()
        rows.append((tx.txid, all(per_sig), per_sig, stats, None))
    return rows


def reference_set(blocks, snapshot=None, genesis: bytes = b"") -> dict:
    """Every record of the UTXO namespace — output rows, undo records,
    watermark — after ``blocks`` (heights 1..n) connect through the Python
    reference, ``UtxoStore.apply_block`` on a ``MemoryKV``; ``snapshot``:
    the ``load_snapshot`` batches under them, at ``genesis``."""
    from tpunode.store import MemoryKV
    from tpunode.utxo import UtxoStore

    kv = MemoryKV()
    ref = UtxoStore(kv)
    if snapshot is not None:
        ref.load_snapshot(0, genesis, snapshot)
    for height, blk in enumerate(blocks, start=1):
        assert ref.apply_block(height, blk.header.hash, list(blk.txs))
    return dict(kv.scan_prefix(b""))


def utxo_records(node) -> dict:
    """The node's UTXO namespace, whole: what :func:`reference_set` gives."""
    return dict(node.utxo._kv.scan_prefix(b""))
