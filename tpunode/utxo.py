"""Persistent UTXO store behind the prevout-oracle seam (ISSUE 9 /
ROADMAP item 5).

The node's verify paths need prevout data — satoshi amount and
scriptPubKey — for BIP143 (P2WPKH / BCH FORKID) and BIP341 (taproot)
digests.  Intra-block spends resolve from the block itself and unconfirmed
parents from the mempool; everything *confirmed* used to require the
embedder's ``NodeConfig.prevout_lookup``.  :class:`UtxoStore` fills that
gap with a durable UTXO set over any :class:`~tpunode.store.KVStore`
(the node wires it over a ``Namespaced`` view of its main store, so one
crash-consistent LogKV holds headers and UTXOs side by side).

Crash consistency contract:

* block connect applies every spend + create **and** the block-height
  watermark in ONE atomic ``write_batch`` — a record-level-atomic log
  (LogKV v2) therefore never persists half a block;
* the watermark is monotone: :meth:`apply` refuses heights at or below it,
  so a crash-then-replay of the same block stream is idempotent (the
  re-delivered blocks are skipped, counted in ``utxo.skipped``);
* lookups never see a partially-connected block: the in-memory index the
  store serves reads from is only mutated by the same atomic batch.

Reorg support (ISSUE 11): every connect also writes a per-block UNDO
record — the spent prevouts' old values, the created keys and the prior
watermark — in the SAME atomic batch, retained for the newest
``undo_depth`` blocks (default 100).  :meth:`disconnect` pops the tip
block by replaying its undo record (again one atomic batch), so a reorg
at or beneath the watermark unwinds cleanly to the fork point instead of
going loudly stale; ``utxo.reorg_stale`` remains the fallback for reorgs
deeper than the retained undo depth.  Disconnect followed by re-connect
round-trips the UTXO set bit-identically (pinned by tests/test_utxo.py).

Block connect has one producer in the node: :meth:`apply_ops_blob`
consumes the C++ extractor's one-pass delta blob
(``ParsedTxRegion.utxo_ops``), so no Python per-tx parse is in block
ingest (node._apply_block_utxo, ISSUE 11).  :meth:`apply_block`, which
parses wire ``Tx`` objects in Python, is the reference the tests hold it
to, bit for bit.  The blob is never
unpacked into per-operation tuples: ``store.write_delta`` hands it to the
store, which frames it natively into the records it appends and gives
back only the keys and pre-spend values the undo record is made of
(ISSUE 26) — the same bytes in the same one append as the reference path.

A set can also arrive whole (ISSUE 31): :meth:`UtxoStore.load_snapshot`
fills an EMPTY store from batches of entries — the node that starts from a
UTXO snapshot (Bitcoin Core's ``loadtxoutset``) instead of replaying the
chain.  No undo record (there is no block to disconnect), the watermark
in the last batch; a crash mid-load leaves a marker and no watermark, and
the next load starts over (ROBUSTNESS.md).

Between a block's parse and its connect its outputs are in neither the
mempool nor this set, while later blocks of a real chain spend them: most
outputs are spent within blocks of being made (Bitcoin Core's coin cache
marks such a coin ``FRESH``; ``ConnectBlock`` reads inputs from a view that
already holds every earlier block's outputs).  :class:`InflightOutputs`
is that view for the blocks a node has parsed and not yet connected
(ISSUE 44): memory only, filled from the block's parse, emptied by the
connect — after a restart the node resumes at the watermark and the
blocks above it publish their outputs again as they are re-fetched.

Schema (within the namespaced view): ``b"o" + txid + vout_le32`` ->
``amount_le64 + scriptPubKey``; ``b"!wm"`` -> ``height_le64 + block_hash``;
``b"U" + height_le64`` -> undo record; ``b"!ld"`` -> a snapshot load has
begun (gone with the batch that writes the watermark).
"""

from __future__ import annotations

import struct
from typing import Iterable, Optional, Sequence

import numpy as np

from . import threadsan

from .events import events
from .metrics import metrics
from .store import (
    BatchOp, KVStore, count_prefix, delete_op, get_many, key_growth, put_op,
    write_delta,
)
from .trace import span

__all__ = [
    "UtxoStore", "InflightOutputs", "NativeInflightOutputs",
    "UTXO_NAMESPACE", "UNDO_DEPTH_DEFAULT", "snapshot_batch",
]

#: The namespace the node mounts the UTXO set under on its main store.
UTXO_NAMESPACE = b"u/"

#: Default per-block UNDO retention: reorgs up to this deep beneath the
#: watermark disconnect cleanly; deeper ones fall back to reorg_stale.
UNDO_DEPTH_DEFAULT = 100

_WM_KEY = b"!wm"
_LOAD_KEY = b"!ld"
_OUT_PREFIX = b"o"
_UNDO_PREFIX = b"U"
_AMOUNT = struct.Struct("<q")
_WM = struct.Struct("<q")
_U32 = struct.Struct("<I")
_PUT_V1 = struct.Struct("<BII")  # a delta blob's record head: op, klen, vlen
_ZERO_TXID = b"\x00" * 32
#: keys a delete batch when an unfinished load is cleared away
_WIPE_BATCH = 1 << 16


def _okey(txid: bytes, vout: int) -> bytes:
    return _OUT_PREFIX + txid + vout.to_bytes(4, "little")


def _ukey(height: int) -> bytes:
    return _UNDO_PREFIX + _WM.pack(height)


def snapshot_batch(entries: Iterable[tuple[bytes, int, int, bytes]]) -> bytes:
    """``(txid, vout, amount, script)`` entries as one batch of
    :meth:`UtxoStore.load_snapshot`: a delta blob of puts (the format of
    ``ParsedTxRegion.utxo_ops`` — ``op=1, klen, vlen`` little-endian, then
    ``b"o" + txid + vout_le32`` and ``amount_le64 + script``).  The plain
    way to make one; a loader with its set in columns packs the same
    records without a Python object an entry."""
    parts = []
    for txid, vout, amount, script in entries:
        key = _okey(txid, vout)
        parts.append(_PUT_V1.pack(1, len(key), _AMOUNT.size + len(script)))
        parts.append(key + _AMOUNT.pack(amount) + script)
    return b"".join(parts)


class UtxoStore:
    """A persistent UTXO set + block-height watermark over a KV store."""

    def __init__(self, kv: KVStore, undo_depth: int = UNDO_DEPTH_DEFAULT):
        self._kv = kv
        self.undo_depth = max(0, int(undo_depth))
        wm = kv.get(_WM_KEY)
        if wm is None:
            self._height, self._block_hash = -1, None
        else:
            self._height = _WM.unpack_from(wm)[0]
            self._block_hash = wm[_WM.size :] or None
        if self._height >= 0:
            metrics.set_gauge("utxo.height", float(self._height))
        # a snapshot load that began and never wrote its watermark: what
        # it left is no set (a prefix of one), and nothing connects over
        # it until a load starts over
        self._load_unfinished = wm is None and kv.get(_LOAD_KEY) is not None
        if self._load_unfinished:
            metrics.inc("utxo.load_unfinished")
            events.emit("utxo.load_unfinished")
        # live outputs: counted once at open (one walk of the index's
        # keys, the only one there is), then kept by every write
        self._entries = count_prefix(kv, _OUT_PREFIX)
        metrics.set_gauge("utxo.entries", float(self._entries))

    # -- prevout oracle ------------------------------------------------------

    @property
    def height(self) -> int:
        """The watermark: every block at or below this height is fully
        applied (−1 = empty store)."""
        return self._height

    @property
    def block_hash(self) -> Optional[bytes]:
        return self._block_hash

    @property
    def entries(self) -> int:
        """Live outputs in the set (the ``utxo.entries`` gauge)."""
        return self._entries

    def lookup(self, txid: bytes, vout: int) -> Optional[tuple[int, bytes]]:
        """The prevout-oracle callable (``NodeConfig.prevout_lookup``
        shape): ``(amount, scriptPubKey)`` or None."""
        raw = self._kv.get(_okey(txid, vout))
        if raw is None:
            return None  # unknown or already spent
        return _AMOUNT.unpack_from(raw)[0], raw[_AMOUNT.size :]

    def lookup_many(
        self, outpoints: Iterable[bytes]
    ) -> list[Optional[tuple[int, bytes]]]:
        """:meth:`lookup` for every outpoint, in order.  An outpoint is
        its 36 wire bytes — ``txid ++ vout_le32``, the key's tail as it
        stands — so no key is built from parts, and the store is read in
        one batch (``store.get_many``).  One ``utxo.lookup`` span a call;
        ``utxo.lookup_rows`` counts what was asked and ``utxo.lookup_hits``
        what the set held."""
        unpack, head = _AMOUNT.unpack_from, _AMOUNT.size
        with span("utxo.lookup"):
            raws = get_many(self._kv, outpoints, _OUT_PREFIX)
            out = [
                None if raw is None else (unpack(raw)[0], raw[head:])
                for raw in raws
            ]
        metrics.inc("utxo.lookup_rows", len(raws))
        metrics.inc("utxo.lookup_hits", len(raws) - raws.count(None))
        return out

    # -- snapshot load (ISSUE 31) --------------------------------------------

    def load_snapshot(
        self, height: int, block_hash: bytes, batches: Iterable[bytes]
    ) -> int:
        """Fill an EMPTY set from a snapshot taken at ``(height,
        block_hash)``; -> the entries loaded.  ``batches`` yields delta
        blobs of puts (:func:`snapshot_batch`'s format); each goes through
        the store's own write path as it stands (``store.write_delta``: one
        native framing, one append, one fsync under ``fsync=True``, no
        Python object an entry but the two the index keeps), so a batch is
        also one hold of the store's lock: some 10^5 entries is a good
        size.  No undo record — there is no block under a snapshot to
        disconnect to.

        A store with a watermark refuses (``ValueError``): a snapshot never
        lands on a set that blocks built.  The crash contract: a marker is
        durable before the first entry, and the batch that carries the
        watermark takes it away; a crash in between leaves the marker and
        no watermark, the next open reports ``utxo.load_unfinished`` and
        connects nothing, and the next ``load_snapshot`` clears what is
        there and starts over."""
        if self._height >= 0:
            raise ValueError(
                f"load_snapshot needs an empty set: the watermark is at "
                f"height {self._height}"
            )
        if height < 0 or not block_hash:
            raise ValueError("a snapshot is taken at a block")
        with span("utxo.load"):
            if self._load_unfinished or self._entries:
                self._wipe_outputs()
            self._kv.write_batch([put_op(_LOAD_KEY, b"\x01")])
            self._load_unfinished = True
            held = b""  # one behind, so that the last batch is known
            for blob in batches:
                if held:
                    self._entries += write_delta(
                        self._kv, held, lambda *_: ()
                    )
                held = blob
            closing = [
                put_op(_WM_KEY, _WM.pack(height) + block_hash),
                delete_op(_LOAD_KEY),
            ]
            if held:
                self._entries += write_delta(
                    self._kv, held, lambda *_: closing
                )
            else:
                self._kv.write_batch(closing)
            self._load_unfinished = False
            self._height, self._block_hash = height, block_hash
        metrics.set_gauge("utxo.height", float(height))
        metrics.set_gauge("utxo.entries", float(self._entries))
        metrics.inc("utxo.loaded", self._entries)
        events.emit("utxo.snapshot", height=height, entries=self._entries)
        return self._entries

    def _wipe_outputs(self) -> None:
        """Delete every output row, in bounded batches: what an unfinished
        load left behind."""
        keys: list[bytes] = []
        for key, _ in self._kv.scan_prefix(_OUT_PREFIX):
            keys.append(key)
            if len(keys) >= _WIPE_BATCH:
                self._kv.write_batch([delete_op(k) for k in keys])
                keys = []
        if keys:
            self._kv.write_batch([delete_op(k) for k in keys])
        self._entries = 0

    def _refuse_over_unfinished_load(self) -> None:
        if self._load_unfinished:
            raise RuntimeError(
                "the UTXO set holds an unfinished snapshot load: call "
                "load_snapshot again before connecting blocks"
            )

    # -- block connect -------------------------------------------------------

    def apply(
        self,
        height: int,
        block_hash: bytes,
        spends: Iterable[tuple[bytes, int]],
        creates: Iterable[tuple[bytes, int, int, bytes]],
    ) -> bool:
        """Connect one block's UTXO delta atomically.

        ``spends`` are ``(txid, vout)`` outpoints consumed; ``creates`` are
        ``(txid, vout, amount, script)`` outputs born.  Everything lands in
        ONE ``write_batch`` together with the advanced watermark (and the
        block's UNDO record), so the store can never hold half a block.
        Heights at or below the watermark are refused (idempotent
        crash-replay); contiguity is the CALLER's job — skipping a height
        would strand that block's delta below the watermark forever (the
        node enforces watermark+1-only connects, ``node._apply_block_utxo``).

        Returns True when applied, False when skipped as already-persisted.
        """
        if height <= self._height:
            metrics.inc("utxo.skipped")
            return False
        self._refuse_over_unfinished_load()
        ops: list[BatchOp] = []
        created_keys: list[bytes] = []
        spent_pairs: list[tuple[bytes, bytes]] = []
        for txid, vout, amount, script in creates:
            key = _okey(txid, vout)
            ops.append(put_op(key, _AMOUNT.pack(amount) + script))
            created_keys.append(key)
        want_undo = self.undo_depth > 0  # pre-spend reads are undo-only
        n_spent = 0
        for txid, vout in spends:
            key = _okey(txid, vout)
            if want_undo:
                old = self._kv.get(key)
                if old is not None:
                    spent_pairs.append((key, old))
            ops.append(delete_op(key))
            n_spent += 1
        return self._commit(
            height, block_hash, ops, spent_pairs, created_keys,
            len(created_keys), n_spent,
        )

    def apply_block(self, height: int, block_hash: bytes, txs: Sequence) -> bool:
        """Connect a block from parsed tx objects (wire.Tx/LazyTx shape:
        ``.txid``, ``.inputs[].prevout.{txid,index}``,
        ``.outputs[].{value,script}``).  Creates are emitted before spends
        *per the whole block*, and write_batch applies ops in order, so a
        same-block child spending a parent's output nets out correctly."""
        if height <= self._height:
            metrics.inc("utxo.skipped")
            return False
        creates: list[tuple[bytes, int, int, bytes]] = []
        spends: list[tuple[bytes, int]] = []
        for tx in txs:
            txid = tx.txid
            for vout, out in enumerate(tx.outputs):
                creates.append((txid, vout, out.value, out.script))
            for txin in tx.inputs:
                prev = txin.prevout
                if prev.txid == _ZERO_TXID:
                    continue  # coinbase input spends nothing
                spends.append((prev.txid, prev.index))
        applied = self.apply(height, block_hash, spends, creates)
        if applied:
            events.emit(
                "utxo.block", height=height, created=len(creates),
                spent=len(spends),
            )
        return applied

    def apply_ops_blob(
        self, height: int, block_hash: bytes, blob: bytes,
        created: int, spent: int,
    ) -> bool:
        """Connect a block from the C++ extractor's one-pass delta blob
        (``ParsedTxRegion.utxo_ops`` — creates then spends in v1 record
        format, ISSUE 11): the hot-path twin of :meth:`apply_block`.  The
        blob goes to the store as it is (``store.write_delta``: LogKV
        frames it natively into the records it appends) and comes back
        only as the keys the undo record needs — no Python object per
        operation (ISSUE 26).  Bit-identical log, index and undo record
        (pinned by tests/test_utxo.py, tests/test_utxo_delta.py)."""
        if height <= self._height:
            metrics.inc("utxo.skipped")
            return False
        self._refuse_over_unfinished_load()

        def tail(put_keys, del_keys, del_olds, strip):
            spent_pairs, created_keys = [], []
            if self.undo_depth > 0:  # both are the undo record's only
                spent_pairs = [
                    (k[strip:], old) for k, old in zip(del_keys, del_olds)
                    if old is not None
                ]
                created_keys = [k[strip:] for k in put_keys]
            return self._tail_ops(
                height, block_hash, spent_pairs, created_keys
            )

        grew = write_delta(self._kv, blob, tail)
        self._advance(height, block_hash, created, spent, grew)
        events.emit(
            "utxo.block", height=height, created=created, spent=spent,
        )
        return True

    def _commit(
        self,
        height: int,
        block_hash: bytes,
        ops: list[BatchOp],
        spent_pairs: list[tuple[bytes, bytes]],
        created_keys: list[bytes],
        created: int,
        spent: int,
    ) -> bool:
        """One atomic connect: delta + undo record + watermark."""
        grew = key_growth(self._kv, ops)  # the delta's: live outputs
        ops.extend(
            self._tail_ops(height, block_hash, spent_pairs, created_keys)
        )
        self._kv.write_batch(ops)
        self._advance(height, block_hash, created, spent, grew)
        return True

    def _tail_ops(
        self,
        height: int,
        block_hash: bytes,
        spent_pairs: list[tuple[bytes, bytes]],
        created_keys: list[bytes],
    ) -> list[BatchOp]:
        """What closes a connect's batch after its delta: the undo record,
        the expiry of the one that leaves the retained depth, the
        watermark."""
        ops: list[BatchOp] = []
        if self.undo_depth > 0:
            ops.append(put_op(
                _ukey(height),
                self._pack_undo(
                    self._height, self._block_hash, spent_pairs,
                    created_keys,
                ),
            ))
            expired = height - self.undo_depth
            if expired >= 0:
                ops.append(delete_op(_ukey(expired)))
        ops.append(put_op(_WM_KEY, _WM.pack(height) + block_hash))
        return ops

    def _advance(
        self, height: int, block_hash: bytes, created: int, spent: int,
        grew: int,
    ) -> None:
        self._height, self._block_hash = height, block_hash
        self._entries += grew
        metrics.set_gauge("utxo.height", float(height))
        metrics.set_gauge("utxo.entries", float(self._entries))
        metrics.inc("utxo.applied")
        metrics.inc("utxo.created", created)
        metrics.inc("utxo.spent", spent)

    # -- per-block UNDO (ISSUE 11) -------------------------------------------

    @staticmethod
    def _pack_undo(
        prior_height: int,
        prior_hash: Optional[bytes],
        spent_pairs: list[tuple[bytes, bytes]],
        created_keys: list[bytes],
    ) -> bytes:
        """Undo record: the exact prior watermark (height + hash), the
        spent keys with their pre-spend values, the created keys —
        everything disconnect needs to restore the exact prior state."""
        ph = prior_hash or b""
        parts = [_WM.pack(prior_height), _U32.pack(len(ph)), ph,
                 _U32.pack(len(spent_pairs))]
        for key, val in spent_pairs:
            parts.append(_U32.pack(len(key)) + key)
            parts.append(_U32.pack(len(val)) + val)
        parts.append(_U32.pack(len(created_keys)))
        for key in created_keys:
            parts.append(_U32.pack(len(key)) + key)
        return b"".join(parts)

    def undo_available(self, height: Optional[int] = None) -> bool:
        """Is the undo record for ``height`` (default: the tip) retained?"""
        h = self._height if height is None else height
        return h >= 0 and self._kv.get(_ukey(h)) is not None

    def disconnect(self) -> bool:
        """Disconnect the tip block by replaying its undo record in ONE
        atomic batch: created outputs deleted, spent outputs restored with
        their pre-spend values, the watermark rolled back to the exact
        prior (height, hash) the record carries.

        Returns False — leaving the store untouched — when the tip has no
        retained undo record (reorg deeper than ``undo_depth``: the
        loudly-stale fallback is the caller's next move)."""
        if self._height < 0:
            return False
        raw = self._kv.get(_ukey(self._height))
        if raw is None:
            metrics.inc("utxo.undo_missing")
            return False
        pos = 0
        prior_height = _WM.unpack_from(raw, pos)[0]
        pos += _WM.size
        phlen = _U32.unpack_from(raw, pos)[0]
        pos += _U32.size
        prior_hash = raw[pos : pos + phlen] or None
        pos += phlen
        n_spent = _U32.unpack_from(raw, pos)[0]
        pos += _U32.size
        restores: list[tuple[bytes, bytes]] = []
        for _ in range(n_spent):
            klen = _U32.unpack_from(raw, pos)[0]
            pos += _U32.size
            key = raw[pos : pos + klen]
            pos += klen
            vlen = _U32.unpack_from(raw, pos)[0]
            pos += _U32.size
            restores.append((key, raw[pos : pos + vlen]))
            pos += vlen
        n_created = _U32.unpack_from(raw, pos)[0]
        pos += _U32.size
        ops: list[BatchOp] = []
        for _ in range(n_created):
            klen = _U32.unpack_from(raw, pos)[0]
            pos += _U32.size
            ops.append(delete_op(raw[pos : pos + klen]))
            pos += klen
        for key, val in restores:
            ops.append(put_op(key, val))
        grew = key_growth(self._kv, ops)  # output rows only, so far
        ops.append(delete_op(_ukey(self._height)))
        if prior_height >= 0:
            ops.append(put_op(
                _WM_KEY, _WM.pack(prior_height) + (prior_hash or b"")
            ))
        else:
            ops.append(delete_op(_WM_KEY))
        self._kv.write_batch(ops)
        disconnected = self._height
        self._height = prior_height
        self._block_hash = prior_hash if prior_height >= 0 else None
        self._entries += grew
        metrics.set_gauge("utxo.height", float(max(prior_height, -1)))
        metrics.set_gauge("utxo.entries", float(self._entries))
        metrics.inc("utxo.disconnected")
        events.emit(
            "utxo.undo", height=disconnected,
            restored=len(restores), removed=n_created,
        )
        return True

    def snapshot(self) -> dict[bytes, bytes]:
        """Every unspent output row (test/bit-identity probe; the undo
        round-trip and native-vs-python connect pins compare these)."""
        return dict(self._kv.scan_prefix(_OUT_PREFIX))

    def stats(self) -> dict:
        return {
            "enabled": True,
            "height": self._height,
            "undo_depth": self.undo_depth,
            "applied": metrics.get("utxo.applied"),
            "skipped": metrics.get("utxo.skipped"),
            "created": metrics.get("utxo.created"),
            "spent": metrics.get("utxo.spent"),
            "disconnected": metrics.get("utxo.disconnected"),
        }


class InflightOutputs:
    """Outpoint -> ``(amount, scriptPubKey)`` for every output created by
    a block that is parsed and not yet connected (ISSUE 44): the prevout
    source between the mempool and the UTXO set.

    A block's outputs are *published* by the job that parses it, in its
    worker thread, *retired* by the connect once the store holds them,
    and *dropped* on every path that lets the block go without one.  An
    outpoint's value is fixed by its txid, so the view never holds a
    wrong answer, only one that is there or not: the connect makes the
    store's copy visible before it retires the view's, and a reader that
    asks the view first and the store second finds the output in one of
    them.  The fetch planner's ``max_lead`` (and the node's
    ``MAX_VERIFY_PENDING``) bound how many blocks are here at once.

    This class keeps the rows in a dict, a Python statement an output:
    the reference, and the base, of the :class:`NativeInflightOutputs` a
    node keeps (tests/test_chain_cell.py holds the one to the other)."""

    def __init__(self):
        self._lock = threadsan.lock("utxo.inflight")
        self._prev: dict[bytes, bytes] = {}  # block -> the block beneath
        self._index: dict[bytes, tuple[int, bytes]] = {}
        self._keys: dict[bytes, list[bytes]] = {}  # block -> its outpoints
        # two blocks in flight made the same outpoint (the same tx on two
        # branches): forgetting one must leave the other's rows indexed
        self._overlap = False

    def __len__(self) -> int:
        return len(self._index)

    @property
    def blocks(self) -> int:
        return len(self._prev)

    def prev_of(self, block_hash: bytes) -> Optional[bytes]:
        """The block beneath a block whose outputs are here; None for a
        block that has none here."""
        return self._prev.get(block_hash)

    # -- writers (worker threads) ---------------------------------------------

    def publish_txs(
        self, block_hash: bytes, prev: bytes, txs: Sequence
    ) -> None:
        """A block's outputs from its parsed txs; ``prev`` the hash of the
        block beneath it.  A block delivered again: the newer parse's."""
        rows = {
            tx.txid + _U32.pack(vout): (out.value, out.script)
            for tx in txs
            for vout, out in enumerate(tx.outputs)
        }
        with self._lock:
            self._forget(block_hash)
            self._prev[block_hash] = prev
            self._install(block_hash, rows)
        metrics.inc("node.inflight_outputs_added", len(rows))

    def retire(self, block_hash: bytes) -> int:
        """The block is connected: the store answers for its outputs."""
        with self._lock:
            n = self._forget(block_hash)
        if n:
            metrics.inc("node.inflight_outputs_retired", n)
        return n

    def drop(self, block_hash: bytes) -> int:
        """The block left without a connect (its verification failed, it
        was parked past the bound, a reorg unwound beneath it, its connect
        failed).  Nothing to do where it was retired or never published
        (the loop asks for every block that is through: no lock then)."""
        if block_hash not in self._prev:
            return 0
        with self._lock:
            n = self._forget(block_hash)
        if n:
            metrics.inc("node.inflight_outputs_dropped", n)
        return n

    def _install(self, block_hash: bytes, rows: dict) -> None:
        before = len(self._index)
        self._index.update(rows)
        if len(self._index) - before != len(rows):
            self._overlap = True
        self._keys[block_hash] = list(rows)

    def _forget(self, block_hash: bytes) -> int:
        if self._prev.pop(block_hash, None) is None:
            return 0
        keys = self._keys.pop(block_hash)
        gone = {key: self._index.pop(key, None) for key in keys}
        if self._overlap:
            # the rows another block made too are that block's again
            left = sum(map(len, self._keys.values()))
            for other in self._keys.values():
                self._index.update(
                    (key, gone[key]) for key in other if key in gone
                )
            self._overlap = len(self._index) != left
        return len(keys)

    def stats(self) -> dict:
        return {
            "blocks": self.blocks,
            "outputs": len(self),
            "added": metrics.get("node.inflight_outputs_added"),
            "retired": metrics.get("node.inflight_outputs_retired"),
            "dropped": metrics.get("node.inflight_outputs_dropped"),
        }

    # -- readers (the loop) -----------------------------------------------------

    def lookup(self, txid: bytes, vout: int) -> Optional[tuple[int, bytes]]:
        """The prevout-oracle callable, as ``UtxoStore.lookup``."""
        return self._index.get(txid + _U32.pack(vout))

    def lookup_many(
        self, outpoints: Sequence[bytes]
    ) -> list[Optional[tuple[int, bytes]]]:
        """:meth:`lookup` for every outpoint (its 36 wire bytes), in
        order."""
        return list(map(self._index.get, outpoints))


class NativeInflightOutputs(InflightOutputs):
    """:class:`InflightOutputs` with its rows in the native library
    (``txx_view_*``, native/txextract): a block is published straight
    from its parse handle in one call that holds no interpreter lock —
    nothing here costs a Python statement an output, at 66k outputs a
    32 MB block — and a batch of lookups is one call."""

    def __init__(self):
        from .txextract import load_txextract_lib

        super().__init__()
        self._lib = load_txextract_lib()
        self._view = self._lib.txx_view_new()
        # the loop's lookups write here: one reader, nothing allocated a call
        self._rows(256)
        self._scripts = np.empty(1 << 16, np.uint8)

    def _rows(self, n: int) -> None:
        self._hit = np.empty(n, np.uint8)
        self._amounts = np.empty(n, np.int64)
        self._ends = np.empty(n, np.int64)
        self._to = tuple(
            a.ctypes.data for a in (self._hit, self._amounts, self._ends)
        )

    def __del__(self):
        view, self._view = getattr(self, "_view", None), None
        if view:
            self._lib.txx_view_free(view)

    def __len__(self) -> int:
        return self._lib.txx_view_size(self._view)

    def publish_region(self, block_hash: bytes, prev: bytes, region) -> None:
        """A block's outputs from its open parse (``ParsedTxRegion``)."""
        # the library has a lock of its own: nothing of this class's is
        # held across a call that takes milliseconds for a large block
        n = region.publish_outputs(self._view, block_hash)
        self._prev[block_hash] = prev
        metrics.inc("node.inflight_outputs_added", n)

    def _install(self, block_hash: bytes, rows: dict) -> None:
        scripts = [script for _, script in rows.values()]
        self._lib.txx_view_publish_rows(
            self._view, block_hash, len(rows), b"".join(rows),
            np.array([amount for amount, _ in rows.values()], np.int64),
            np.cumsum([len(s) for s in scripts], dtype=np.int64),
            b"".join(scripts),
        )

    def _forget(self, block_hash: bytes) -> int:
        self._prev.pop(block_hash, None)
        return self._lib.txx_view_forget(self._view, block_hash)

    def lookup(self, txid: bytes, vout: int) -> Optional[tuple[int, bytes]]:
        return self.lookup_many([txid + _U32.pack(vout)])[0]

    def lookup_many(
        self, outpoints: Sequence[bytes]
    ) -> list[Optional[tuple[int, bytes]]]:
        n = len(outpoints)
        if len(self._hit) < n:
            self._rows(2 * n)
        keys = b"".join(outpoints)
        while True:
            hits = self._lib.txx_view_lookup_held(
                self._view, keys, n, *self._to,
                self._scripts.ctypes.data, len(self._scripts),
            )
            if hits >= 0:
                break
            self._scripts = np.empty(-2 * hits, np.uint8)
        if not hits:
            return [None] * n
        ends = self._ends[:n].tolist()
        scripts = self._scripts[: ends[-1]].tobytes()
        return [
            (amount, scripts[lo:hi]) if hit else None
            for hit, amount, lo, hi in zip(
                self._hit[:n].tolist(), self._amounts[:n].tolist(),
                [0] + ends, ends,
            )
        ]
