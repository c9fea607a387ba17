"""A UTXO set that arrives whole, and a node that answers its own prevouts
from it (ISSUE 31).

Store level: ``UtxoStore.load_snapshot`` against the benchmark's plain
reference (``chipbench/reference_utxo.py``, one dict) — round trip, the
refusal of a set that blocks built, no undo record, the watermark after the
last entry, a reopen, an interrupted load and the load that starts over.

Node level: a node with ``utxo=True`` and ``prevout_lookup=None`` over a
loaded snapshot gives the verdicts a node with the callback gives, and the
Python reference's (``tests/fixtures.py``), signature by signature; after N
connected blocks its set is the reference's; a row no source answers is
counted (``node.resolve_missing``), and ``utxo.lookup_hits`` and
``utxo.lookup_rows`` add up.
"""

from __future__ import annotations

import asyncio
import os
import random

import pytest

from chipbench import gen, reference_utxo
from chipbench import wirefmt as w
from tests.fakenet import poll_until
from tests.fixtures import (
    reference_set, reference_verdicts, tuples, utxo_records,
)
from tests.test_verdict_reuse import (
    NETJ, a_node, block_of, make_txs, plain_block,
)
from tpunode.events import events
from tpunode.metrics import metrics
from tpunode.store import LogKV, MemoryKV, Namespaced
from tpunode.utxo import UTXO_NAMESPACE, UtxoStore, snapshot_batch

GENESIS = w.sha256d(w.genesis_header(NETJ))
COUNTERS = ("node.resolve_rows", "node.resolve_missing",
            "node.resolve_oracle_calls", "utxo.lookup_rows",
            "utxo.lookup_hits")


def entries(n: int, seed: int = 1, width: int = 25) -> list:
    rng = random.Random(seed)
    return [(rng.randbytes(32), rng.randrange(4), rng.randrange(1, 10**9),
             rng.randbytes(width if k % 7 else 35)) for k in range(n)]


def batches(rows: list, size: int) -> list:
    return [snapshot_batch(rows[i:i + size]) for i in range(0, len(rows), size)]


def reference(rows: list) -> reference_utxo.UtxoSet:
    ref = reference_utxo.UtxoSet()
    ref.seed([t + v.to_bytes(4, "little") for t, v, _, _ in rows],
             [reference_utxo.entry(a, s) for _, _, a, s in rows])
    return ref


def same_set(u: UtxoStore, ref: reference_utxo.UtxoSet) -> bool:
    """The program's output rows against the reference's dict, whole."""
    return {k[1:]: v for k, v in u.snapshot().items()} == ref.set


def open_store(kind: str, tmp_path):
    if kind == "memory":
        return MemoryKV(), None
    log = LogKV(str(tmp_path / "kv.log"), fsync=True)
    return (Namespaced(log, UTXO_NAMESPACE) if kind == "namespaced-log"
            else log), log


# ---- the store ----------------------------------------------------------------


@pytest.mark.parametrize("size", [1000, 64, 1], ids=["one-batch", "five", "each"])
@pytest.mark.parametrize("kind", ["memory", "log", "namespaced-log"])
def test_a_loaded_snapshot_is_the_references_set(kind, size, tmp_path):
    rows = entries(300 if size > 1 else 40)
    kv, log = open_store(kind, tmp_path)
    u = UtxoStore(kv)
    loaded0 = metrics.get("utxo.loaded")
    assert u.load_snapshot(7, b"\x77" * 32, iter(batches(rows, size))) == len(rows)
    ref = reference(rows)
    assert same_set(u, ref) and u.entries == len(ref.set) == len(rows)
    assert (u.height, u.block_hash) == (7, b"\x77" * 32)
    assert metrics.get("utxo.loaded") - loaded0 == len(rows)
    assert metrics.get("utxo.entries") == len(rows)
    for txid, vout, amount, script in rows[::17]:
        assert u.lookup(txid, vout) == (amount, script) == ref.lookup(
            txid + vout.to_bytes(4, "little"))
    # no undo record: there is no block under a snapshot to go back to
    assert not u.undo_available() and not list(kv.scan_prefix(b"U"))
    assert u.disconnect() is False and u.height == 7
    assert kv.get(b"!ld") is None
    # blocks connect on top of it, and spends of its entries are real
    t, v, a, s = rows[0]
    assert u.apply(8, b"\x88" * 32, [(t, v)], [(b"\xaa" * 32, 0, 5, b"\x51")])
    assert u.entries == len(rows) and u.lookup(t, v) is None
    assert u.disconnect() and u.lookup(t, v) == (a, s) and u.entries == len(rows)
    if log is not None:
        log.close()


def test_an_empty_snapshot_is_a_watermark():
    u = UtxoStore(MemoryKV())
    assert u.load_snapshot(0, GENESIS, []) == 0
    assert (u.height, u.block_hash, u.entries) == (0, GENESIS, 0)


@pytest.mark.parametrize("how", ["apply", "load"])
def test_a_set_with_a_watermark_refuses_a_snapshot(how):
    kv = MemoryKV()
    u = UtxoStore(kv)
    if how == "apply":
        u.apply(1, b"\x11" * 32, [], [(b"\x01" * 32, 0, 9, b"\x51")])
    else:
        u.load_snapshot(1, b"\x11" * 32, batches(entries(5), 5))
    before = dict(kv._data)
    with pytest.raises(ValueError, match="empty set"):
        u.load_snapshot(2, b"\x22" * 32, batches(entries(9, seed=2), 9))
    assert kv._data == before and u.height == 1
    with pytest.raises(ValueError):
        UtxoStore(MemoryKV()).load_snapshot(-1, b"", [])


def test_a_reopened_store_holds_the_loaded_set(tmp_path):
    rows = entries(500)
    kv, log = open_store("namespaced-log", tmp_path)
    UtxoStore(kv).load_snapshot(3, b"\x33" * 32, batches(rows, 128))
    log.close()
    kv, log = open_store("namespaced-log", tmp_path)
    u = UtxoStore(kv)
    assert (u.height, u.block_hash, u.entries) == (3, b"\x33" * 32, 500)
    assert same_set(u, reference(rows))
    assert metrics.get("utxo.entries") == 500
    log.close()


def _log_size(tmp_path) -> int:
    return os.path.getsize(tmp_path / "kv.log.00000001.seg")


@pytest.mark.parametrize("cut,finished", [(0, True), (22, True), (84, False)],
                         ids=["whole", "marker-delete-torn", "watermark-torn"])
def test_the_watermark_is_the_last_thing_a_load_writes(cut, finished, tmp_path):
    """The log's tail after a load is the watermark's put (17 + 5 + 40
    bytes) and then the marker's delete (17 + 5): a tail torn inside the
    second leaves a finished load, one torn inside the first an unfinished
    one — every entry is before both."""
    rows = entries(200)
    kv, log = open_store("namespaced-log", tmp_path)
    UtxoStore(kv).load_snapshot(0, GENESIS, batches(rows, 64))
    log.close()
    with open(tmp_path / "kv.log.00000001.seg", "r+b") as f:
        f.truncate(_log_size(tmp_path) - cut)
    kv, log = open_store("namespaced-log", tmp_path)
    u = UtxoStore(kv)
    assert u.entries == 200
    assert (u.height == 0) is finished
    if finished:
        assert same_set(u, reference(rows))
        assert u.apply(1, b"\x11" * 32, [], [])
    else:
        with pytest.raises(RuntimeError, match="unfinished snapshot load"):
            u.apply(1, b"\x11" * 32, [], [])
    log.close()


@pytest.mark.parametrize("kind", ["memory", "namespaced-log"])
def test_an_interrupted_load_starts_over(kind, tmp_path):
    """The batches' source fails after two of five: the marker is there
    and the watermark is not, a reopen says so (counter and event) and
    connects nothing, and the next load clears the partial set first —
    nothing of the first snapshot is left in the second."""
    first, second = entries(320, seed=5), entries(150, seed=6)
    kv, log = open_store(kind, tmp_path)
    u = UtxoStore(kv)

    def failing():
        for k, blob in enumerate(batches(first, 64)):
            if k == 3:
                raise OSError("the snapshot's source went away")
            yield blob

    with pytest.raises(OSError):
        u.load_snapshot(0, GENESIS, failing())
    assert u.height == -1 and kv.get(b"!ld") is not None
    assert kv.get(b"!wm") is None and u.entries == 128  # one behind
    if log is not None:  # the next open sees it
        log.close()
        kv, log = open_store(kind, tmp_path)
    n0 = metrics.get("utxo.load_unfinished")
    e0 = events.counts().get("utxo.load_unfinished", 0)
    u = UtxoStore(kv)
    assert metrics.get("utxo.load_unfinished") - n0 == 1
    assert events.counts().get("utxo.load_unfinished", 0) - e0 == 1
    assert u.height == -1 and u.entries == 128
    with pytest.raises(RuntimeError, match="unfinished snapshot load"):
        u.apply_block(1, b"\x11" * 32, [])
    assert u.load_snapshot(0, GENESIS, batches(second, 64)) == 150
    assert same_set(u, reference(second)) and u.entries == 150
    assert kv.get(b"!ld") is None and u.height == 0
    if log is not None:
        log.close()


@pytest.mark.parametrize("kind", ["memory", "log"])
def test_count_prefix_counts_what_scan_prefix_yields(kind, tmp_path):
    from tpunode.store import count_prefix, put_op

    kv, log = open_store(kind, tmp_path)
    kv.write_batch([put_op(b"oa", b"1"), put_op(b"ob", b"2"),
                    put_op(b"p", b"3"), put_op(b"o", b"")])
    for store in (kv, Namespaced(kv, b"o")):
        for prefix in (b"", b"o", b"oa", b"zz"):
            assert count_prefix(store, prefix) == len(
                list(store.scan_prefix(prefix)))
    if log is not None:
        log.close()


@pytest.mark.parametrize("kind", ["memory", "namespaced-log"])
def test_lookup_hits_and_rows_add_up(kind, tmp_path):
    rows = entries(50)
    kv, log = open_store(kind, tmp_path)
    u = UtxoStore(kv)
    u.load_snapshot(0, GENESIS, batches(rows, 50))
    asked = [t + v.to_bytes(4, "little") for t, v, _, _ in rows[:30]]
    asked += [bytes([k]) * 36 for k in range(12)]  # not in the set
    before = {k: metrics.get(k) for k in
              ("utxo.lookup_rows", "utxo.lookup_hits", "span.utxo.lookup.count")}
    got = u.lookup_many(asked)
    moved = {k: metrics.get(k) - v for k, v in before.items()}
    assert moved == {"utxo.lookup_rows": 42, "utxo.lookup_hits": 30,
                     "span.utxo.lookup.count": 1}
    assert got[:30] == [(a, s) for _, _, a, s in rows[:30]]
    assert got[30:] == [None] * 12
    if log is not None:
        log.close()


@pytest.mark.parametrize("framer", ["native", "decoded"])
def test_write_delta_says_what_it_did_to_the_number_of_keys(
        tmp_path, monkeypatch, framer):
    """The delta's net change in keys (three new, two gone): a key put
    twice is new once, a put over a key that was there is not new, a delete
    of a key the same blob puts removes it, a second delete of one key
    removes nothing — with the native framer, without it, and on a store
    that takes no blob at all."""
    from tests.test_utxo_delta import _v1
    from tpunode import store as store_mod
    from tpunode.store import put_op, write_delta

    if framer == "decoded":
        monkeypatch.setattr(store_mod, "_delta_framer_state", (None,))
    blob = b"".join([
        _v1(1, b"dup", b"first"), _v1(1, b"was-there", b"new value"),
        _v1(1, b"dup", b"second"), _v1(1, b"empty"), _v1(1, b"k", b"v"),
        _v1(2, b"k"), _v1(2, b"absent"), _v1(2, b"was-there-too"),
        _v1(2, b"was-there-too"),
    ])
    log = LogKV(str(tmp_path / "kv.log"))
    for kv in (Namespaced(log, b"n/"), Namespaced(MemoryKV(), b"n/")):
        kv.write_batch([put_op(b"was-there", b"old"),
                        put_op(b"was-there-too", b"old too")])
        n0 = len(list(kv.scan_prefix(b"")))
        assert write_delta(kv, blob, lambda *_: [put_op(b"tail", b"1")]) == 1
        assert len(list(kv.scan_prefix(b""))) == n0 + 3 - 2 + 1  # and the tail
    assert metrics.get("store.live_bytes") == log._live_bytes > 0
    assert metrics.get("store.dead_bytes") == log._dead_bytes > 0
    log.close()


@pytest.mark.parametrize("entry", ["apply_block", "apply_ops_blob"])
def test_the_entry_count_follows_every_connect_and_disconnect(entry, tmp_path):
    """``entries`` is the number of output rows after a load, a block whose
    spends miss, a block whose spends hit (64 of the first's outputs) and
    each disconnect, on both connect paths, and a reopen counts the same."""
    from tests.test_utxo_delta import connect, mix_block, spending_block

    log = LogKV(str(tmp_path / "kv.log"))
    u = UtxoStore(Namespaced(log, UTXO_NAMESPACE))
    u.load_snapshot(0, GENESIS, batches(entries(90), 40))
    counts = [u.entries]
    first = mix_block(64, 1)
    for height, (region, count) in enumerate(
            [first[:2], spending_block(first[2], 2)[:2]], start=1):
        assert connect(u, entry, height, region, count)
        counts.append(u.entries)
        assert u.entries == len(u.snapshot()) == metrics.get("utxo.entries")
    assert counts[1] == 90 + 65 and counts[2] == counts[1] + 65 - 64
    log.close()
    log = LogKV(str(tmp_path / "kv.log"))
    u = UtxoStore(Namespaced(log, UTXO_NAMESPACE))
    assert u.entries == counts[2] and u.height == 2
    for want in (counts[1], counts[0]):
        assert u.disconnect() and u.entries == want == len(u.snapshot())
    log.close()


# ---- the node -----------------------------------------------------------------


def chain_of(n_blocks: int, per_block: int, seed: int):
    """Blocks of signed txs on the genesis block, the generator's oracle,
    what it expects of every tx, and a snapshot of every outpoint they
    spend (with the oracle's own answers) plus ``filler`` entries."""
    made = make_txs(n_blocks * per_block, seed)
    oracle = gen.Oracle()
    oracle.p2pk.update(made["p2pk"])
    blocks, prev = [], None
    for b in range(n_blocks):
        blk = block_of(made["raw"][b * per_block:(b + 1) * per_block],
                       height=b + 1, prev=prev)
        prev = blk.header.hash
        blocks.append(blk)
    spendable = []
    for raw in made["raw"]:
        (_, ins, _, _), _ = w.parse_tx(raw)
        spendable += [(t, v, *oracle(t, v)) for t, v, _, _ in ins]
    return blocks, oracle, dict(zip(made["txids"], made["expect"])), spendable


def filler(n: int, seed: int) -> list:
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        txid, vout = rng.randbytes(32), rng.randrange(4)
        out.append((txid, vout, gen.synth_amount(txid, vout),
                    gen.synth_script(txid)))
    return out


async def through(blocks, *, oracle, snapshot):
    """The blocks through a node with ``utxo=True``: -> (verdicts, counter
    deltas, the node's UTXO namespace after the last connect — every
    record of it —, its entry count)."""
    async with a_node(oracle=oracle, utxo=True, port=17931) as d:
        if snapshot is not None:
            d.node.utxo.load_snapshot(0, GENESIS, batches(snapshot, 97))
        for blk in blocks:
            d.node.chain.headers(d.peer, [blk.header])
        await poll_until(lambda: d.node.chain.get_block(
            blocks[-1].header.hash) is not None, what="header import")
        c0 = {k: metrics.get(k) for k in COUNTERS}
        got = []
        for blk in blocks:
            got += (await d.block(blk))[0]
        await poll_until(lambda: d.node.utxo.height >= len(blocks),
                         what="utxo connect")
        moved = {k: int(metrics.get(k) - c0[k]) for k in COUNTERS}
        return got, moved, utxo_records(d.node), d.node.utxo.entries


def by_reference(blocks, prevouts) -> list:
    """The blocks' verdict rows by the Python reference, a block at a time."""
    return [row for blk in blocks
            for row in reference_verdicts(list(blk.txs), prevouts, bch=True)]


@pytest.mark.asyncio
@pytest.mark.parametrize("path", ["native", "reference"])
async def test_a_node_without_a_callback_answers_from_its_snapshot(path):
    """Verdicts identical, signature by signature, to a node with the
    callback (and an empty set) — or to the Python reference's, and every
    record of the UTXO namespace to ``UtxoStore.apply_block``'s — and to
    the generator's expectation; no callback is asked, no row goes
    unanswered, and the set afterwards is the plain reference's: spent
    absent, created present and equal, filler untouched, the count equal."""
    blocks, oracle, expect, spendable = chain_of(3, 21, seed=0x31)
    snapshot = spendable + filler(400, seed=0x32)
    async with asyncio.timeout(120):
        own, moved, records, n = await through(
            blocks, oracle=None, snapshot=snapshot)
        if path == "reference":
            assert tuples(own) == by_reference(blocks, oracle)
            assert records == reference_set(
                blocks, batches(snapshot, 97), GENESIS)
        else:
            called, moved_cb, _, _ = await through(
                blocks, oracle=oracle, snapshot=None)
            assert tuples(own) == tuples(called)
            # the other node's set answered nothing and its callback everything
            assert moved_cb["utxo.lookup_hits"] == 0
            assert moved_cb["node.resolve_oracle_calls"] == len(spendable)
    assert len(own) == sum(b.tx_count for b in blocks)
    for v in own:
        if v.txid in expect:
            assert tuple(v.verdicts) == expect[v.txid] and v.error is None
            assert v.stats.unsupported == 0
    assert any(not all(expect[v.txid]) for v in own if v.txid in expect)
    assert moved["node.resolve_oracle_calls"] == 0
    assert moved["node.resolve_missing"] == 0
    assert moved["utxo.lookup_rows"] == moved["utxo.lookup_hits"] == len(
        spendable) == moved["node.resolve_rows"]
    # conservation against the plain reference
    snap = {k: v for k, v in records.items() if k[:1] == b"o"}
    ref = reference(snapshot)
    for blk in blocks:
        ref.apply_block(blk.header.serialize() + w.varint(blk.tx_count)
                        + blk.raw_txs)
    assert ref.spent_absent == 0 and len(ref.spent) == len(spendable)
    assert {k[1:]: v for k, v in snap.items()} == ref.set
    assert n == len(ref.set) == len(snapshot) - len(spendable) + len(ref.created)
    assert all(k not in ref.set for k in ref.spent)


@pytest.mark.asyncio
@pytest.mark.parametrize("path", ["native", "reference"])
async def test_a_row_no_source_answers_is_counted(path):
    """A snapshot short of three spendable entries and no callback: the
    three rows are counted, the inputs go unverified (``unsupported``), and
    the set's misses show in hits against rows; ``reference``: the Python
    reference, asked of a table short of the same three, says the same of
    every tx."""
    blocks, oracle, expect, spendable = chain_of(1, 16, seed=0x33)
    # the bare-P2PK rows' keys are in their prevout scripts: keep those
    short = [e for e in spendable if len(e[3]) == 25][:3]
    snapshot = [e for e in spendable if e not in short]
    async with asyncio.timeout(120):
        got, moved, _, _ = await through(
            blocks, oracle=None, snapshot=snapshot)
    if path == "reference":
        table = {(t, v): (a, s) for t, v, a, s in snapshot}
        assert tuples(got) == by_reference(
            blocks, lambda txid, vout: table.get((txid, vout)))
    assert moved["node.resolve_missing"] == 3
    assert moved["node.resolve_oracle_calls"] == 0
    assert moved["utxo.lookup_rows"] - moved["utxo.lookup_hits"] == 3
    assert moved["utxo.lookup_rows"] == len(spendable)
    assert sum(v.stats.unsupported for v in got) == 3
    assert sum(len(v.verdicts) for v in got) < sum(
        len(e) for e in expect.values())
