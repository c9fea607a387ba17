"""Persistent UTXO store (ISSUE 9 / ROADMAP item 5): unit invariants +
the node wiring — block connect applies atomically behind the watermark,
the prevout oracle serves confirmed outputs, and a restart resumes from
the persisted chain + UTXO set without re-applying or re-verifying.
"""

import asyncio
import contextlib

import pytest

from tests.fakenet import dummy_peer_connect, poll_until
from tests.fixtures import all_blocks
from tpunode import (
    BCH_REGTEST,
    ChainSynced,
    Namespaced,
    Node,
    NodeConfig,
    Publisher,
    UtxoStore,
)
from tpunode.chaos import ChaosFault, ChaosPlan, chaos
from tpunode.metrics import metrics
from tpunode.peer import PeerConnected, PeerMessage
from tpunode.store import LogKV, MemoryKV
from tpunode.wire import MsgBlock

NET = BCH_REGTEST


@pytest.fixture
def chaos_off():
    yield
    chaos.uninstall()


# ---------------------------------------------------------------------------
# unit invariants

def test_apply_lookup_spend_watermark():
    u = UtxoStore(MemoryKV())
    assert u.height == -1
    assert u.lookup(b"\x01" * 32, 0) is None
    assert u.apply(
        0, b"h0", spends=[],
        creates=[(b"\x01" * 32, 0, 5000, b"\x51"), (b"\x01" * 32, 1, 7, b"")],
    )
    assert u.height == 0
    assert u.lookup(b"\x01" * 32, 0) == (5000, b"\x51")
    assert u.lookup(b"\x01" * 32, 1) == (7, b"")
    # next block spends one output
    assert u.apply(
        1, b"h1", spends=[(b"\x01" * 32, 0)],
        creates=[(b"\x02" * 32, 0, 9000, b"\x52")],
    )
    assert u.lookup(b"\x01" * 32, 0) is None
    assert u.lookup(b"\x02" * 32, 0) == (9000, b"\x52")
    assert u.height == 1


@pytest.mark.parametrize("store", ["memory", "log", "namespaced-log"])
def test_lookup_many_is_lookup_row_by_row(store, tmp_path):
    """The UTXO set's batch read (ISSUE 30): an outpoint is its 36 wire
    bytes — txid ++ vout as four little-endian bytes, the key's tail as it
    stands — and ``lookup_many`` answers each as ``lookup`` does: unspent
    outputs, spent ones, unknown ones, an empty script, a vout past 31
    bits, under the node's namespace or none."""
    from tpunode.store import Namespaced
    from tpunode.utxo import UTXO_NAMESPACE

    kv = MemoryKV() if store == "memory" else LogKV(str(tmp_path / "u.log"))
    view = Namespaced(kv, UTXO_NAMESPACE) if store.startswith("namesp") else kv
    u = UtxoStore(view)
    a, b, c = b"\x01" * 32, b"\x02" * 32, b"\x03" * 32
    big = 0xFFFFFFF0
    assert u.lookup_many([a + (0).to_bytes(4, "little")]) == [None]
    u.apply(0, b"h0", spends=[], creates=[
        (a, 0, 5000, b"\x51"), (a, 1, 7, b""), (b, big, 9, b"\x52\x53")])
    u.apply(1, b"h1", spends=[(a, 0)], creates=[(c, 2, 11, b"\x54")])
    asked = [(a, 0), (a, 1), (b, big), (b, 0), (c, 2), (c, 3), (a, 1)]
    got = u.lookup_many([t + v.to_bytes(4, "little") for t, v in asked])
    assert got == [u.lookup(t, v) for t, v in asked]
    assert got == [None, (7, b""), (9, b"\x52\x53"), None, (11, b"\x54"),
                   None, (7, b"")]
    assert u.lookup_many([]) == []
    # the watermark and undo rows share the store and never answer
    assert u.lookup_many([b"!wm", b"U" + (1).to_bytes(8, "little")]) == [
        None, None]
    # a disconnect between two reads is seen by the second
    assert u.disconnect()
    assert u.lookup_many([a + (0).to_bytes(4, "little"),
                          c + (2).to_bytes(4, "little")]) == [
        (5000, b"\x51"), None]
    kv.close()


def test_apply_is_idempotent_below_watermark():
    u = UtxoStore(MemoryKV())
    u.apply(3, b"h3", spends=[], creates=[(b"\x03" * 32, 0, 1, b"")])
    s0 = metrics.get("utxo.skipped")
    # a crash-replayed (re-delivered) block is refused, state unchanged
    assert not u.apply(
        3, b"h3", spends=[(b"\x03" * 32, 0)], creates=[]
    )
    assert not u.apply(2, b"h2", spends=[], creates=[])
    assert metrics.get("utxo.skipped") == s0 + 2
    assert u.lookup(b"\x03" * 32, 0) == (1, b"")
    assert u.height == 3


def test_watermark_persists_across_reopen(tmp_path):
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    u = UtxoStore(Namespaced(s, b"u/"))
    u.apply(7, b"hash7" + b"\x00" * 27, spends=[],
            creates=[(b"\x07" * 32, 0, 42, b"\x53")])
    s.close()
    s2 = LogKV(path)
    u2 = UtxoStore(Namespaced(s2, b"u/"))
    assert u2.height == 7
    assert u2.block_hash == b"hash7" + b"\x00" * 27
    assert u2.lookup(b"\x07" * 32, 0) == (42, b"\x53")
    s2.close()


def test_apply_atomic_under_chaos(tmp_path, chaos_off):
    """One write_batch carries spends+creates+watermark: an injected fault
    applies NOTHING — no half-connected block, watermark unmoved."""
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    u = UtxoStore(Namespaced(s, b"u/"))
    u.apply(0, b"h0", spends=[], creates=[(b"\x01" * 32, 0, 1, b"")])
    chaos.install(ChaosPlan.parse("seed=5;store.write:error:n=1"))
    with pytest.raises(ChaosFault):
        u.apply(
            1, b"h1", spends=[(b"\x01" * 32, 0)],
            creates=[(b"\x02" * 32, 0, 2, b"")],
        )
    chaos.uninstall()
    assert u.height == 0  # watermark unmoved
    assert u.lookup(b"\x01" * 32, 0) == (1, b"")  # spend not applied
    assert u.lookup(b"\x02" * 32, 0) is None  # create not applied
    s.close()
    # and the durable state agrees
    s2 = LogKV(path)
    u2 = UtxoStore(Namespaced(s2, b"u/"))
    assert u2.height == 0
    s2.close()


def test_apply_block_from_parsed_txs():
    """apply_block extracts creates/spends from wire Tx objects, skipping
    the coinbase's null prevout, and same-block chains net out."""
    blocks = all_blocks()
    u = UtxoStore(MemoryKV())
    for height, b in enumerate(blocks, start=1):
        assert u.apply_block(height, b.header.hash, list(b.txs))
    assert u.height == len(blocks)
    # every block's coinbase output is present with its real amount/script
    last = blocks[-1]
    cb = last.txs[0]
    got = u.lookup(cb.txid, 0)
    assert got == (cb.outputs[0].value, cb.outputs[0].script)


# ---------------------------------------------------------------------------
# node wiring

@contextlib.asynccontextmanager
async def utxo_node(store, blocks):
    pub = Publisher(name="utxo-node-events")
    cfg = NodeConfig(
        net=NET,
        store=store,
        pub=pub,
        peers=["[::1]:17486"],
        discover=False,
        connect=lambda sa: dummy_peer_connect(NET, blocks),
        utxo=True,
    )
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            yield node, events


async def _sync_and_connect_blocks(node, events, blocks):
    async with asyncio.timeout(15):
        peer = None
        while True:
            ev = await events.receive()
            if isinstance(ev, PeerConnected):
                peer = ev.peer
            if isinstance(ev, ChainSynced):
                break
        assert peer is not None
        for b in blocks:
            node._peer_pub.publish(PeerMessage(peer, MsgBlock(b)))
    await poll_until(
        lambda: node.utxo.height == len(blocks), what="utxo catch-up"
    )
    return peer


@pytest.mark.asyncio
async def test_node_connects_blocks_and_serves_prevouts_from_its_set(tmp_path):
    blocks = all_blocks()
    store = LogKV(str(tmp_path / "node.log"))
    async with utxo_node(store, blocks) as (node, events):
        await _sync_and_connect_blocks(node, events, blocks)
        assert node.utxo.height == len(blocks)
        cb = blocks[2].txs[0]
        # the set is the one prevout source this node has
        assert node._prevout_sources() == (None, None, node.utxo, None)
        assert node.utxo.lookup(cb.txid, 0) == (
            cb.outputs[0].value, cb.outputs[0].script,
        )
        assert node.health()["utxo_height"] == len(blocks)
        assert node.stats()["utxo"]["enabled"] is True
    store.close()


@pytest.mark.asyncio
async def test_restart_resumes_from_persisted_chain_and_utxo(tmp_path):
    """The ISSUE 9 restart pin (in-process flavor; the SIGKILL subprocess
    variant lives in test_store_recovery.py): a node reopened over the
    same store starts at the persisted best height BEFORE any peer
    traffic, keeps the UTXO watermark, and re-delivered blocks are
    skipped — no re-apply, no re-verification."""
    blocks = all_blocks()
    path = str(tmp_path / "node.log")
    store = LogKV(path)
    async with utxo_node(store, blocks) as (node, events):
        await _sync_and_connect_blocks(node, events, blocks)
        best = node.chain.get_best()
        assert best.height == len(blocks)
    store.close()

    store2 = LogKV(path)  # a real cold replay of the segmented log
    pub = Publisher(name="utxo-restart")
    cfg = NodeConfig(
        net=NET, store=store2, pub=pub, peers=[], discover=False,
        connect=lambda sa: dummy_peer_connect(NET, blocks), utxo=True,
    )
    async with pub.subscription():
        async with Node(cfg) as node2:
            # resumed BEFORE any peer traffic: nothing to re-download
            assert node2.chain.get_best().height == len(blocks)
            assert node2.utxo.height == len(blocks)
            applied0 = metrics.get("utxo.applied")
            skipped0 = metrics.get("node.block_replay_skipped")

            class P:  # minimal peer surface for the router
                label = "replay:0"

            node2._peer_pub.publish(PeerMessage(P(), MsgBlock(blocks[9])))
            await poll_until(
                lambda: metrics.get("node.block_replay_skipped")
                == skipped0 + 1,
                what="replayed block skipped",
            )
            assert metrics.get("utxo.applied") == applied0  # no re-apply
    store2.close()


@pytest.mark.asyncio
async def test_restart_with_blocks_in_flight_resumes_at_the_watermark(tmp_path):
    """The in-flight output view is memory only (ISSUE 44).  A node is
    stopped with three blocks parsed, published to the view and held in
    verification; the node reopened over the same store starts at the
    watermark with the view empty, the blocks above the watermark are
    fetched again, and every verdict of theirs — spends of each other's
    outputs among them — equals the plain reference's."""
    from chipbench import reference_chain
    from tests.chain_cell import a_node, chain

    ch = chain()
    path = str(tmp_path / "node.log")
    store = LogKV(path)
    async with a_node(ch, store=store) as d:
        for h in (1, 2, 3):
            d.give(ch.block(h))
        await poll_until(lambda: d.node.utxo.height == 3, what="connects")
        d.hold = asyncio.Event()  # never set: stopped mid-verification
        for h in (4, 5, 6):
            d.give(ch.block(h))
        await poll_until(lambda: d.node._inflight.blocks == 3,
                         what="three blocks in the view")
        assert len(d.node._inflight) > 0 and d.node.utxo.height == 3
    store.close()

    store2 = LogKV(path)
    missing0 = metrics.get("node.resolve_missing")
    async with a_node(ch, store=store2, port=17945) as d:
        node = d.node
        assert node.utxo.height == 3 and node.utxo.block_hash == ch.hashes[2]
        assert len(node._inflight) == 0 and node._inflight.blocks == 0
        skipped0 = metrics.get("node.block_replay_skipped")
        for h in (3, 6, 5, 4):  # 3 is under the watermark: not verified again
            d.give(ch.block(h))
        txids = [t for ids in ch.txids[3:6] for t in ids]
        await d.verdicts_of(txids)
        # (the view empties a moment after the height is up)
        await poll_until(lambda: node.utxo.height == 6 and d.clear_view(),
                         what="connects")
        assert metrics.get("node.block_replay_skipped") == skipped0 + 1
        assert not set(ch.txids[2]) & set(d.verdicts)
        ref = dict(reference_chain.check_job(
            {"raw": [ch.raw[t] for t in txids], "p2pk": ch.table(txids)}))
        for t in txids:
            assert tuple(d.verdicts[t].verdicts) == ref[t] == ch.expect[t]
        assert metrics.get("node.resolve_missing") == missing0
        assert d.clear_view()
    store2.close()


@pytest.mark.asyncio
async def test_out_of_order_block_parks_until_predecessor(tmp_path):
    """Review pin: applying height N+2 over a watermark of N would strand
    N+1's delta below the watermark forever.  An early arrival PARKS
    (utxo.deferred) without advancing the watermark; once its
    predecessor lands, the parked chain drains contiguously."""
    blocks = all_blocks()
    store = LogKV(str(tmp_path / "node.log"))
    async with utxo_node(store, blocks) as (node, events):
        async with asyncio.timeout(15):
            peer = None
            while True:
                ev = await events.receive()
                if isinstance(ev, PeerConnected):
                    peer = ev.peer
                if isinstance(ev, ChainSynced):
                    break
        d0 = metrics.get("utxo.deferred")
        # deliver heights 3 and 2 FIRST: parked, watermark stays -1
        node._peer_pub.publish(PeerMessage(peer, MsgBlock(blocks[2])))
        node._peer_pub.publish(PeerMessage(peer, MsgBlock(blocks[1])))
        await poll_until(
            lambda: metrics.get("utxo.deferred") == d0 + 2,
            what="gaps parked",
        )
        assert node.utxo.height == -1
        # height 1 lands: the parked chain drains to 3 without re-delivery
        node._peer_pub.publish(PeerMessage(peer, MsgBlock(blocks[0])))
        await poll_until(lambda: node.utxo.height == 3, what="parked drain")
        cb = blocks[1].txs[0]
        assert node.utxo.lookup(cb.txid, 0) == (
            cb.outputs[0].value, cb.outputs[0].script,
        )
    store.close()


@pytest.mark.asyncio
async def test_reorg_beneath_watermark_goes_loudly_stale(tmp_path):
    """Review pin: a watermark on a branch the chain no longer follows
    must not silently absorb the new branch's deltas — the next connect
    fails the hash-chain check AND finds no undo record (the seed wrote
    none: the reorg is effectively deeper than the retained undo depth),
    so it emits utxo.reorg_stale and the watermark never advances
    (rebuild is the remedy).  Clean unwinds with undo records are pinned
    by test_ibd.py's reorg test."""
    from tpunode.utxo import UTXO_NAMESPACE

    blocks = all_blocks()
    store = LogKV(str(tmp_path / "node.log"))
    # seed a height-1 watermark pointing at a block hash that is NOT on
    # (or even known to) the canned chain — an orphaned branch's tip,
    # with NO undo record retained (undo_depth=0)
    UtxoStore(Namespaced(store, UTXO_NAMESPACE), undo_depth=0).apply(
        1, b"\xab" * 32, spends=[], creates=[]
    )
    r0 = metrics.get("utxo.reorg_stale")
    async with utxo_node(store, blocks) as (node, events):
        async with asyncio.timeout(15):
            peer = None
            while True:
                ev = await events.receive()
                if isinstance(ev, PeerConnected):
                    peer = ev.peer
                if isinstance(ev, ChainSynced):
                    break
        # height 1 is NOT treated as persisted (watermark block unknown
        # to the header store -> re-verify) ...
        assert node._persisted_height(MsgBlock(blocks[0]).block) is None
        for b in blocks:
            node._peer_pub.publish(PeerMessage(peer, MsgBlock(b)))
        # ... and height 2 refuses to stack onto the foreign watermark
        await poll_until(
            lambda: metrics.get("utxo.reorg_stale") > r0,
            what="stale reorg detected",
        )
        assert node.utxo.height == 1  # never advanced onto wrong state
    store.close()


# ---------------------------------------------------------------------------
# per-block UNDO records (ISSUE 11)

def _demo_blocks():
    """Three small hand-rolled deltas exercising spends of earlier
    creates and same-block create+spend netting."""
    t1, t2, t3 = b"\x01" * 32, b"\x02" * 32, b"\x03" * 32
    return [
        # height 1: two outputs born
        ([], [(t1, 0, 100, b"\x51"), (t1, 1, 200, b"\x52")]),
        # height 2: spends t1:0, creates t2:0
        ([(t1, 0)], [(t2, 0, 300, b"\x53")]),
        # height 3: spends t2:0 and t1:1, creates t3:0
        ([(t2, 0), (t1, 1)], [(t3, 0, 400, b"\x54")]),
    ]


def test_undo_disconnect_reconnect_round_trips():
    """The ISSUE 11 pin: disconnect + re-connect round-trips the UTXO
    set bit-identically, at every depth."""
    u = UtxoStore(MemoryKV())
    snaps = [u.snapshot()]
    hashes = []
    for h, (spends, creates) in enumerate(_demo_blocks(), start=1):
        bh = bytes([h]) * 32
        hashes.append(bh)
        assert u.apply(h, bh, spends=spends, creates=creates)
        snaps.append(u.snapshot())
    # unwind all the way down, checking each restored state
    for h in (3, 2, 1):
        assert u.disconnect()
        assert u.height == (h - 1 if h >= 2 else -1)
        assert u.snapshot() == snaps[h - 1]
        assert u.block_hash == (hashes[h - 2] if h >= 2 else None)
    assert u.height == -1 and u.block_hash is None
    # reconnect everything: same final state as the first pass
    for h, (spends, creates) in enumerate(_demo_blocks(), start=1):
        assert u.apply(h, hashes[h - 1], spends=spends, creates=creates)
    assert u.snapshot() == snaps[-1]
    assert u.block_hash == hashes[-1]


def test_undo_retention_depth():
    """Undo records older than undo_depth are pruned in the connect
    batch: disconnect works back exactly undo_depth blocks, then refuses
    (the loudly-stale fallback's trigger)."""
    u = UtxoStore(MemoryKV(), undo_depth=2)
    for h in range(1, 5):
        u.apply(h, bytes([h]) * 32, spends=[],
                creates=[(bytes([h]) * 32, 0, h, b"")])
    assert u.undo_available(4) and u.undo_available(3)
    assert not u.undo_available(2)  # pruned by the height-4 connect
    assert u.disconnect()
    assert u.disconnect()
    assert not u.disconnect()  # deeper than retention
    assert u.height == 2  # store untouched by the refused disconnect


def test_undo_disabled_with_zero_depth():
    u = UtxoStore(MemoryKV(), undo_depth=0)
    u.apply(1, b"\x01" * 32, spends=[], creates=[(b"\x0a" * 32, 0, 1, b"")])
    assert not u.undo_available()
    assert not u.disconnect()
    assert u.height == 1


def test_watermark_persists_with_undo_across_reopen(tmp_path):
    """Undo records survive the log replay: a reopened store can still
    disconnect its tip."""
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    u = UtxoStore(Namespaced(s, b"u/"))
    u.apply(1, b"\x01" * 32, spends=[], creates=[(b"\x0b" * 32, 0, 9, b"")])
    u.apply(2, b"\x02" * 32, spends=[(b"\x0b" * 32, 0)], creates=[])
    s.close()
    s2 = LogKV(path)
    u2 = UtxoStore(Namespaced(s2, b"u/"))
    assert u2.height == 2
    assert u2.disconnect()
    assert u2.height == 1
    assert u2.block_hash == b"\x01" * 32
    assert u2.lookup(b"\x0b" * 32, 0) == (9, b"")  # spend restored
    s2.close()


def test_apply_ops_blob_matches_apply_block():
    """ISSUE 11: the C++ one-pass delta blob (ParsedTxRegion.utxo_ops ->
    apply_ops_blob) produces a store bit-identical to the Python
    apply_block path — undo records included (both disconnect to the
    same state)."""
    txextract = pytest.importorskip("tpunode.txextract")
    if not txextract.have_native_extract():
        pytest.skip("native txextract unavailable")
    from tpunode.txextract import ParsedTxRegion

    blocks = all_blocks()
    upy = UtxoStore(MemoryKV())
    unat = UtxoStore(MemoryKV())
    for height, b in enumerate(blocks, start=1):
        assert upy.apply_block(height, b.header.hash, list(b.txs))
        raw = b.serialize()[80:]  # strip header; varint(count) + txs
        # skip the tx-count varint (fixture blocks carry < 0xFD txs)
        with ParsedTxRegion(raw[1:], len(b.txs)) as region:
            blob, created, spent = region.utxo_ops()
        assert unat.apply_ops_blob(
            height, b.header.hash, blob, created, spent
        )
    assert upy.snapshot() == unat.snapshot()
    assert upy.height == unat.height == len(blocks)
    # undo parity: both paths disconnect to the same prior state
    assert upy.disconnect() and unat.disconnect()
    assert upy.snapshot() == unat.snapshot()
