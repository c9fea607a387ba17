"""Integration tests against the in-memory fake network.

Mirrors the reference test suite (/root/reference/test/Haskoin/NodeSpec.hs:
149-229): the full node runs with the transport hook swapped for
``dummy_peer_connect`` — no sockets, no real peers — and every assertion goes
through the public event subscription, exactly like an embedding application.
"""

import asyncio
import contextlib

import pytest

from tests.fakenet import dummy_peer_connect
from tests.fixtures import all_blocks
from tpunode import (
    BCH_REGTEST,
    ChainBestBlock,
    ChainSynced,
    Namespaced,
    Node,
    NodeConfig,
    PeerConnected,
    Publisher,
    get_blocks,
    txextract,
)
from tpunode.peermgr import to_host_service
from tpunode.store import LogKV, MemoryKV
from tpunode.util import hex_to_hash
from tpunode.wire import NetworkAddress, build_merkle_root

NET = BCH_REGTEST


@contextlib.asynccontextmanager
async def make_test_node(store=None, blocks=None):
    """The ``withTestNode`` harness (reference NodeSpec.hs:237-280): real
    store with a column-family namespace, fake transport, one static peer
    that is never actually dialed."""
    pub = Publisher(name="node-events")
    blocks = all_blocks() if blocks is None else blocks
    cfg = NodeConfig(
        net=NET,
        store=Namespaced(store if store is not None else MemoryKV(), b"node:"),
        pub=pub,
        max_peers=20,
        peers=["[::1]:17486"],
        discover=False,
        address=NetworkAddress.from_host_port("0.0.0.0", 0, services=1),
        timeout=120,
        max_peer_life=48 * 3600,
        connect=lambda sa: dummy_peer_connect(NET, blocks),
    )
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            yield node, events


def wait_for_peer(events):
    return events.receive_match(
        lambda ev: ev.peer if isinstance(ev, PeerConnected) else None
    )


@pytest.mark.asyncio
async def test_connects_to_a_peer():
    # reference "connects to a peer" (NodeSpec.hs:172-177)
    async with make_test_node() as (node, events):
        async with asyncio.timeout(10):
            p = await wait_for_peer(events)
        o = node.peer_mgr.get_online_peer(p)
        assert o is not None and o.online
        assert o.version is not None and o.version.version >= 70002


@pytest.mark.asyncio
async def test_downloads_some_blocks():
    # reference "downloads some blocks" (NodeSpec.hs:178-193)
    h1 = hex_to_hash("3094ed3592a06f3d8e099eed2d9c1192329944f5df4a48acb29e08f12cfbb660")
    h2 = hex_to_hash("0c89955fc5c9f98ecc71954f167b938138c90c6a094c4737f2e901669d26763f")
    async with make_test_node() as (node, events):
        async with asyncio.timeout(10):
            p = await wait_for_peer(events)
        bs = await get_blocks(NET, 10, p, [h1, h2])
        assert bs is not None
        b1, b2 = bs
        assert b1.header.hash == h1
        assert b2.header.hash == h2
        for b in (b1, b2):
            assert b.header.merkle == build_merkle_root([t.txid for t in b.txs])


@pytest.mark.asyncio
async def test_syncs_some_headers():
    # reference "syncs some headers" (NodeSpec.hs:194-212)
    bh = "3bfa0c6da615fc45aa44ddea6854ac19d16f3ca167e0e21ac2cc262a49c9b002"
    ah = "7dc835a78a55fa76f9184dc4f6663a73e418c7afec789c5ae25e432fd7fc8467"
    async with make_test_node() as (node, events):
        async with asyncio.timeout(10):
            bn = await events.receive_match(
                lambda ev: ev.node
                if isinstance(ev, ChainBestBlock) and ev.node.height > 0
                else None
            )
        bb = node.chain.get_best()
        assert bb.height == 15
        an = node.chain.get_ancestor(10, bn)
        assert an is not None
        assert bn.hash_hex == bh
        assert an.hash_hex == ah


@pytest.mark.asyncio
async def test_downloads_some_block_parents():
    # reference "downloads some block parents" (NodeSpec.hs:213-229)
    hs = [
        "52e886df7b166d961ac2d3d2d561d806325d51a609dc0a5d9d5fcb65d47906d7",
        "2537a081b9e2b24d217fac2886f387758cb3aa4e4956b3be7ed229bafbb71b0f",
        "7c72f306215a296f9714320a497b1f2cb5f9b99f162d7e04333c243fac9a54d8",
    ]
    async with make_test_node() as (node, events):
        async with asyncio.timeout(10):
            bns = [
                await events.receive_match(
                    lambda ev: ev.node if isinstance(ev, ChainBestBlock) else None
                )
                for _ in range(2)
            ]
        bn = bns[1]
        assert bn.height == 15
        ps = node.chain.get_parents(12, bn)
        assert len(ps) == 3
        assert [p.hash_hex for p in ps] == hs


@pytest.mark.asyncio
async def test_chain_synced_event_and_queries():
    async with make_test_node() as (node, events):
        async with asyncio.timeout(10):
            sn = await events.receive_match(
                lambda ev: ev.node if isinstance(ev, ChainSynced) else None
            )
        assert sn.height == 15
        assert node.chain.is_synced()
        # block_main: fixture block 10 is on the main chain; random hash isn't
        an = node.chain.get_ancestor(10, node.chain.get_best())
        assert node.chain.block_main(an.hash)
        assert not node.chain.block_main(b"\x42" * 32)
        # split block of best with itself is itself
        best = node.chain.get_best()
        assert node.chain.get_split_block(best, best).hash == best.hash


@pytest.mark.asyncio
async def test_restart_resumes_from_store(tmp_path):
    # checkpoint/resume contract: the header store IS the checkpoint
    # (reference Chain.hs:302-303,464-468; SURVEY.md §5)
    store = LogKV(str(tmp_path / "headers.log"))
    async with make_test_node(store=store) as (node, events):
        async with asyncio.timeout(10):
            await events.receive_match(
                lambda ev: ev.node
                if isinstance(ev, ChainBestBlock) and ev.node.height == 15
                else None
            )
    store.close()
    store2 = LogKV(str(tmp_path / "headers.log"))
    # no blocks served this time: the node must come up at height 15 from disk
    async with make_test_node(store=store2, blocks=all_blocks()[:0]) as (node, events):
        async with asyncio.timeout(10):
            bn = await events.receive_match(
                lambda ev: ev.node if isinstance(ev, ChainBestBlock) else None
            )
        assert bn.height == 15
    store2.close()


def test_to_host_service_table():
    # reference "reads some specific addresses" (NodeSpec.hs:161-170)
    assert to_host_service("localhost") == ("localhost", None)
    assert to_host_service("::1") == ("::1", None)
    assert to_host_service("localhost:8080") == ("localhost", "8080")
    assert to_host_service("example.com") == ("example.com", None)
    assert to_host_service("api.example.com:443") == ("api.example.com", "443")
    assert to_host_service("api.example.com:http") == ("api.example.com", "http")
    assert to_host_service("[::1]") == ("::1", None)
    assert to_host_service("[::1]:8080") == ("::1", "8080")
    assert to_host_service("[2002::dead:beef]:ssh") == ("2002::dead:beef", "ssh")


@pytest.mark.asyncio
async def test_to_sock_addr_numeric():
    from tpunode.peermgr import to_sock_addr

    assert await to_sock_addr(NET, "127.0.0.1:1234") == [("127.0.0.1", 1234)]
    # default port filled from network
    out = await to_sock_addr(NET, "127.0.0.1")
    assert out == [("127.0.0.1", NET.default_port)]
    v6 = await to_sock_addr(NET, "[::1]:17486")
    assert ("::1", 17486) in v6


@pytest.mark.asyncio
async def test_busy_peer_stays_in_sync_queue():
    # A peer locked by the embedding app must not be dropped from the chain's
    # sync queue (reference nextPeer leaves busy peers queued; review fix).
    async with make_test_node() as (node, events):
        async with asyncio.timeout(10):
            p = await wait_for_peer(events)
            # wait for the first sync cycle to finish and release the peer
            await events.receive_match(
                lambda ev: ev.node if isinstance(ev, ChainSynced) else None
            )
        assert p.set_busy()  # app takes the lock
        node.chain.peer_connected(p)  # re-queue the peer
        await asyncio.sleep(0.05)
        node.chain._check_timeout()  # ping tick: cannot lock, must keep queued
        await asyncio.sleep(0.05)
        assert p in node.chain._peers
        p.set_free()


@pytest.mark.asyncio
async def test_internal_crash_tears_down_node():
    # Crash-only design: an internal actor crash aborts the embedding scope
    # (reference link semantics, Node.hs:191-192; review fix).
    with pytest.raises(RuntimeError, match="injected chain crash"):
        async with make_test_node() as (node, events):
            async def crash():
                raise RuntimeError("injected chain crash")
            node.chain._tasks.link(crash(), name="crash-injection")
            async with asyncio.timeout(10):
                await events.receive_match(lambda ev: None)  # wait forever


def test_pong_window_keeps_newest_samples():
    import time as _time
    from tpunode.actors import Mailbox as _Mb
    from tpunode.peer import Peer as _Peer
    from tpunode.peermgr import OnlinePeer as _OP

    o = _OP(
        address=("h", 1), peer=_Peer(_Mb(), Publisher(), "x"),
        task=None, nonce=1, connected=0.0, tickled=0.0,
    )
    o.pings = [0.01] * 11  # 11 fast samples
    # a slow new sample must displace the oldest, not be discarded
    o.pings = ([5.0] + o.pings)[:11]
    assert 5.0 in o.pings and len(o.pings) == 11


@pytest.mark.asyncio
async def test_tx_ingest_verify_hook():
    """North-star hook: an inbound tx streams through the verify engine and
    a TxVerdict lands on the user bus (no reference analog — the reference
    never validates scripts; BASELINE.json north_star)."""
    from tests.test_sighash import make_signed_tx
    from tpunode import TxVerdict
    from tpunode.peer import PeerMessage
    from tpunode.verify.engine import VerifyConfig
    from tpunode.wire import MsgTx

    pub = Publisher(name="node-events")
    cfg = NodeConfig(
        net=NET,
        store=MemoryKV(),
        pub=pub,
        peers=["[::1]:17486"],
        connect=lambda sa: dummy_peer_connect(NET, all_blocks()),
        verify=VerifyConfig(backend="oracle", max_wait=0.0),
    )
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            async with asyncio.timeout(10):
                peer = await wait_for_peer(events)
                good = make_signed_tx(0xC0FFEE, n_inputs=2)
                node._peer_pub.publish(PeerMessage(peer, MsgTx(good)))
                v = await events.receive_match(
                    lambda ev: ev if isinstance(ev, TxVerdict) else None
                )
                assert v.txid == good.txid
                assert v.valid and v.verdicts == (True, True)
                assert v.stats.extracted == 2


@pytest.mark.asyncio
async def test_block_ingest_resolves_segwit_amounts_intra_block():
    """BIP143 end-to-end (VERDICT r2 item 5): a block whose P2WPKH txs
    spend in-block outputs verifies those signatures using the intra-block
    prevout amounts — no embedder hook needed."""
    from benchmarks.txgen import gen_signed_txs
    from tpunode import TxVerdict
    from tpunode.peer import PeerMessage
    from tpunode.verify.engine import VerifyConfig
    from tpunode.wire import Block, BlockHeader, MsgBlock

    txs = gen_signed_txs(4, inputs_per_tx=1, seed=0x5E6, segwit_every=2)
    assert any(t.witnesses for t in txs), "fixture must contain segwit txs"
    hdr = BlockHeader(1, b"\x00" * 32, b"\x00" * 32, 0, 0x207FFFFF, 0)
    block = Block(hdr, tuple(txs))

    pub = Publisher(name="node-events")
    cfg = NodeConfig(
        net=NET,
        store=MemoryKV(),
        pub=pub,
        peers=["[::1]:17486"],
        connect=lambda sa: dummy_peer_connect(NET, all_blocks()),
        verify=VerifyConfig(backend="oracle", max_wait=0.0),
    )
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            async with asyncio.timeout(15):
                peer = await wait_for_peer(events)
                node._peer_pub.publish(PeerMessage(peer, MsgBlock(block)))
                seen = {}
                while len(seen) < len(txs):
                    ev = await events.receive()
                    if isinstance(ev, TxVerdict):
                        seen[ev.txid] = ev
    segwit_txids = {t.txid for t in txs if t.witnesses}
    for t in txs:
        v = seen[t.txid]
        assert v.valid, t.txid.hex()
        if t.txid in segwit_txids:
            assert v.stats.extracted == 1  # BIP143 item actually verified


@pytest.mark.asyncio
async def test_mempool_segwit_uses_embedder_prevout_lookup():
    """Single-tx (mempool) segwit verification flows through
    NodeConfig.prevout_lookup — the embedder-supplied amount channel."""
    from benchmarks.txgen import gen_signed_txs
    from tpunode import TxVerdict
    from tpunode.peer import PeerMessage
    from tpunode.verify.engine import VerifyConfig
    from tpunode.wire import MsgTx

    txs = gen_signed_txs(2, inputs_per_tx=1, seed=0x5E7, segwit_every=2)
    funding, spender = txs
    assert spender.witnesses
    amounts = {(funding.txid, 0): funding.outputs[0].value}

    pub = Publisher(name="node-events")
    cfg = NodeConfig(
        net=NET,
        store=MemoryKV(),
        pub=pub,
        peers=["[::1]:17486"],
        connect=lambda sa: dummy_peer_connect(NET, all_blocks()),
        verify=VerifyConfig(backend="oracle", max_wait=0.0),
        prevout_lookup=lambda txid, vout: amounts.get((txid, vout)),
    )
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            async with asyncio.timeout(10):
                peer = await wait_for_peer(events)
                node._peer_pub.publish(PeerMessage(peer, MsgTx(spender)))
                v = await events.receive_match(
                    lambda ev: ev if isinstance(ev, TxVerdict) else None
                )
                assert v.txid == spender.txid
                assert v.valid and v.stats.extracted == 1

    # without the hook the same tx is unsupported (amount unknown), not invalid
    cfg2 = NodeConfig(
        net=NET,
        store=MemoryKV(),
        pub=pub,
        peers=["[::1]:17486"],
        connect=lambda sa: dummy_peer_connect(NET, all_blocks()),
        verify=VerifyConfig(backend="oracle", max_wait=0.0),
    )
    async with pub.subscription() as events:
        async with Node(cfg2) as node:
            async with asyncio.timeout(10):
                peer = await wait_for_peer(events)
                node._peer_pub.publish(PeerMessage(peer, MsgTx(spender)))
                v = await events.receive_match(
                    lambda ev: ev if isinstance(ev, TxVerdict) else None
                )
                assert v.stats.extracted == 0 and v.stats.unsupported == 1
                assert v.valid  # nothing extractable failed


@pytest.mark.asyncio
async def test_hand_built_block_and_its_wire_round_trip_verdict_alike():
    """A block built in-process (``raw_txs is None``) and its wire round
    trip (raw bytes carried) give the same TxVerdict stream through the
    one ingest path — the Python reference's —, each in one
    ``_verify_txs_native`` call."""
    import tpunode.node as node_mod
    from benchmarks.txgen import gen_signed_txs
    from tests.fixtures import reference_verdicts, tuples
    from tpunode import TxVerdict
    from tpunode.peer import PeerMessage
    from tpunode.util import Reader
    from tpunode.verify.engine import VerifyConfig
    from tpunode.wire import Block, BlockHeader, MsgBlock, MsgTx, Tx

    if not txextract.have_native_extract():
        pytest.skip("native extractor unavailable")

    txs = gen_signed_txs(
        6, inputs_per_tx=2, seed=0x7A77, invalid_every=3, segwit_every=5
    )
    hdr = BlockHeader(1, b"\x00" * 32, b"\x00" * 32, 0, 0x207FFFFF, 0)
    built = Block(hdr, tuple(txs))
    assert built.raw_txs is None  # serialised at the node's door
    rt = Block.deserialize(Reader(built.serialize()))  # raw_txs set
    assert rt.raw_txs is not None

    native_calls = 0
    orig = node_mod.Node._verify_txs_native

    async def counting(self, peer, raw, n_txs, block=None, txs=None):
        nonlocal native_calls
        native_calls += 1
        return await orig(self, peer, raw, n_txs, block=block, txs=txs)

    async def run(block_msg) -> dict[bytes, object]:
        pub = Publisher(name="node-events")
        cfg = NodeConfig(
            net=NET,
            store=MemoryKV(),
            pub=pub,
            peers=["[::1]:17486"],
            connect=lambda sa: dummy_peer_connect(NET, all_blocks()),
            verify=VerifyConfig(backend="cpu", max_wait=0.0),
        )
        seen: dict[bytes, object] = {}
        async with pub.subscription() as events:
            async with Node(cfg) as node:
                async with asyncio.timeout(15):
                    peer = await wait_for_peer(events)
                    node._peer_pub.publish(PeerMessage(peer, block_msg))
                    while len(seen) < len(txs):
                        ev = await events.receive()
                        if isinstance(ev, TxVerdict):
                            seen[ev.txid] = ev
        return seen

    node_mod.Node._verify_txs_native = counting
    try:
        wire = await run(MsgBlock(rt))
        assert native_calls == 1
        hand = await run(MsgBlock(built))
        assert native_calls == 2, "a constructed block goes the same way"
    finally:
        node_mod.Node._verify_txs_native = orig

    ref = reference_verdicts(txs, None, bch=True)
    assert tuples(wire[t.txid] for t in txs) == ref
    assert tuples(hand[t.txid] for t in txs) == ref
    assert any(not row[1] for row in ref), (
        "fixture must exercise invalid signatures")

    # mempool path: a wire-round-tripped tx rides the batch accumulator
    # (round 4), one drain for the message
    one = Tx.deserialize(Reader(txs[0].serialize()))
    assert one.raw is not None
    drain_calls = 0
    orig_drain = node_mod.Node._drain_tx_accum

    async def counting_drain(self):
        nonlocal drain_calls
        drain_calls += 1
        return await orig_drain(self)

    node_mod.Node._drain_tx_accum = counting_drain
    try:
        got = await run_single(one)
        assert drain_calls == 1
        assert got.valid is not None
    finally:
        node_mod.Node._drain_tx_accum = orig_drain


async def run_single(tx):
    """Deliver one MsgTx through a node and return its TxVerdict."""
    from tpunode import TxVerdict
    from tpunode.peer import PeerMessage
    from tpunode.verify.engine import VerifyConfig
    from tpunode.wire import MsgTx

    pub = Publisher(name="node-events")
    cfg = NodeConfig(
        net=NET,
        store=MemoryKV(),
        pub=pub,
        peers=["[::1]:17486"],
        connect=lambda sa: dummy_peer_connect(NET, all_blocks()),
        verify=VerifyConfig(backend="cpu", max_wait=0.0),
    )
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            async with asyncio.timeout(10):
                peer = await wait_for_peer(events)
                node._peer_pub.publish(PeerMessage(peer, MsgTx(tx)))
                return await events.receive_match(
                    lambda ev: ev if isinstance(ev, TxVerdict) else None
                )


@pytest.mark.asyncio
@pytest.mark.parametrize("asks_for", ["verify", "utxo"])
async def test_a_node_that_ingests_does_not_start_without_the_extractor(
        asks_for, monkeypatch):
    """The requirement is stated once, at construction: a ``Node`` whose
    config asks for verification or a UTXO set raises an error that names
    the library and how to build it when ``libtxextract`` does not load;
    a header-only node starts, syncs and never asks for it."""
    from tpunode.verify.engine import VerifyConfig

    def no_library():
        raise OSError("libtxextract.so: cannot open shared object file")

    monkeypatch.setattr(txextract, "load_txextract_lib", no_library)
    pub = Publisher(name="node-events")
    cfg = NodeConfig(
        net=NET, store=MemoryKV(), pub=pub, peers=["[::1]:17486"],
        connect=lambda sa: dummy_peer_connect(NET, all_blocks()),
        verify=(VerifyConfig(backend="oracle") if asks_for == "verify"
                else None),
        utxo=asks_for == "utxo",
    )
    with pytest.raises(RuntimeError) as err:
        Node(cfg)
    assert "libtxextract" in str(err.value)
    assert "make -C native" in str(err.value)
    assert isinstance(err.value.__cause__, OSError)
    async with make_test_node() as (node, events):
        async with asyncio.timeout(10):
            await wait_for_peer(events)
        assert node.verify_engine is None and node.utxo is None


@pytest.mark.asyncio
async def test_objects_built_without_wire_bytes_enter_as_their_wire_forms(
        monkeypatch):
    """A relayed ``Tx`` with ``raw=None`` and a ``Block`` with
    ``raw_txs=None`` (tests, an embedder's own injection) are serialised
    at the node's door and go the way a peer's bytes go: the tx through
    the accumulator and one extract shard, the block through exactly one
    ``_verify_txs_native`` call, both over the bytes of their wire forms,
    and they publish their wire forms' verdicts."""
    import tpunode.node as node_mod
    from benchmarks.txgen import gen_signed_txs
    from tests.fakenet import poll_until
    from tests.fixtures import tuples
    from tpunode import TxVerdict
    from tpunode.peer import PeerMessage
    from tpunode.util import Reader
    from tpunode.verify.engine import VerifyConfig
    from tpunode.wire import Block, BlockHeader, LazyBlock, MsgBlock, MsgTx

    if not txextract.have_native_extract():
        pytest.skip("native extractor unavailable")
    txs = gen_signed_txs(5, inputs_per_tx=2, seed=0x47, invalid_every=2)
    one = txs[0]
    hdr = BlockHeader(1, b"\x00" * 32, b"\x00" * 32, 0, 0x207FFFFF, 0)
    built = Block(hdr, tuple(txs[1:]))
    assert one.raw is None and built.raw_txs is None
    region = b"".join(t.serialize() for t in built.txs)
    wire_tx = MsgTx.deserialize_payload(Reader(one.serialize())).tx
    wire_block = LazyBlock(hdr, built.tx_count, region)

    shards, regions = [], []
    extract_shard = node_mod.Node._extract_shard
    verify_native = node_mod.Node._verify_txs_native

    def spy_shard(self, shard, bch):
        shards.append([raw for _, _, raw, _ in shard])
        return extract_shard(self, shard, bch)

    def spy_native(self, peer, raw, n_txs, **kw):
        regions.append((raw, n_txs))
        return verify_native(self, peer, raw, n_txs, **kw)

    monkeypatch.setattr(node_mod.Node, "_extract_shard", spy_shard)
    monkeypatch.setattr(node_mod.Node, "_verify_txs_native", spy_native)
    pub = Publisher(name="node-events")
    cfg = NodeConfig(
        net=NET, store=MemoryKV(), pub=pub, peers=["[::1]:17486"],
        connect=lambda sa: dummy_peer_connect(NET, all_blocks()),
        verify=VerifyConfig(backend="cpu", max_wait=0.0),
    )
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            async with asyncio.timeout(20):
                peer = await wait_for_peer(events)

                async def verdicts_of(msg, n: int) -> list:
                    node._peer_pub.publish(PeerMessage(peer, msg))
                    got = []
                    while len(got) < n:
                        ev = await events.receive()
                        if isinstance(ev, TxVerdict):
                            got.append(ev)
                    return tuples(got)

                hand_tx = await verdicts_of(MsgTx(one), 1)
                assert shards == [[one.serialize()]] and not regions
                assert hand_tx == await verdicts_of(MsgTx(wire_tx), 1)
                hand_block = await verdicts_of(MsgBlock(built), 4)
                assert regions == [(region, 4)]
                # (a block still on its way out is a duplicate to drop)
                await poll_until(lambda: not node._blocks_taken,
                                 what="the block is through")
                assert hand_block == await verdicts_of(MsgBlock(wire_block), 4)
                assert regions == [(region, 4)] * 2
    assert [row[0] for row in hand_tx + hand_block] == [t.txid for t in txs]
    assert not all(row[1] for row in hand_block)  # an invalid one among them


@pytest.mark.asyncio
async def test_native_block_ingest_never_parses_txs_in_python():
    """The lazy-block native path (LazyBlock + scan_prevouts) must produce
    TxVerdicts for a block without a single Python Tx.deserialize call —
    the round-4 fix for the IBD ingest bottleneck (VERDICT r3 item 2)."""
    import tpunode.wire as wire_mod
    from benchmarks.txgen import gen_mixed_txs, synth_amount
    from tpunode import TxVerdict
    from tpunode.peer import PeerMessage
    from tpunode.verify.engine import VerifyConfig
    from tpunode.wire import Block, BlockHeader, MsgBlock

    if not txextract.have_native_extract():
        pytest.skip("native extractor unavailable")

    txs = gen_mixed_txs(10, seed=0xDEF)
    hdr = BlockHeader(1, b"\x00" * 32, b"\x00" * 32, 0, 0x207FFFFF, 0)
    raw_block = Block(hdr, tuple(txs)).serialize()
    from tpunode.util import Reader

    msg = MsgBlock.deserialize_payload(Reader(raw_block))

    parses = 0
    orig_deser = wire_mod.Tx.deserialize.__func__

    @classmethod
    def counting_deser(cls, r):
        nonlocal parses
        parses += 1
        return orig_deser(cls, r)

    pub = Publisher(name="node-events")
    cfg = NodeConfig(
        net=NET,
        store=MemoryKV(),
        pub=pub,
        peers=["[::1]:17486"],
        connect=lambda sa: dummy_peer_connect(NET, all_blocks()),
        verify=VerifyConfig(backend="cpu", max_wait=0.0),
        prevout_lookup=synth_amount,
    )
    seen = {}
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            async with asyncio.timeout(15):
                peer = await wait_for_peer(events)
                wire_mod.Tx.deserialize = counting_deser
                try:
                    node._peer_pub.publish(PeerMessage(peer, msg))
                    while len(seen) < len(txs):
                        ev = await events.receive()
                        if isinstance(ev, TxVerdict):
                            seen[ev.txid] = ev
                finally:
                    wire_mod.Tx.deserialize = classmethod(orig_deser)
    assert parses == 0, f"block ingest parsed {parses} txs in Python"
    assert {tx.txid for tx in txs} == set(seen)
    # verdicts are real: the mixed workload's supported txs verify fully
    for tx in txs:
        ev = seen[tx.txid]
        assert ev.error is None
        if ev.stats.unsupported == 0:
            assert ev.valid, tx.txid.hex()


@pytest.mark.asyncio
async def test_malformed_lazy_block_kills_peer_not_node():
    """A block whose envelope decodes but whose tx region is malformed used
    to die in eager decode; with lazy blocks it surfaces in verify ingest —
    which must publish an error TxVerdict and kill the peer, never crash
    the event router (code-review r4 finding 1)."""
    from tpunode import TxVerdict
    from tpunode.peer import PeerDisconnected, PeerMessage
    from tpunode.verify.engine import VerifyConfig
    from tpunode.wire import BlockHeader, LazyBlock, MsgBlock

    pub = Publisher(name="node-events")
    cfg = NodeConfig(
        net=NET,
        store=MemoryKV(),
        pub=pub,
        peers=["[::1]:17486"],
        connect=lambda sa: dummy_peer_connect(NET, all_blocks()),
        verify=VerifyConfig(backend="cpu", max_wait=0.0),
    )
    hdr = BlockHeader(1, b"\x00" * 32, b"\x00" * 32, 0, 0x207FFFFF, 0)
    bad = MsgBlock(LazyBlock(hdr, 3, b"\x01\x02\x03"))  # truncated region
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            async with asyncio.timeout(15):
                peer = await wait_for_peer(events)
                node._peer_pub.publish(PeerMessage(peer, bad))
                saw_error = saw_disconnect = False
                while not (saw_error and saw_disconnect):
                    ev = await events.receive()
                    if isinstance(ev, TxVerdict):
                        assert ev.error is not None and not ev.valid
                        saw_error = True
                    elif isinstance(ev, PeerDisconnected):
                        saw_disconnect = True
                # node is still alive and queryable after the bad peer died
                assert node.chain.get_best() is not None


@pytest.mark.asyncio
async def test_tx_accumulator_isolates_malformed_tx():
    """The mempool accumulator batches many tx messages into one native
    extract; a malformed tx must fail only itself (its peer dies, its
    verdict is an error) while the rest of the batch still verdicts."""
    from benchmarks.txgen import gen_mixed_txs, synth_amount
    from tpunode import TxVerdict
    from tpunode.peer import PeerDisconnected, PeerMessage
    from tpunode.util import Reader
    from tpunode.verify.engine import VerifyConfig
    from tpunode.wire import LazyTx, MsgTx

    if not txextract.have_native_extract():
        pytest.skip("native extractor unavailable")

    txs = gen_mixed_txs(8, seed=0xBAD)
    good = [MsgTx.deserialize_payload(Reader(t.serialize())) for t in txs]
    bad = MsgTx(LazyTx(b"\x01\x00\x00\x00\xff\xee"))  # malformed region

    pub = Publisher(name="node-events")
    cfg = NodeConfig(
        net=NET,
        store=MemoryKV(),
        pub=pub,
        peers=["[::1]:17486"],
        connect=lambda sa: dummy_peer_connect(NET, all_blocks()),
        verify=VerifyConfig(backend="cpu", max_wait=0.0),
        prevout_lookup=synth_amount,
    )
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            async with asyncio.timeout(20):
                peer = await wait_for_peer(events)
                for m in good[:4]:
                    node._peer_pub.publish(PeerMessage(peer, m))
                node._peer_pub.publish(PeerMessage(peer, bad))
                for m in good[4:]:
                    node._peer_pub.publish(PeerMessage(peer, m))
                seen = {}
                err = None
                disconnected = False
                while len(seen) < len(txs) or err is None or not disconnected:
                    ev = await events.receive()
                    if isinstance(ev, TxVerdict):
                        if ev.error is not None:
                            err = ev
                        else:
                            seen[ev.txid] = ev
                    elif isinstance(ev, PeerDisconnected):
                        disconnected = True
    assert {t.txid for t in txs} == set(seen)
    for t in txs:
        ev = seen[t.txid]
        if ev.stats.unsupported == 0:
            assert ev.valid
    assert err.txid == b"" and "extract" in err.error


@pytest.mark.asyncio
async def test_node_reorgs_to_heavier_chain_from_second_peer():
    """Full-stack reorg: the node syncs chain A from peer 1, then a second
    peer appears carrying a heavier chain B (same genesis, more work) and
    the chain actor switches best to B's tip (reference: connectBlocks'
    chain-work compare + syncNewPeer on PeerConnected, Chain.hs:352-362)."""
    from benchmarks.txgen import gen_chain
    from tpunode import ChainBestBlock

    chain_a = gen_chain(NET, 6, 1, seed=0xAAA, cache=None)
    chain_b = gen_chain(NET, 9, 1, seed=0xBBB, cache=None)
    assert chain_a[-1].header.hash != chain_b[-1].header.hash

    a_synced = asyncio.Event()

    def connect(sa):
        import contextlib as _ctx

        host = sa[0]

        @_ctx.asynccontextmanager
        async def factory():
            if host == "192.0.2.2":
                await a_synced.wait()  # peer 2 joins only after A is best
                blocks = chain_b
            else:
                blocks = chain_a
            async with dummy_peer_connect(NET, blocks)() as conn:
                yield conn

        return factory

    pub = Publisher(name="node-events")
    cfg = NodeConfig(
        net=NET,
        store=MemoryKV(),
        pub=pub,
        max_peers=2,
        peers=["192.0.2.1:8333", "192.0.2.2:8333"],
        discover=False,
        connect=connect,
    )
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            async with asyncio.timeout(30):
                # phase 1: chain A becomes best
                await events.receive_match(
                    lambda ev: ev
                    if isinstance(ev, ChainBestBlock) and ev.node.height == 6
                    else None
                )
                assert node.chain.get_best().hash == chain_a[-1].header.hash
                a_synced.set()
                # phase 2: heavier chain B takes over
                await events.receive_match(
                    lambda ev: ev
                    if isinstance(ev, ChainBestBlock) and ev.node.height == 9
                    else None
                )
            best = node.chain.get_best()
            assert best.hash == chain_b[-1].header.hash
            assert node.chain.block_main(chain_b[-1].header.hash)
            # A's tip is now a side-chain block
            assert not node.chain.block_main(chain_a[-1].header.hash)
            # split point of the two tips is genesis
            a_node = node.chain.get_block(chain_a[-1].header.hash)
            assert a_node is not None  # side chain retained in the store


@pytest.mark.asyncio
async def test_verify_shed_rate_limited_and_lossless_counts(monkeypatch):
    """Backpressure shedding publishes aggregated VerifyShed events at a
    bounded rate, and the dropped_txs counts sum to the true number of
    drops (the delayed flush reports trailing bursts; review r4 findings
    2-3)."""
    import tpunode.node as node_mod
    from benchmarks.txgen import gen_mixed_txs
    from tpunode import VerifyShed
    from tpunode.peer import PeerMessage
    from tpunode.util import Reader
    from tpunode.verify.engine import VerifyConfig
    from tpunode.wire import MsgTx

    if not txextract.have_native_extract():
        pytest.skip("native extractor unavailable")
    monkeypatch.setattr(node_mod.Node, "MAX_TX_ACCUM", 4)

    txs = gen_mixed_txs(6, seed=0x5ED)
    msgs = [MsgTx.deserialize_payload(Reader(t.serialize())) for t in txs]

    pub = Publisher(name="node-events")
    cfg = NodeConfig(
        net=NET,
        store=MemoryKV(),
        pub=pub,
        peers=["[::1]:17486"],
        connect=lambda sa: dummy_peer_connect(NET, all_blocks()),
        verify=VerifyConfig(backend="cpu", max_wait=0.0),
    )
    N_SENT = 120
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            async with asyncio.timeout(20):
                peer = await wait_for_peer(events)
                # flood without yielding: the drain task cannot run, so
                # everything past the 4-slot accumulator is shed
                for i in range(N_SENT):
                    node._peer_pub.publish(PeerMessage(peer, msgs[i % len(msgs)]))
                shed_events = []
                shed_total = 0
                t0 = asyncio.get_running_loop().time()
                while shed_total < N_SENT - node.MAX_TX_ACCUM:
                    ev = await events.receive()
                    if isinstance(ev, VerifyShed):
                        shed_events.append(
                            (asyncio.get_running_loop().time() - t0, ev)
                        )
                        shed_total += ev.dropped_txs
    assert shed_total == N_SENT - node_mod.Node.MAX_TX_ACCUM
    # aggregated: far fewer events than drops, bounded ~2/sec + 1 initial
    span = shed_events[-1][0] if shed_events else 0.0
    assert len(shed_events) <= 2 + span * 2.5, (len(shed_events), span)


@pytest.mark.asyncio
async def test_verify_shed_attributed_per_peer():
    """Shed counts are attributed to the peer that caused them — one
    VerifyShed per shedding peer per flush window, never a pooled count
    under whichever peer triggered the flush (VERDICT r4 weak #4:
    embedders do per-peer DoS banning on this)."""
    from tpunode import VerifyShed

    pub = Publisher(name="shed-test")
    node = Node(
        NodeConfig(net=NET, store=MemoryKV(), pub=pub, peers=[])
    )
    pa, pb = object(), object()
    async with pub.subscription() as events:
        # first drop: window open -> immediate flush, attributed to pa
        node._publish_shed(pa, 3)
        ev = await asyncio.wait_for(events.receive(), 2)
        assert isinstance(ev, VerifyShed)
        assert ev.peer is pa and ev.dropped_txs == 3
        # burst from both peers inside the closed window: ONE delayed
        # flush emits one event per peer with that peer's own count,
        # regardless of which peer arrived last
        node._publish_shed(pa, 2)
        node._publish_shed(pb, 7)
        node._publish_shed(pa, 1)
        got = {}
        async with asyncio.timeout(5):
            while len(got) < 2:
                ev = await events.receive()
                assert isinstance(ev, VerifyShed)
                assert ev.peer not in got
                got[ev.peer] = ev.dropped_txs
        assert got == {pa: 3, pb: 7}
    await node._verify_tasks.aclose()


@pytest.mark.asyncio
async def test_peer_sending_bad_headers_is_killed():
    """Headers failing consensus (wrong difficulty bits) kill the sync
    peer (reference Chain.hs:334-338 killPeer PeerSentBadHeaders) and the
    chain stays at its prior best; the node remains healthy."""
    import dataclasses

    from tpunode import PeerDisconnected
    from tpunode.wire import Block

    good = all_blocks()
    # corrupt block 1's difficulty bits: the retarget check must reject
    bad_hdr = dataclasses.replace(good[0].header, bits=0x1D00FFFF)
    bad_blocks = [Block(bad_hdr, good[0].txs)] + good[1:]

    async with make_test_node(blocks=bad_blocks) as (node, events):
        async with asyncio.timeout(15):
            p = await wait_for_peer(events)
            await events.receive_match(
                lambda ev: ev
                if isinstance(ev, PeerDisconnected) and ev.peer is p
                else None
            )
        assert node.chain.get_best().height == 0  # nothing imported
        # the connect loop will keep re-dialing; the node itself is healthy
        assert node.chain.is_synced() is False


@pytest.mark.asyncio
async def test_tcp_connect_rejects_non_numeric_host():
    """The connect path is NUMERIC-only (reference ``fromSockAddr``
    resolves with NumericHost): hostnames are resolved once in
    ``to_sock_addr`` at address-book build, so ``tcp_connect`` must fail
    fast on a non-numeric host instead of performing DNS inside the
    connect (a wedged resolver would stall the peer slot)."""
    import time

    from tpunode.node import PeerAddressInvalid, tcp_connect

    t0 = time.monotonic()
    with pytest.raises(PeerAddressInvalid, match="non-numeric host"):
        async with tcp_connect(("definitely-not-an-ip.invalid", 8333))():
            pass
    # fail-fast: no resolver round-trip happened (DNS timeouts are >> 1s)
    assert time.monotonic() - t0 < 1.0


def test_numeric_host_classifier():
    from tpunode.node import _numeric_host

    assert _numeric_host("127.0.0.1")
    assert _numeric_host("::1")
    assert _numeric_host("2002::dead:beef")
    assert _numeric_host("fe80::1%eth0")  # zone id allowed
    assert not _numeric_host("localhost")
    assert not _numeric_host("example.com")
    assert not _numeric_host("")
