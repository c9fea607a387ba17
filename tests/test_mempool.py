"""Mempool subsystem tests (ISSUE 5).

Two altitudes, mirroring the other actor suites:

* actor-level — a :class:`tpunode.mempool.Mempool` driven through its
  public handles with a counting ``submit`` hook and stub peers: admission
  dedup, verdict cache + misbehavior, orphan park/resolve/expiry, LRU and
  want-list bounds, fetch retry-with-reassignment (``get_txs``
  monkeypatched per peer), peer-gone cleanup;
* fakenet integration — a full Node with ``NodeConfig.mempool`` set and
  several fake remotes announcing/pushing overlapping tx sets: the
  ISSUE 5 acceptance paths (announced-by-one + pushed-by-three verifies
  exactly once, orphan admitted after its parent, confirmed tx evicted on
  block connect).
"""

from __future__ import annotations

import asyncio
import contextlib
from types import SimpleNamespace

import pytest

from tests.fakenet import TxRelay, dummy_peer_connect, poll_until
from tests.fixtures import all_blocks
from tpunode import BCH_REGTEST, Node, NodeConfig, Publisher, TxVerdict
from tpunode.mempool import Mempool, MempoolConfig, TxState
from tpunode.metrics import metrics
from tpunode.peer import PeerConnected, PeerMessage
from tpunode.store import MemoryKV
from tpunode.util import Reader
from tpunode.verify.engine import VerifyConfig
from tpunode.wire import (
    Block,
    BlockHeader,
    InvType,
    InvVector,
    LazyTx,
    MsgBlock,
    MsgInv,
    MsgTx,
)

NET = BCH_REGTEST


class StubPeer:
    """Label + kill recorder; NOT a tpunode.peer.Peer (the actor treats it
    as a push-only source, never a fetch target for orphan parents)."""

    def __init__(self, label: str):
        self.label = label
        self.killed: list = []

    def kill(self, exc) -> None:
        self.killed.append(exc)


def lazy(tx) -> LazyTx:
    """The wire form of a pushed tx (raw bytes present -> fast dedup)."""
    return MsgTx.deserialize_payload(Reader(tx.serialize())).tx


def signed_txs(n: int, seed: int, **kw) -> list:
    from benchmarks.txgen import gen_signed_txs

    return gen_signed_txs(n, inputs_per_tx=1, seed=seed, **kw)


@contextlib.asynccontextmanager
async def mempool_actor(cfg: MempoolConfig = None, prevout_lookup=None, **kw):
    """A running Mempool actor with a counting submit hook that plays the
    part of the node's relay drain (``Node._extract_shard``, a shard of
    one, none of its threads): the node's own walk over this mempool and
    ``prevout_lookup``, its question to the mempool (``parks``) and its
    probe extract; a tx the extractor would leave an input out of goes
    back by ``orphaned`` and is not counted as submitted."""
    from tpunode.node import _WalkLeft
    from tpunode.txextract import ParsedTxRegion

    submitted: list = []
    node = SimpleNamespace(utxo=None, _inflight=None,
                           cfg=SimpleNamespace(prevout_lookup=prevout_lookup))
    node._prevout_sources = lambda: Node._prevout_sources(node)

    def submit(peer, tx) -> None:
        rec = (peer, tx, tx.serialize(), None)
        region, left = ParsedTxRegion(rec[2], 1), _WalkLeft()
        amounts, scripts = Node._resolve_ext_rows(node, region, NET.bch,
                                                  left=left)
        maybe = left.rows and Node._may_wait(
            node, region.input_offsets().tolist(), [rec], left)
        if not maybe:
            region.close()
        elif Node._extract_kept_and_close(region, [0], NET.bch, amounts,
                                          scripts)[0]:
            return node.mempool.orphaned(peer, tx, maybe[0])
        submitted.append((peer, tx))

    node.mempool = mp = Mempool(
        cfg if cfg is not None else MempoolConfig(tick_interval=0.02),
        net=NET,
        submit=submit,
        **kw,
    )
    async with mp:
        yield mp, submitted


# --- actor level: admission dedup + verdict cache ---------------------------


@pytest.mark.asyncio
async def test_duplicate_pushes_submit_once():
    txs = signed_txs(3, seed=0xD5D0)
    peers = [StubPeer(f"p{i}") for i in range(3)]
    hits0 = metrics.get("mempool.dedup_hits")
    async with mempool_actor() as (mp, submitted):
        for p in peers:  # every peer pushes the whole set
            for t in txs:
                mp.tx_pushed(p, lazy(t))
        await poll_until(lambda: len(submitted) == 3, what="3 submissions")
        await asyncio.sleep(0.05)  # the duplicates must NOT trickle in
        assert len(submitted) == 3
        assert {t.txid for _, t in submitted} == {t.txid for t in txs}
        assert metrics.get("mempool.dedup_hits") - hits0 == 6
        assert mp.size() == 3
        for t in txs:
            assert mp.contains(t.txid)
            assert mp.state(t.txid) == TxState.PENDING
            assert mp.get(t.txid) is not None


@pytest.mark.asyncio
async def test_invalid_verdict_cached_and_misbehavior_counted():
    (bad,) = signed_txs(1, seed=0xBAD, invalid_every=1)
    p1, p2 = StubPeer("a"), StubPeer("b")
    async with mempool_actor() as (mp, submitted):
        mp.tx_pushed(p1, lazy(bad))
        await poll_until(lambda: len(submitted) == 1, what="submission")
        mp.verdict(bad.txid, False, (False,))
        await poll_until(
            lambda: mp.state(bad.txid) == TxState.INVALID, what="verdict"
        )
        assert mp.misbehavior(p1) == 1  # relayed-invalid, attributed
        # re-push of a known-invalid tx: zero verify work, counted
        mp.tx_pushed(p2, lazy(bad))
        await poll_until(lambda: mp.misbehavior(p2) == 1, what="misbehavior")
        assert len(submitted) == 1
        assert not mp.contains(bad.txid)  # invalid is not a member


@pytest.mark.asyncio
async def test_indeterminate_verdict_forgets_entry():
    (tx,) = signed_txs(1, seed=0x1D7)
    p = StubPeer("a")
    async with mempool_actor() as (mp, submitted):
        mp.tx_pushed(p, lazy(tx))
        await poll_until(lambda: len(submitted) == 1, what="submission")
        mp.verdict(tx.txid, False, (), error="engine: boom")
        await poll_until(lambda: mp.state(tx.txid) is None, what="forget")
        # a later re-push retries instead of serving a bogus verdict
        mp.tx_pushed(p, lazy(tx))
        await poll_until(lambda: len(submitted) == 2, what="re-submit")


@pytest.mark.asyncio
async def test_malformed_push_kills_peer_not_actor():
    p = StubPeer("evil")
    async with mempool_actor() as (mp, submitted):
        mp.tx_pushed(p, LazyTx(b"\x01\x00\x00\x00\xff"))
        await poll_until(lambda: len(p.killed) == 1, what="peer kill")
        assert not submitted
        assert mp.size() == 0
        # the actor survives: a good push still admits
        (tx,) = signed_txs(1, seed=0x90D)
        mp.tx_pushed(StubPeer("ok"), lazy(tx))
        await poll_until(lambda: len(submitted) == 1, what="submission")


# --- actor level: orphan pool ------------------------------------------------


@pytest.mark.asyncio
async def test_orphan_parked_then_resolved_by_parent():
    funding, spender = signed_txs(2, seed=0x0F0, segwit_every=2)
    assert spender.has_witness
    p = StubPeer("a")
    resolved0 = metrics.get("mempool.orphan_resolved")
    async with mempool_actor() as (mp, submitted):
        mp.tx_pushed(p, lazy(spender))  # child first: prevout unknown
        await poll_until(lambda: mp.orphan_count() == 1, what="orphan parked")
        assert not submitted
        assert mp.state(spender.txid) == TxState.ORPHAN
        assert mp.orphans() == [spender.txid]
        mp.tx_pushed(p, lazy(funding))  # parent arrives: child re-admits
        await poll_until(lambda: len(submitted) == 2, what="both submitted")
        assert [t.txid for _, t in submitted] == [funding.txid, spender.txid]
        assert mp.orphan_count() == 0
        assert metrics.get("mempool.orphan_resolved") - resolved0 == 1
        # the in-mempool parent is the child's prevout oracle
        assert mp.lookup_prevout(funding.txid, 0) == (
            funding.outputs[0].value,
            funding.outputs[0].script,
        )


@pytest.mark.asyncio
async def test_orphan_ttl_expiry_admits_degraded():
    _, spender = signed_txs(2, seed=0x77A, segwit_every=2)
    async with mempool_actor(
        MempoolConfig(orphan_ttl=0.05, tick_interval=0.02)
    ) as (mp, submitted):
        mp.tx_pushed(StubPeer("a"), lazy(spender))
        await poll_until(lambda: mp.orphan_count() == 1, what="orphan parked")
        # aged out: admitted anyway (verify-what's-extractable), not dropped
        await poll_until(lambda: len(submitted) == 1, what="degraded admit")
        assert mp.orphan_count() == 0
        assert mp.state(spender.txid) == TxState.PENDING


@pytest.mark.asyncio
async def test_orphan_pool_size_bound_admits_oldest_degraded():
    chains = [signed_txs(2, seed=0xC0 + i, segwit_every=2) for i in range(3)]
    spenders = [c[1] for c in chains]
    async with mempool_actor(
        MempoolConfig(max_orphans=2, orphan_ttl=600, tick_interval=0)
    ) as (mp, submitted):
        for s in spenders:
            mp.tx_pushed(StubPeer("a"), lazy(s))
        await poll_until(lambda: mp.orphan_count() == 2, what="bounded pool")
        # size pressure keeps the verdict contract: the oldest orphan is
        # admitted degraded (verify-what's-extractable, same as TTL
        # expiry), never silently dropped without a verdict
        assert [tx.txid for _, tx in submitted] == [spenders[0].txid]
        assert mp.state(spenders[0].txid) == TxState.PENDING
        assert {mp.state(s.txid) for s in spenders[1:]} == {TxState.ORPHAN}


@pytest.mark.asyncio
async def test_external_oracle_prevents_orphaning():
    funding, spender = signed_txs(2, seed=0x0AC, segwit_every=2)
    oracle = {
        (funding.txid, 0): (funding.outputs[0].value, funding.outputs[0].script)
    }
    async with mempool_actor(
        prevout_lookup=lambda txid, vout: oracle.get((txid, vout))
    ) as (mp, submitted):
        mp.tx_pushed(StubPeer("a"), lazy(spender))
        await poll_until(lambda: len(submitted) == 1, what="direct admit")
        assert mp.orphan_count() == 0


# --- actor level: confirmation + bounds --------------------------------------


@pytest.mark.asyncio
async def test_confirmed_evicts_and_unblocks_waiting_orphans():
    funding, spender = signed_txs(2, seed=0x0FF, segwit_every=2)
    ext = {
        (funding.txid, 0): (funding.outputs[0].value, funding.outputs[0].script)
    }
    oracle_on = []  # flipped on when the "block" with the parent connects

    async with mempool_actor(
        prevout_lookup=lambda t, v: ext.get((t, v)) if oracle_on else None
    ) as (mp, submitted):
        mp.tx_pushed(StubPeer("a"), lazy(spender))
        await poll_until(lambda: mp.orphan_count() == 1, what="orphan parked")
        # parent confirms in a block: its outputs are the chain's business
        # now (the embedder oracle's), and the waiting child re-admits
        oracle_on.append(True)
        mp.confirmed([funding.txid])
        await poll_until(lambda: len(submitted) == 1, what="child admitted")
        assert mp.state(funding.txid) == TxState.CONFIRMED
        # the child itself confirms: evicted from the active set
        mp.verdict(spender.txid, True, (True,))
        await poll_until(
            lambda: mp.state(spender.txid) == TxState.VALID, what="valid"
        )
        assert mp.size() == 1
        mp.confirmed([spender.txid])
        await poll_until(lambda: mp.size() == 0, what="confirm eviction")
        assert not mp.contains(spender.txid)
        assert mp.get(spender.txid) is None  # payload dropped


@pytest.mark.asyncio
async def test_seen_lru_bound_evicts_resolved_entries():
    txs = signed_txs(4, seed=0x14B)
    p = StubPeer("a")
    async with mempool_actor(
        MempoolConfig(max_txs=2, tick_interval=0)
    ) as (mp, submitted):
        for t in txs[:2]:
            mp.tx_pushed(p, lazy(t))
        await poll_until(lambda: len(submitted) == 2, what="2 submissions")
        for t in txs[:2]:
            mp.verdict(t.txid, True, (True,))
        await poll_until(
            lambda: mp.state(txs[1].txid) == TxState.VALID, what="valid"
        )
        for t in txs[2:]:
            mp.tx_pushed(p, lazy(t))
        await poll_until(lambda: len(submitted) == 4, what="4 submissions")
        # the two oldest (resolved) entries were evicted to make room
        assert mp.state(txs[0].txid) is None
        assert mp.state(txs[1].txid) is None


@pytest.mark.asyncio
async def test_pending_entries_hard_capped_at_twice_lru_bound():
    """Unresolved (PENDING) entries are protected from LRU eviction only
    up to a hard 2x ceiling: with no verify engine publishing verdicts
    (or one wedged), "never evict pending" would otherwise be an
    unbounded leak under a flooding peer."""
    txs = signed_txs(6, seed=0x2CAF)
    p = StubPeer("flood")
    async with mempool_actor(
        MempoolConfig(max_txs=2, tick_interval=0)
    ) as (mp, submitted):
        for t in txs:  # no verdicts ever arrive: all stay PENDING
            mp.tx_pushed(p, lazy(t))
        await poll_until(lambda: len(submitted) == 6, what="6 submissions")
        assert mp.size() <= 4  # 2 * max_txs
        # the newest entries survived; the oldest were force-evicted
        assert mp.state(txs[-1].txid) == TxState.PENDING
        assert mp.state(txs[0].txid) is None
    dropped0 = metrics.get("mempool.inv_dropped")
    async with mempool_actor(
        MempoolConfig(max_wanted=2, tick_interval=0),
        pressure=lambda: True,  # defer fetching: the bound is the subject
    ) as (mp, _):
        # announce 3 unknown txids from a non-fetchable stub: the third
        # must be dropped (counted), not grow the want-list
        mp.invs(StubPeer("a"), [bytes([i]) * 32 for i in range(3)])
        await poll_until(
            lambda: metrics.get("mempool.inv_dropped") - dropped0 == 1,
            what="inv drop",
        )
        assert mp.stats()["wanted"] == 2


@pytest.mark.asyncio
async def test_backpressure_defers_fetch_scheduling():
    deferred0 = metrics.get("mempool.fetch_deferred")
    async with mempool_actor(pressure=lambda: True) as (mp, _):
        mp.invs(StubPeer("a"), [b"\xaa" * 32])
        await poll_until(
            lambda: metrics.get("mempool.fetch_deferred") > deferred0,
            what="deferred fetch",
        )
        assert mp.stats()["inflight_fetches"] == 0


# --- actor level: fetch scheduler (get_txs monkeypatched) --------------------


@pytest.mark.asyncio
async def test_fetch_retry_reassigns_to_another_announcer(monkeypatch):
    """notfound from the first announcer -> the fetch is retried from the
    second; the served tx arrives through the push path (single-path
    admission) and the want entry clears."""
    import tpunode.mempool as mempool_mod

    (tx,) = signed_txs(1, seed=0xFE7C)
    p_bad, p_good = StubPeer("bad"), StubPeer("good")
    calls: list = []

    async def fake_get_txs(net, seconds, peer, txids):
        calls.append((peer, tuple(txids)))
        if peer is p_bad:
            return None  # notfound/timeout
        # a real peer would deliver via the wire loop; emulate that push
        mp.tx_pushed(peer, lazy(tx))
        return [tx]

    monkeypatch.setattr(mempool_mod, "get_txs", fake_get_txs)
    retries0 = metrics.get("mempool.fetch_retries")
    async with mempool_actor() as (mp, submitted):
        # both invs enqueue before the actor runs: announcer order is
        # deterministic (p_bad first), and p_good is already registered
        # as an alternate announcer when p_bad's fetch comes back empty
        mp.invs(p_bad, [tx.txid])
        mp.invs(p_good, [tx.txid])
        await poll_until(lambda: len(submitted) == 1, what="served via retry")
        assert [p for p, _ in calls] == [p_bad, p_good]
        assert metrics.get("mempool.fetch_retries") - retries0 == 1
        await poll_until(lambda: mp.stats()["wanted"] == 0, what="want clear")


@pytest.mark.asyncio
async def test_fetch_gives_up_after_retries_and_counts_failure(monkeypatch):
    import tpunode.mempool as mempool_mod

    calls: list = []

    async def always_notfound(net, seconds, peer, txids):
        calls.append(peer)
        return None

    monkeypatch.setattr(mempool_mod, "get_txs", always_notfound)
    fails0 = metrics.get("mempool.fetch_failures")
    async with mempool_actor(
        MempoolConfig(fetch_retries=2, tick_interval=0.02)
    ) as (mp, submitted):
        peers = [StubPeer(f"p{i}") for i in range(3)]
        for p in peers:
            mp.invs(p, [b"\x77" * 32])
        await poll_until(
            lambda: metrics.get("mempool.fetch_failures") - fails0 == 1,
            what="fetch failure",
        )
        assert len(calls) == 2  # fetch_retries, each against a new announcer
        assert calls[0] is not calls[1]
        assert mp.stats()["wanted"] == 0
        assert not submitted


@pytest.mark.asyncio
async def test_peer_gone_releases_want_entries(monkeypatch):
    import tpunode.mempool as mempool_mod

    started = asyncio.Event()
    hang = asyncio.Event()

    async def hanging_get_txs(net, seconds, peer, txids):
        started.set()
        await hang.wait()
        return None

    monkeypatch.setattr(mempool_mod, "get_txs", hanging_get_txs)
    async with mempool_actor() as (mp, _):
        p = StubPeer("gone")
        mp.invs(p, [b"\x55" * 32])
        await asyncio.wait_for(started.wait(), 5)
        assert mp.stats()["inflight_fetches"] == 1
        mp.peer_gone(p)  # sole announcer disconnects mid-fetch
        await poll_until(lambda: mp.stats()["wanted"] == 0, what="want drop")
        await poll_until(
            lambda: mp.stats()["inflight_fetches"] == 0, what="slot release"
        )
        hang.set()


# --- node integration (fakenet) ----------------------------------------------


def _relay_connect(relays: dict):
    """connect hook dispatching a per-port TxRelay to each fake remote."""

    def connect(sa):
        return dummy_peer_connect(NET, all_blocks(), relay=relays.get(sa[1]))

    return connect


@contextlib.asynccontextmanager
async def relay_node(relays: dict, **cfg_kw):
    pub = Publisher(name="node-events")
    cfg = NodeConfig(
        net=NET,
        store=MemoryKV(),
        pub=pub,
        peers=[f"[::1]:{port}" for port in relays],
        connect=_relay_connect(relays),
        verify=VerifyConfig(backend="oracle", max_wait=0.0),
        mempool=MempoolConfig(tick_interval=0.05),
        **cfg_kw,
    )
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            yield node, events


async def wait_peers(events, n: int):
    peers = []
    while len(peers) < n:
        peers.append(
            await events.receive_match(
                lambda ev: ev.peer if isinstance(ev, PeerConnected) else None
            )
        )
    return peers


@pytest.mark.asyncio
async def test_announced_tx_is_fetched_and_verified():
    """Inv-driven relay end-to-end over the real wire codec: announce ->
    want-list -> getdata batch -> tx served -> admitted -> verified."""
    txs = signed_txs(3, seed=0x1117)
    relays = {17601: TxRelay(txs, announce=True, mode="serve")}
    fetched0 = metrics.get("mempool.fetched")
    async with relay_node(relays) as (node, events):
        async with asyncio.timeout(20):
            seen = {}
            while len(seen) < 3:
                ev = await events.receive()
                if isinstance(ev, TxVerdict):
                    seen[ev.txid] = ev
            assert {t.txid for t in txs} == set(seen)
            assert all(v.valid for v in seen.values())
    assert metrics.get("mempool.fetched") - fetched0 == 3


@pytest.mark.asyncio
async def test_four_peers_same_txs_verified_exactly_once():
    """ISSUE 5 acceptance: a tx set announced+served by one peer and
    pushed unsolicited by three others is extracted/verified exactly once
    per unique tx (pinned via mempool.dedup_hits and the engine
    submission count), and a later re-push serves from the verdict
    cache."""
    txs = signed_txs(4, seed=0x4444)
    relays = {
        17611: TxRelay(txs, announce=True, mode="serve"),
        17612: TxRelay(announce=False, push=txs),
        17613: TxRelay(announce=False, push=txs),
        17614: TxRelay(announce=False, push=txs),
    }
    hits0 = metrics.get("mempool.dedup_hits")
    ntx0 = metrics.get("node.verify_txs")
    async with relay_node(relays) as (node, events):
        async with asyncio.timeout(30):
            verdicts: list[TxVerdict] = []
            while {t.txid for t in txs} - {v.txid for v in verdicts}:
                ev = await events.receive()
                if isinstance(ev, TxVerdict):
                    verdicts.append(ev)
            # 3 peers pushed all 4 txs; at most one delivery per unique tx
            # was admitted, so at least 2/3 of the pushes were dedup hits
            await poll_until(
                lambda: metrics.get("mempool.dedup_hits") - hits0 >= 8,
                what="dedup hits",
            )
            assert len(verdicts) == 4  # exactly one verdict per unique tx
            assert all(v.valid for v in verdicts)
            assert metrics.get("node.verify_txs") - ntx0 == 4
            assert node.mempool.size() == 4

            # verdict served from cache thereafter: re-push -> no verify
            hits1 = metrics.get("mempool.dedup_hits")
            peer = verdicts[0].peer
            node._peer_pub.publish(PeerMessage(peer, MsgTx(lazy(txs[0]))))
            await poll_until(
                lambda: metrics.get("mempool.dedup_hits") > hits1,
                what="cache hit",
            )
            assert metrics.get("node.verify_txs") - ntx0 == 4
            stats = node.mempool.stats()
            assert stats["dedup_hits"] >= 9
            assert 0.0 < stats["dedup_hit_rate"] <= 1.0
            # The announcing peer may be the LAST one the jittered
            # connect loop dials (up to ~5s between dials): poll until
            # its post-handshake inv lands instead of racing it.
            await poll_until(
                lambda: node.mempool.stats()["top_announcers"],
                timeout=25.0,
                what="announcer inv recorded",
            )


@pytest.mark.asyncio
async def test_orphan_admitted_after_parent_arrives_fakenet():
    """ISSUE 5 acceptance: child pushed before its (unknown) parent parks
    as an orphan; the parent's arrival re-admits it and both verify —
    the child's BIP143 amount resolved from the in-mempool parent."""
    funding, spender = signed_txs(2, seed=0x0A11, segwit_every=2)
    relays = {17621: TxRelay(announce=False, push=[spender, funding])}
    async with relay_node(relays) as (node, events):
        async with asyncio.timeout(20):
            seen = {}
            while len(seen) < 2:
                ev = await events.receive()
                if isinstance(ev, TxVerdict):
                    seen[ev.txid] = ev
            assert seen[funding.txid].valid
            assert seen[spender.txid].valid
            assert seen[spender.txid].stats.extracted == 1
            assert node.mempool.orphan_count() == 0


@pytest.mark.asyncio
async def test_confirmed_tx_evicted_on_block_connect_fakenet():
    """ISSUE 5 acceptance: a verified mempool member is evicted when a
    block containing it connects through the ingest path."""
    txs = signed_txs(2, seed=0xB10C)
    relays = {17631: TxRelay(announce=False, push=txs)}
    evict0 = metrics.get("mempool.confirmed_evictions")
    async with relay_node(relays) as (node, events):
        async with asyncio.timeout(20):
            seen = set()
            while len(seen) < 2:
                ev = await events.receive()
                if isinstance(ev, TxVerdict):
                    seen.add(ev.txid)
            peer = node.peer_mgr.fleet()[0].peer
            assert node.mempool.size() == 2
            hdr = BlockHeader(1, b"\x00" * 32, b"\x00" * 32, 0, 0x207FFFFF, 0)
            node._peer_pub.publish(
                PeerMessage(peer, MsgBlock(Block(hdr, tuple(txs))))
            )
            await poll_until(lambda: node.mempool.size() == 0, what="evict")
            assert node.mempool.state(txs[0].txid) == TxState.CONFIRMED
            assert not node.mempool.contains(txs[0].txid)
    assert metrics.get("mempool.confirmed_evictions") - evict0 == 2


@pytest.mark.asyncio
async def test_notfound_peer_falls_back_to_serving_peer_fakenet():
    """Retry-from-another-announcer over the real RPC: the notfound
    remote costs a retry, the serving remote delivers."""
    txs = signed_txs(2, seed=0x404)
    relays = {
        17641: TxRelay(txs, announce=True, mode="notfound"),
        17642: TxRelay(txs, announce=True, mode="serve"),
    }
    async with relay_node(relays) as (node, events):
        async with asyncio.timeout(30):
            seen = set()
            while len(seen) < 2:
                ev = await events.receive()
                if isinstance(ev, TxVerdict):
                    assert ev.valid
                    seen.add(ev.txid)
            assert seen == {t.txid for t in txs}


@pytest.mark.asyncio
async def test_shed_tx_is_forgotten_not_wedged_pending():
    """A mempool-admitted tx that the saturated ingest path sheds must
    be forgotten (like an engine failure), not left PENDING — a wedged
    PENDING entry would dedup-block its own re-verification forever."""
    (tx,) = signed_txs(1, seed=0x54ED)
    relays = {17671: TxRelay(announce=False)}
    dropped0 = metrics.get("node.verify_dropped")
    async with relay_node(relays) as (node, events):
        async with asyncio.timeout(20):
            peer = (await wait_peers(events, 1))[0]
            # saturate both ingest gates: every submission path sheds
            node.MAX_TX_ACCUM = 0
            node.MAX_VERIFY_PENDING = 0
            node._peer_pub.publish(PeerMessage(peer, MsgTx(lazy(tx))))
            # admitted then shed: the entry must clear, not stay PENDING
            await poll_until(
                lambda: metrics.get("node.verify_dropped") > dropped0
                and node.mempool.state(tx.txid) is None,
                what="shed forgets entry",
            )
            # gates reopen: a re-push re-admits and verifies
            del node.MAX_TX_ACCUM, node.MAX_VERIFY_PENDING
            node._peer_pub.publish(PeerMessage(peer, MsgTx(lazy(tx))))
            v = await events.receive_match(
                lambda ev: ev if isinstance(ev, TxVerdict) else None
            )
            assert v.txid == tx.txid and v.valid


@pytest.mark.asyncio
async def test_inv_counted_unhandled_without_mempool():
    """Satellite: with no mempool configured the node still counts what
    it drops — an inv lands in node.unhandled{cmd=inv} instead of
    vanishing."""
    pub = Publisher(name="node-events")
    cfg = NodeConfig(
        net=NET,
        store=MemoryKV(),
        pub=pub,
        peers=["[::1]:17651"],
        connect=lambda sa: dummy_peer_connect(NET, all_blocks()),
    )
    before = metrics.get("node.unhandled", labels={"cmd": "inv"})
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            async with asyncio.timeout(15):
                peer = (await wait_peers(events, 1))[0]
                node._peer_pub.publish(
                    PeerMessage(
                        peer,
                        MsgInv((InvVector(InvType.TX, b"\x33" * 32),)),
                    )
                )
                await poll_until(
                    lambda: metrics.get(
                        "node.unhandled", labels={"cmd": "inv"}
                    ) == before + 1,
                    what="unhandled inv counted",
                )


@pytest.mark.asyncio
async def test_node_stats_and_health_carry_mempool():
    relays = {17661: TxRelay(announce=False)}
    async with relay_node(relays) as (node, _):
        s = node.stats()
        assert s["mempool"]["size"] == 0
        assert "dedup_hit_rate" in s["mempool"]
    # and without a mempool the section says so
    pub = Publisher()
    cfg = NodeConfig(
        net=NET, store=MemoryKV(), pub=pub, peers=[],
        connect=lambda sa: dummy_peer_connect(NET, all_blocks()),
    )
    async with Node(cfg) as node:
        assert node.stats()["mempool"] == {"enabled": False}
        assert node.mempool is None


# --- the orphan pool on BCH: a FORKID P2PKH child (ISSUE 48) ------------------
#
# The six orphan tests above pin the witness gate, with transactions that
# cannot exist on the network they run on.  Their twins: a P2PKH spend signed
# under SIGHASH_ALL|FORKID of a P2PKH output, which is what a BCH node is
# relayed — and which, until ISSUE 48, no gate held.


def _forkid_pair(seed: int):
    from tests.unconf_cell import Maker

    mk = Maker(seed)
    funding, spender = mk.chain("p2pkh", 2)
    return mk, funding, spender


async def _bch_parked_then_resolved_by_parent():
    mk, funding, spender = _forkid_pair(0x0F0)
    p = StubPeer("a")
    snap = metrics.snapshot()
    # the callback holds the funding outpoints and nothing unconfirmed
    async with mempool_actor(prevout_lookup=mk.callback) as (mp, submitted):
        mp.tx_pushed(p, spender.lazy)  # child first: prevout unknown
        await poll_until(lambda: mp.orphan_count() == 1, what="orphan parked")
        assert not submitted
        assert mp.state(spender.txid) == TxState.ORPHAN
        mp.tx_pushed(p, funding.lazy)  # parent arrives: child re-admits
        await poll_until(lambda: len(submitted) == 2, what="both submitted")
        assert [t.txid for _, t in submitted] == [funding.txid, spender.txid]
        assert mp.orphan_count() == 0
        assert mp.lookup_prevout(funding.txid, 0) == funding.outs[0]
    now = metrics.snapshot()
    for name in ("mempool.orphan_resolved", "span.mempool.orphan_wait.count",
                 'mempool.orphan_resolved_by{how="push"}'):
        assert now.get(name, 0) - snap.get(name, 0) == 1, name


async def _bch_ttl_expiry_admits_degraded():
    mk, _, spender = _forkid_pair(0x77A)
    async with mempool_actor(
        MempoolConfig(orphan_ttl=0.05, tick_interval=0.02),
        prevout_lookup=mk.callback,
    ) as (mp, submitted):
        mp.tx_pushed(StubPeer("a"), spender.lazy)
        await poll_until(lambda: mp.orphan_count() == 1, what="orphan parked")
        await poll_until(lambda: len(submitted) == 1, what="degraded admit")
        assert mp.orphan_count() == 0
        assert mp.state(spender.txid) == TxState.PENDING
        # and the submit path's walk is told not to hand it back
        assert mp.parks(spender.lazy, [(b"\x07" * 32, 0)]) == frozenset()


async def _bch_size_bound_admits_oldest_degraded():
    pairs = [_forkid_pair(0xC0 + i) for i in range(3)]
    spenders = [p[2] for p in pairs]

    def callback(txid, vout):
        return next((hit for mk, _, _ in pairs
                     if (hit := mk.callback(txid, vout))), None)

    async with mempool_actor(
        MempoolConfig(max_orphans=2, orphan_ttl=600, tick_interval=0),
        prevout_lookup=callback,
    ) as (mp, submitted):
        for s in spenders:
            mp.tx_pushed(StubPeer("a"), s.lazy)
        await poll_until(lambda: mp.orphan_count() == 2, what="bounded pool")
        assert [tx.txid for _, tx in submitted] == [spenders[0].txid]
        assert mp.state(spenders[0].txid) == TxState.PENDING
        assert {mp.state(s.txid) for s in spenders[1:]} == {TxState.ORPHAN}


async def _bch_external_oracle_prevents_orphaning():
    mk, _, spender = _forkid_pair(0x0AC)
    async with mempool_actor(prevout_lookup=mk.prevout) as (mp, submitted):
        mp.tx_pushed(StubPeer("a"), spender.lazy)
        await poll_until(lambda: len(submitted) == 1, what="direct admit")
        assert mp.orphan_count() == 0


async def _bch_confirmed_unblocks_waiting_orphans():
    mk, funding, spender = _forkid_pair(0x0FF)
    oracle_on = []  # flipped on when the "block" with the parent connects
    async with mempool_actor(
        prevout_lookup=lambda t, v: (mk.prevout if oracle_on
                                     else mk.callback)(t, v)
    ) as (mp, submitted):
        mp.tx_pushed(StubPeer("a"), spender.lazy)
        await poll_until(lambda: mp.orphan_count() == 1, what="orphan parked")
        oracle_on.append(True)
        snap = metrics.snapshot()
        mp.confirmed([funding.txid])
        await poll_until(lambda: len(submitted) == 1, what="child admitted")
        assert mp.state(funding.txid) == TxState.CONFIRMED
        key = 'mempool.orphan_resolved_by{how="block"}'
        assert metrics.snapshot().get(key, 0) - snap.get(key, 0) == 1


async def _bch_admitted_after_parent_arrives_fakenet():
    """Through Node: the park is the outcome of the drain's walk, the
    callback answers the funding outpoints alone, and the child's verdict
    is whole — until ISSUE 48 it was ``valid=True, verdicts=()``."""
    mk, funding, spender = _forkid_pair(0x0A11)
    relays = {17671: TxRelay([funding.lazy], announce=False, mode="serve",
                             push=[spender.lazy])}
    async with relay_node(relays, prevout_lookup=mk.callback) as (node, events):
        async with asyncio.timeout(20):
            seen = []
            while len(seen) < 2:
                ev = await events.receive()
                if isinstance(ev, TxVerdict):
                    seen.append(ev)
            assert [v.txid for v in seen] == [funding.txid, spender.txid]
            assert all(v.valid and v.stats.unsupported == 0 for v in seen)
            assert seen[1].stats.extracted == 2 and len(seen[1].verdicts) == 2
            assert node.mempool.orphan_count() == 0
            assert node.mempool.stats()["orphan_resolved"] == 1


@pytest.mark.asyncio
@pytest.mark.parametrize("case", [
    _bch_parked_then_resolved_by_parent,
    _bch_ttl_expiry_admits_degraded,
    _bch_size_bound_admits_oldest_degraded,
    _bch_external_oracle_prevents_orphaning,
    _bch_confirmed_unblocks_waiting_orphans,
    _bch_admitted_after_parent_arrives_fakenet,
], ids=lambda f: f.__name__[5:])
async def test_orphan_pool_on_a_forkid_network(case):
    await case()
