"""A block's known transactions are answered from their relay verdicts
(ISSUE 27): the native block path reads the mempool's finished verdicts,
keyed by the double-SHA of each transaction's full wire bytes, and verifies
only the rest — the analogue of Bitcoin Core's signature cache, which
``ConnectBlock`` reads and never writes.

One parametrised family.  Every case relays transactions to a node (or does
not), hands it a block, and holds the block's verdicts — tx by tx, signature
by signature — to (i) a mempool-less node's on the same block, (ii) the
Python reference's (``tests/fixtures.py``), and to the generator's
by-construction expectation; the rules
that keep a reused verdict exact each have their case.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import os
import random

import pytest

from chipbench import gen
from chipbench import wirefmt as w
from tests.fakenet import dummy_peer_connect, poll_until
from tests.fixtures import reference_verdicts, tuples
from tpunode import BCH_REGTEST, BTC_REGTEST, Node, NodeConfig, Publisher, TxVerdict
from tpunode.mempool import MempoolConfig, TxState
from tpunode.metrics import metrics
from tpunode.peer import PeerConnected, PeerMessage
from tpunode.store import MemoryKV
from tpunode.util import Reader
from tpunode.verify.engine import VerifyConfig
from tpunode.wire import Block, BlockHeader, LazyBlock, MsgBlock, MsgTx

txextract = pytest.importorskip("tpunode.txextract")
if not txextract.have_native_extract():
    pytest.skip("native txextract unavailable", allow_module_level=True)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "chipbench", "configs", "bch-node.json")) as _f:
    NETJ = json.load(_f)["network"]
with open(os.path.join(REPO, "chipbench", "traffic", "mempool.json")) as _f:
    # the relay cells' own mix (P2PKH, Schnorr, bare P2PK, P2SH 2-of-3), with
    # an adversarial tx in every 6 so that a small case holds several kinds
    MIX = dict(json.load(_f)["mix"], adversarial_every=6)

REUSE = ("node.reuse_blocks", "node.reuse_lookups", "node.reuse_hits",
         "node.reuse_pending", "node.reuse_unfit")


def make_txs(n: int, seed: int) -> dict:
    """``n`` signed txs of the mix: raw, txids, expected verdicts, p2pk table."""
    return gen.gen_job(gen.jobs_for(MIX, seed, n, n)[0])


def block_of(raws: list, height: int = 1, prev: bytes | None = None,
             nonce_salt: int = 0) -> LazyBlock:
    """A coinbase and ``raws`` under a mined regtest header on ``prev``
    (the genesis block by default)."""
    cb = w.coinbase(height)
    txids = [w.sha256d(cb)] + [w.sha256d(r) for r in raws]
    prev = prev or w.sha256d(w.genesis_header(NETJ))
    hdr = w.mine_header(prev, w.merkle_root(txids),
                        NETJ["genesis"]["timestamp"] + 600 * height + nonce_salt,
                        NETJ["genesis"]["bits"])
    return LazyBlock(BlockHeader.deserialize(Reader(hdr)), len(raws) + 1,
                     cb + b"".join(raws))


def lazy_tx(raw: bytes):
    return MsgTx.deserialize_payload(Reader(raw)).tx


class Drive:
    """A node, its one fake peer, every ``TxVerdict`` it published and every
    batch it handed the engine."""

    def __init__(self, node, peer):
        self.node, self.peer = node, peer
        self.verdicts: list = []
        self.submissions: list = []  # (priority, device items, txids)
        self.hold_relay: asyncio.Event | None = None
        eng = node.verify_engine
        plain = eng.verify_raw

        async def recorded(items, priority="bulk", **kw):
            self.submissions.append(
                (priority, items.count,
                 [items.txid(i) for i in range(items.n_txs)]))
            if priority == "mempool" and self.hold_relay is not None:
                await self.hold_relay.wait()
            return await plain(items, priority=priority, **kw)

        eng.verify_raw = recorded

    async def relay(self, raws: list, wait: bool = True) -> None:
        n0 = len(self.verdicts)
        for raw in raws:
            self.node._peer_pub.publish(
                PeerMessage(self.peer, MsgTx(lazy_tx(raw))))
        if wait:
            await poll_until(lambda: len(self.verdicts) - n0 >= len(raws),
                             timeout=60, what="relay verdicts")
            mp = self.node.mempool
            if mp is not None:  # the mailbox has taken them in too
                await poll_until(
                    lambda: all(mp.state(v.txid) != TxState.PENDING
                                for v in self.verdicts[n0:]),
                    what="mempool takes the verdicts")

    async def block(self, blk: LazyBlock) -> tuple:
        """-> (the block's verdicts in block order, its engine submissions,
        the reuse counters' deltas)."""
        n0, s0 = len(self.verdicts), len(self.submissions)
        c0 = {k: metrics.get(k) for k in REUSE}
        self.node._peer_pub.publish(PeerMessage(self.peer, MsgBlock(blk)))
        await poll_until(lambda: len(self.verdicts) - n0 >= blk.tx_count,
                         timeout=60, what="the block's verdicts")
        await asyncio.sleep(0.05)  # one too many would show now
        got = self.verdicts[n0:]
        order = {tx.txid: k for k, tx in enumerate(blk.txs)}
        got.sort(key=lambda v: order[v.txid])
        subs = [s for s in self.submissions[s0:] if s[0] != "mempool"]
        return got, subs, {k: int(metrics.get(k) - c0[k]) for k in REUSE}


@contextlib.asynccontextmanager
async def a_node(*, mempool: MempoolConfig | None = None, utxo: bool = False,
                 oracle=None, net=BCH_REGTEST, port: int = 17901):
    pub = Publisher(name="reuse-test", maxsize=None)
    cfg = NodeConfig(
        net=net, store=MemoryKV(), pub=pub, peers=[f"[::1]:{port}"],
        connect=lambda sa: dummy_peer_connect(net, []), discover=False,
        verify=VerifyConfig(backend="cpu", batch_size=64, max_wait=0.002),
        mempool=mempool, prevout_lookup=oracle, utxo=utxo,
    )
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            peer = await events.receive_match(
                lambda ev: ev.peer if isinstance(ev, PeerConnected) else None)
            drive = Drive(node, peer)

            async def collect():
                while True:
                    ev = await events.receive()
                    if isinstance(ev, TxVerdict):
                        drive.verdicts.append(ev)

            task = asyncio.ensure_future(collect())
            try:
                yield drive
            finally:
                task.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await task


async def plain_block(blk: LazyBlock, oracle, *, utxo: bool = False,
                      headers: list = (), net=BCH_REGTEST) -> tuple:
    """The same block through a node with no mempool (nothing to reuse):
    the verdicts to hold a reuse to."""
    async with a_node(oracle=oracle, utxo=utxo, port=17902, net=net) as d:
        for h in headers:
            d.node.chain.headers(d.peer, [h])
            await poll_until(
                lambda: d.node.chain.get_block(h.hash) is not None,
                what="header import")
        got, subs, counters = await d.block(blk)
        if utxo:
            await poll_until(lambda: d.node.utxo.height >= 1,
                             what="utxo connect")
        snap = d.node.utxo.snapshot() if utxo else None
        return got, subs, counters, snap


def mixed_case(n_known: int = 36, n_unseen: int = 7, seed: int = 27):
    known, unseen = make_txs(n_known, seed), make_txs(n_unseen, seed + 1)
    oracle = gen.Oracle()
    oracle.p2pk.update(known["p2pk"])
    oracle.p2pk.update(unseen["p2pk"])
    body = known["raw"] + unseen["raw"]
    random.Random(seed).shuffle(body)
    expect = dict(zip(known["txids"] + unseen["txids"],
                      known["expect"] + unseen["expect"]))
    return known, unseen, oracle, body, expect


CASES = [
    "mixed", "invalid-tuple", "pending", "degraded", "evicted", "witness",
    "twice", "mempool-off", "mempool-empty", "block-verdicts-not-stored",
    "utxo", "hand-built-block", "all-known",
]


@pytest.mark.asyncio
@pytest.mark.parametrize("case", CASES)
async def test_block_answered_from_relay_verdicts(case, monkeypatch):
    async with asyncio.timeout(120):
        await globals()["_case_" + case.replace("-", "_")](monkeypatch)


def _engine_txids(subs: list) -> set:
    return {t for _, _, txids in subs for t in txids}


async def _case_mixed(monkeypatch):
    """Valid, invalid, multisig and Schnorr txs relayed, then a block of
    them plus unseen ones: equal to a mempool-less node's verdicts, to the
    Python reference's and to construction; the engine sees the unseen txs
    only."""
    known, unseen, oracle, body, expect = mixed_case()
    assert any(not all(e) for e in known["expect"])
    assert any(not all(e) for e in unseen["expect"])
    assert any(len(e) > 2 for e in known["expect"])  # a 2-of-3 among them
    blk = block_of(body)
    async with a_node(mempool=MempoolConfig(tick_interval=0.05),
                      oracle=oracle) as d:
        await d.relay(known["raw"])
        relay_verdicts = {v.txid: v for v in d.verdicts}
        assert d.node.mempool.finished() == len(known["raw"])
        got, subs, counters = await d.block(blk)
    assert len(got) == blk.tx_count  # exactly one verdict per block tx
    for v in got[1:]:
        assert tuple(v.verdicts) == expect[v.txid] and v.error is None
        assert v.valid == all(v.verdicts)
    # answered from relay: the relay verdict's own fields
    for v in got:
        if v.txid in relay_verdicts:
            r = relay_verdicts[v.txid]
            assert (v.valid, v.verdicts, v.stats) == (r.valid, r.verdicts, r.stats)
    assert counters == {"node.reuse_blocks": 1,
                        "node.reuse_lookups": blk.tx_count,
                        "node.reuse_hits": len(known["raw"]),
                        "node.reuse_pending": 0, "node.reuse_unfit": 0}
    # the engine was handed the unseen txs, and only those (the coinbase
    # holds no signature)
    assert {s[0] for s in subs} == {"block"}
    assert _engine_txids(subs) - {got[0].txid} == set(unseen["txids"])
    assert sum(s[1] for s in subs) == gen.totals(MIX, len(unseen["raw"]))["items"]
    plain, plain_subs, plain_counters, _ = await plain_block(blk, oracle)
    assert tuples(got) == tuples(plain)
    assert plain_counters["node.reuse_lookups"] == 0
    assert sum(s[1] for s in plain_subs) == gen.totals(MIX, len(body))["items"]
    assert tuples(got) == reference_verdicts(list(blk.txs), oracle, bch=True)


async def _case_invalid_tuple(monkeypatch):
    """An ``INVALID`` entry gives back its own per-signature tuple: the
    2-of-3 with a bad first signature reads (False, True) from relay."""
    known = make_txs(48, 0x1A)
    oracle = gen.Oracle()
    oracle.p2pk.update(known["p2pk"])
    bad = [(t, e) for t, e in zip(known["txids"], known["expect"])
           if not all(e)]
    assert any(any(e) and not all(e) for _, e in bad)  # a mixed tuple
    blk = block_of(known["raw"])
    async with a_node(mempool=MempoolConfig(tick_interval=0.05),
                      oracle=oracle) as d:
        await d.relay(known["raw"])
        for txid, _ in bad:
            assert d.node.mempool.state(txid) == TxState.INVALID
        got, subs, counters = await d.block(blk)
    assert counters["node.reuse_hits"] == 48 and not _engine_txids(subs) - {
        got[0].txid}
    by = {v.txid: v for v in got}
    for txid, e in bad:
        assert tuple(by[txid].verdicts) == e and by[txid].valid is False


async def _case_pending(monkeypatch):
    """A tx whose relay verdict is still in flight is verified afresh by the
    block: one block verdict each, none taken from the entry."""
    first, rest = make_txs(6, 0x2A), make_txs(12, 0x2B)
    oracle = gen.Oracle()
    oracle.p2pk.update(first["p2pk"])
    oracle.p2pk.update(rest["p2pk"])
    blk = block_of(first["raw"] + rest["raw"])
    expect = dict(zip(first["txids"] + rest["txids"],
                      first["expect"] + rest["expect"]))
    async with a_node(mempool=MempoolConfig(tick_interval=0.05),
                      oracle=oracle) as d:
        await d.relay(first["raw"])
        d.hold_relay = asyncio.Event()
        await d.relay(rest["raw"], wait=False)
        await poll_until(
            lambda: all(d.node.mempool.state(t) == TxState.PENDING
                        for t in rest["txids"]), what="admission")
        await poll_until(lambda: any(s[0] == "mempool" and set(s[2]) & set(
            rest["txids"]) for s in d.submissions), what="relay in flight")
        n0 = len(d.verdicts)
        got, subs, counters = await d.block(blk)
        assert counters["node.reuse_hits"] == 6
        assert counters["node.reuse_pending"] == 12
        assert _engine_txids(subs) - {got[0].txid} == set(rest["txids"])
        assert len(got) == blk.tx_count
        for v in got[1:]:
            assert tuple(v.verdicts) == expect[v.txid]
        d.hold_relay.set()  # the relay verdicts still arrive, once each
        await poll_until(lambda: len(d.verdicts) == n0 + blk.tx_count + 12,
                         what="late relay verdicts")
        await asyncio.sleep(0.05)
        assert len(d.verdicts) == n0 + blk.tx_count + 12
        # and they find the entries CONFIRMED: nothing becomes reusable
        assert d.node.mempool.finished() == 0


async def _case_degraded(monkeypatch):
    """A tx relayed while an input's prevout was unknown (``unsupported``
    > 0) is not answered from that verdict: the block verifies it with what
    the block's time knows."""
    known = make_txs(24, 0x3A)
    full = gen.Oracle()
    full.p2pk.update(known["p2pk"])
    assert known["p2pk"]  # bare P2PK inputs: the key is in the prevout script
    late: dict = {}  # what the relay-time oracle knows of them: nothing yet
    relay_time = gen.Oracle()
    relay_time.p2pk = late
    blk = block_of(known["raw"])
    async with a_node(mempool=MempoolConfig(tick_interval=0.05),
                      oracle=relay_time) as d:
        await d.relay(known["raw"])
        degraded = {v.txid for v in d.verdicts if v.stats.unsupported}
        assert degraded and len(degraded) < 24
        late.update(known["p2pk"])  # by block time the prevouts are known
        got, subs, counters = await d.block(blk)
    assert counters["node.reuse_unfit"] == len(degraded)
    assert counters["node.reuse_hits"] == 24 - len(degraded)
    assert _engine_txids(subs) - {got[0].txid} == degraded
    plain, _, _, _ = await plain_block(blk, full)
    assert tuples(got) == tuples(plain)
    assert all(v.stats.unsupported == 0 for v in got)


async def _case_evicted(monkeypatch):
    """Entries the LRU let go are misses: verified afresh, one verdict each."""
    known = make_txs(30, 0x4A)
    oracle = gen.Oracle()
    oracle.p2pk.update(known["p2pk"])
    blk = block_of(known["raw"])
    async with a_node(mempool=MempoolConfig(tick_interval=0.05, max_txs=10),
                      oracle=oracle) as d:
        for lo in range(0, 30, 10):  # finished entries may be evicted
            await d.relay(known["raw"][lo:lo + 10])
        kept = {t for t in known["txids"]
                if d.node.mempool.state(t) is not None}
        assert 0 < len(kept) <= 10
        got, subs, counters = await d.block(blk)
    assert counters["node.reuse_hits"] == len(kept)
    assert _engine_txids(subs) - {got[0].txid} == set(known["txids"]) - kept
    plain, _, _, _ = await plain_block(blk, oracle)
    assert tuples(got) == tuples(plain) and len(got) == 31


async def _case_witness(monkeypatch):
    """On a segwit network the key is the hash of the full wire bytes: the
    same txid under another witness misses, and is verified as it stands."""
    from benchmarks.txgen import gen_signed_txs
    from tpunode.wire import Tx

    txs = gen_signed_txs(6, inputs_per_tx=1, seed=0x5A, segwit_every=2)
    spender = next(t for t in txs if t.has_witness)
    sig, key = spender.witnesses[0]
    forged = Tx(spender.version, spender.inputs, spender.outputs,
                spender.locktime,
                witnesses=((sig[:10] + bytes([sig[10] ^ 1]) + sig[11:], key),))
    assert forged.txid == spender.txid and forged.wtxid != spender.wtxid
    raws = [t.serialize() for t in txs]
    header = BlockHeader(1, b"\x00" * 32, b"\x00" * 32, 0, 0x207FFFFF, 0)

    def blk(body):
        cb = w.coinbase(1)
        return LazyBlock(header, len(body) + 1, cb + b"".join(
            t.serialize() for t in body))

    stripped = Tx(spender.version, spender.inputs, spender.outputs,
                  spender.locktime)
    assert stripped.txid == spender.txid and not stripped.has_witness
    # (what stands in for the spender, hits, seen-but-unfit)
    for stand_in, hits, unfit in ((forged, 5, 0), (stripped, 5, 1),
                                  (spender, 6, 0)):
        body = [stand_in if t is spender else t for t in txs]
        async with a_node(mempool=MempoolConfig(tick_interval=0.05),
                          net=BTC_REGTEST) as d:
            await d.relay(raws)
            assert all(v.valid for v in d.verdicts)
            got, subs, counters = await d.block(blk(body))
        assert counters["node.reuse_hits"] == hits
        assert counters["node.reuse_unfit"] == unfit
        # (the stripped spend holds no signature: nothing for the engine)
        want = {spender.txid} if stand_in is forged else set()
        assert _engine_txids(subs) - {got[0].txid} == want
        plain, _, _, _ = await plain_block(blk(body), None, net=BTC_REGTEST)
        assert tuples(got) == tuples(plain)  # each verified as it stands
        by = {v.txid: v for v in got}
        assert by[spender.txid].valid is (stand_in is not forged)
        # stripped: no signature left to judge, and the stats say so
        assert by[spender.txid].stats.unsupported == (stand_in is stripped)


async def _case_twice(monkeypatch):
    """The block path does not write the store: the same block a second
    time finds its txs confirmed, and verifies every one of them."""
    known, unseen, oracle, body, expect = mixed_case(18, 4, seed=0x6A)
    blk = block_of(body)
    async with a_node(mempool=MempoolConfig(tick_interval=0.05),
                      oracle=oracle) as d:
        await d.relay(known["raw"])
        got1, subs1, c1 = await d.block(blk)
        await poll_until(lambda: d.node.mempool.state(
            known["txids"][0]) == TxState.CONFIRMED, what="confirmation")
        got2, subs2, c2 = await d.block(blk)
    assert c1["node.reuse_hits"] == 18 and c2["node.reuse_hits"] == 0
    # (the second time the store is empty of finished verdicts: no look-up)
    assert c2["node.reuse_lookups"] in (0, blk.tx_count)
    assert _engine_txids(subs2) - {got2[0].txid} == set(expect)
    assert tuples(got1) == tuples(got2)


async def _case_mempool_off(monkeypatch):
    known, unseen, oracle, body, expect = mixed_case(12, 3, seed=0x7A)
    blk = block_of(body)
    async with a_node(oracle=oracle) as d:
        await d.relay(known["raw"])  # verified, and kept nowhere
        got, subs, counters = await d.block(blk)
    assert all(v == 0 for v in counters.values())
    assert sum(s[1] for s in subs) == gen.totals(MIX, 15)["items"]
    assert [s[2] for s in subs] == [[v.txid for v in got]]  # as today


async def _case_mempool_empty(monkeypatch):
    """A mempool with no finished verdict costs a block nothing: no hash,
    no look-up, the items of today."""
    known, unseen, oracle, body, expect = mixed_case(12, 3, seed=0x7B)
    blk = block_of(body)
    looked = []
    async with a_node(mempool=MempoolConfig(tick_interval=0.05),
                      oracle=oracle) as d:
        monkeypatch.setattr(d.node.mempool, "relay_verdicts",
                            lambda keys: looked.append(keys))
        monkeypatch.setattr(txextract.ParsedTxRegion, "wire_hashes",
                            lambda self: looked.append(self))
        got, subs, counters = await d.block(blk)
    assert not looked and all(v == 0 for v in counters.values())
    assert sum(s[1] for s in subs) == gen.totals(MIX, 15)["items"]
    assert [s[2] for s in subs] == [[v.txid for v in got]]
    assert [tuple(v.verdicts) for v in got[1:]] == [
        expect[v.txid] for v in got[1:]]


async def _case_block_verdicts_not_stored(monkeypatch):
    """A pool of txs that comes in block after block (``bch-32mb.blocks``)
    never becomes a cache measurement: block verdicts are not stored."""
    known, unseen, oracle, body, expect = mixed_case(20, 1, seed=0x8A)
    other = make_txs(2, 0x8F)  # something relayed, so that look-ups happen
    oracle.p2pk.update(other["p2pk"])
    async with a_node(mempool=MempoolConfig(tick_interval=0.05),
                      oracle=oracle) as d:
        await d.relay(other["raw"])
        assert d.node.mempool.finished() == 2
        for k in range(3):
            random.Random(k).shuffle(body)
            got, subs, counters = await d.block(block_of(body, nonce_salt=k))
            assert counters["node.reuse_lookups"] == 22
            assert counters["node.reuse_hits"] == 0
            assert _engine_txids(subs) - {got[0].txid} == set(expect)
            assert all(d.node.mempool.state(t) is None for t in expect)
        assert d.node.mempool.finished() == 2


async def _case_utxo(monkeypatch):
    """The UTXO delta and the watermark cover the whole block, reuse or not."""
    known, unseen, oracle, body, expect = mixed_case(24, 5, seed=0x9A)
    blk = block_of(body)
    async with a_node(mempool=MempoolConfig(tick_interval=0.05),
                      oracle=oracle, utxo=True) as d:
        d.node.chain.headers(d.peer, [blk.header])
        await poll_until(
            lambda: d.node.chain.get_block(blk.header.hash) is not None,
            what="header import")
        await d.relay(known["raw"])
        got, subs, counters = await d.block(blk)
        assert counters["node.reuse_hits"] == 24
        await poll_until(lambda: d.node.utxo.height == 1, what="utxo connect")
        assert d.node.utxo.block_hash == blk.header.hash
        snap = d.node.utxo.snapshot()
        await poll_until(lambda: d.node.mempool.size() == 0, what="eviction")
    plain, _, _, plain_snap = await plain_block(
        blk, oracle, utxo=True, headers=[blk.header])
    assert tuples(got) == tuples(plain)
    assert snap == plain_snap and len(snap) > 29


async def _case_hand_built_block(monkeypatch):
    """A ``wire.Block`` built in-process (``raw_txs is None``) is its wire
    form at the node's door: its known txs are answered from relay under
    the hash of the bytes it serialises to, the engine sees the rest, the
    verdicts are the Python reference's, and no block verdict is stored."""
    known, unseen, oracle, body, expect = mixed_case(10, 2, seed=0xAA)
    lazy = block_of(body)
    blk = Block(lazy.header, lazy.txs)
    assert blk.raw_txs is None
    async with a_node(mempool=MempoolConfig(tick_interval=0.05),
                      oracle=oracle) as d:
        await d.relay(known["raw"])
        got, subs, counters = await d.block(blk)
        assert counters["node.reuse_hits"] == 10
        assert counters["node.reuse_lookups"] == blk.tx_count == 13
        assert _engine_txids(subs) - {got[0].txid} == set(unseen["txids"])
        assert tuples(got) == reference_verdicts(list(blk.txs), oracle, bch=True)
        assert [tuple(v.verdicts) for v in got[1:]] == [
            expect[v.txid] for v in got[1:]]
        await poll_until(lambda: d.node.mempool.state(
            known["txids"][0]) == TxState.CONFIRMED, what="confirmation")
        assert d.node.mempool.finished() == 0
        assert all(d.node.mempool.state(t) is None for t in unseen["txids"])


async def _case_all_known(monkeypatch):
    """A block with nothing unseen but its coinbase: no engine item at all."""
    known = make_txs(130, 0xBA)  # past the sharding threshold of 128
    oracle = gen.Oracle()
    oracle.p2pk.update(known["p2pk"])
    blk = block_of(known["raw"])
    async with a_node(mempool=MempoolConfig(tick_interval=0.05),
                      oracle=oracle) as d:
        await d.relay(known["raw"])
        got, subs, counters = await d.block(blk)
    assert counters["node.reuse_hits"] == 130 and len(got) == 131
    assert sum(s[1] for s in subs) == 0
    assert [tuple(v.verdicts) for v in got[1:]] == known["expect"]
