"""What ``tests/test_chain_cell.py`` and the restart case of
``tests/test_utxo.py`` share: a short chain of ``chipbench/gen_chain.py``
that spends its own outputs, the truth about its prevouts as the raw blocks
give it, and a node with a UTXO set, a snapshot and no prevout callback
that is handed its blocks one ``block`` message at a time."""

from __future__ import annotations

import asyncio
import contextlib
import functools

from chipbench import gen, gen_chain, harness
from chipbench import wirefmt as w
from tests.fakenet import dummy_peer_connect, poll_until
from tpunode import BCH_REGTEST, Node, NodeConfig, Publisher, TxVerdict
from tpunode.metrics import metrics
from tpunode.peer import PeerConnected, PeerMessage
from tpunode.store import MemoryKV
from tpunode.util import Reader
from tpunode.utxo import snapshot_batch
from tpunode.verify.engine import VerifyConfig
from tpunode.wire import BlockHeader, LazyBlock, MsgBlock

CELL = "bch-chain.ibd-recent"
BENCH, _WL, CONFIG, TRAFFIC = harness.load_cell(CELL)
NETJ = CONFIG["network"]
GENESIS = w.sha256d(w.genesis_header(NETJ))
# a short chain that holds all four sources and every adversarial kind
SHORT = harness.deep_merge(TRAFFIC, {
    "mix": {"adversarial_every": 16}, "chain": {"old_blocks": [49, 56]}})


class Chain:
    """``n`` blocks of the mix, woven from their strands."""

    def __init__(self, n: int, seed: int, traffic: dict = SHORT):
        self.n, self.per = n, traffic["txs_per_block"]
        parts = [gen_chain.strand_job(j)
                 for j in gen_chain.jobs_for(traffic, seed, n)]
        self.got = {k: sum(p["got"].get(k, 0) for p in parts)
                    for k in gen_chain.SOURCES}
        self.expect = {t: e for p in parts
                       for t, e in zip(p["txids"], p["expect"])}
        self.p2pk = {k: v for p in parts for k, v in p["p2pk"].items()}
        spent = b"".join(p["snapshot"] for p in parts)
        self.snapshot = [spent[i:i + 36] for i in range(0, len(spent), 36)]
        self.bodies, self.txids, self.offsets = gen_chain.weave(
            parts, n, self.per)
        self.headers, self.hashes, frames = gen.chain_frames(NETJ, self.bodies)
        for body in self.bodies:
            self.expect[body[1]] = ()  # a coinbase signs nothing
        # what the raw blocks say of every output they make
        self.made: dict = {}
        self.raw: dict = {}
        for body, ids, offs in zip(self.bodies, self.txids, self.offsets):
            for txid, lo, hi in zip(ids, offs, offs[1:]):
                raw = body[2][lo:hi]
                self.raw[txid] = raw
                (_, _, outs, _), _ = w.parse_tx(raw)
                for vout, out in enumerate(outs):
                    self.made[txid + vout.to_bytes(4, "little")] = out

    def block(self, height: int, prev: bytes | None = None,
              salt: int = 0) -> LazyBlock:
        """Block ``height`` as the node's decoder would hand it on; with
        ``prev`` / ``salt`` the same transactions under another header."""
        body = self.bodies[height - 1]
        hdr = self.headers[height - 1]
        if prev is not None or salt:
            if prev is None:
                prev = (w.sha256d(self.headers[height - 2]) if height > 1
                        else GENESIS)
            hdr = w.mine_header(
                prev, body[0],
                NETJ["genesis"]["timestamp"] + 600 * height + salt,
                NETJ["genesis"]["bits"])
        n, off = w.read_varint(body[2], 0)
        return LazyBlock(BlockHeader.deserialize(Reader(hdr)), n,
                         body[2][off:])

    def prevout(self, txid: bytes, vout: int) -> tuple:
        """(amount, script) of any outpoint the chain spends: the parent's
        output as the raw blocks have it, else the snapshot's entry."""
        key = txid + vout.to_bytes(4, "little")
        if key in self.made:
            return self.made[key]
        return (gen.synth_amount(txid, vout),
                self.p2pk.get(key) or gen.synth_script(txid))

    def table(self, txids: list) -> dict:
        """The reference's table for these txs, as ``drivers/ibd_chain``
        makes it."""
        out = {}
        for t in txids:
            (_, ins, _, _), _ = w.parse_tx(self.raw[t])
            for txid, vout, _, _ in ins:
                key = txid + vout.to_bytes(4, "little")
                if key in self.made:
                    out[key] = self.made[key]
                elif key in self.p2pk:
                    out[key] = self.p2pk[key]
        return out

    def snapshot_blob(self) -> bytes:
        return snapshot_batch(
            (k[:32], int.from_bytes(k[32:], "little"))
            + self.prevout(k[:32], int.from_bytes(k[32:], "little"))
            for k in self.snapshot)


@functools.lru_cache(maxsize=None)
def chain(n: int = 60, seed: int = 44) -> Chain:
    return Chain(n, seed)


class Drive:
    """A node, its one fake peer, every ``TxVerdict`` it published."""

    def __init__(self, node, peer):
        self.node, self.peer = node, peer
        self.verdicts: dict = {}
        self.hold: asyncio.Event | None = None  # set: verification waits
        eng = node.verify_engine
        plain = eng.verify_raw

        async def held(items, **kw):
            if self.hold is not None:
                await self.hold.wait()
            return await plain(items, **kw)

        eng.verify_raw = held

    async def know(self, headers: list) -> None:
        hs = [BlockHeader.deserialize(Reader(h)) if isinstance(h, bytes) else h
              for h in headers]
        self.node.chain.headers(self.peer, hs)
        await poll_until(
            lambda: self.node.chain.get_block(hs[-1].hash) is not None,
            what="header import")

    def give(self, blk: LazyBlock) -> None:
        self.node._peer_pub.publish(PeerMessage(self.peer, MsgBlock(blk)))

    async def verdicts_of(self, txids: list, timeout: float = 90) -> None:
        await poll_until(lambda: all(t in self.verdicts for t in txids),
                         timeout=timeout, what="verdicts")

    def clear_view(self) -> bool:
        n = self.node
        return (len(n._inflight) == 0 and n._inflight.blocks == 0
                and not n._gate_passed and not n._gate_waiters
                and n._gate_held == 0)


@contextlib.asynccontextmanager
async def a_node(ch: Chain | None = None, *, store=None, ibd=None,
                 known: int | None = None, also=(),
                 port: int = 17944, lookup=None):
    """``NodeConfig(utxo=True)``, no callback (but ``lookup``), no mempool;
    with ``ch`` its snapshot (and ``also``: further batches of entries) is
    loaded and its headers (the first ``known`` of them) are known."""
    pub = Publisher(name="chain-test", maxsize=None)
    cfg = NodeConfig(
        net=BCH_REGTEST, store=store if store is not None else MemoryKV(),
        pub=pub, peers=[f"[::1]:{port}"],
        connect=lambda sa: dummy_peer_connect(BCH_REGTEST, []),
        discover=False, utxo=True, ibd=ibd, prevout_lookup=lookup,
        verify=VerifyConfig(backend="cpu", batch_size=64, max_wait=0.002),
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
                        drive.verdicts[ev.txid] = ev

            task = asyncio.ensure_future(collect())
            try:
                if ch is not None:
                    if node.utxo.height < 0:
                        node.utxo.load_snapshot(
                            0, GENESIS, [ch.snapshot_blob(), *also])
                    await drive.know(ch.headers[:known])
                yield drive
            finally:
                task.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await task


def moved(names: tuple) -> dict:
    return {k: metrics.get(k) for k in names}
