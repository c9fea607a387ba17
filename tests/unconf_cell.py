"""What ``tests/test_unconf_cell.py`` and the BCH orphan cases of
``tests/test_mempool.py`` share: BCH transactions made by hand that spend
each other — ``chipbench/gen.py``'s signing over ``gen_chain.py``'s outputs,
every input SIGHASH_ALL|FORKID over its parent's true amount — the truth
about their prevouts as their raw bytes give it, and a synced node with a
mempool, a UTXO set and a callback that knows funding outpoints alone."""

from __future__ import annotations

import asyncio
import contextlib
import random

from chipbench import gen, harness, reference_chain, secp
from chipbench import wirefmt as w
from chipbench.gen_chain import FEE, _Held, _script_for
from tests.fakenet import dummy_peer_connect, poll_until
from tpunode import BCH_REGTEST, Node, NodeConfig, Publisher, TxVerdict
from tpunode.mempool import MempoolConfig
from tpunode.peer import PeerConnected, PeerMessage
from tpunode.store import MemoryKV
from tpunode.util import Reader
from tpunode.verify.engine import VerifyConfig
from tpunode.wire import BlockHeader, LazyBlock, LazyTx, MsgBlock, MsgTx

CELL = "bch-unconf.tip-unconf"
BENCH, WL, CONFIG, TRAFFIC = harness.load_cell(CELL)
NETJ = CONFIG["network"]
GENESIS = w.sha256d(w.genesis_header(NETJ))
KINDS = ("p2pkh", "schnorr", "p2pk", "msig")


class Wire:
    """A tx as its wire bytes."""

    def __init__(self, raw: bytes):
        self.raw, self.txid = raw, w.sha256d(raw)

    @property
    def lazy(self) -> LazyTx:
        """As the node's decoder hands a pushed tx on."""
        return LazyTx(self.raw)


class Made(Wire):
    """One signed tx: its bytes, what construction says of its signatures,
    and the keys its outputs wait under."""

    def __init__(self, raw: bytes, expect: tuple, outs: list, held: list):
        super().__init__(raw)
        self.expect, self.outs, self.held = expect, outs, held


class Maker:
    """Txs of two outputs each.  ``tx(spends, pays)``: ``spends`` is a list
    of ``(kind, None)`` (a funding outpoint nobody made) or ``(kind,
    (parent, vout))`` (``parent.outs[vout]`` was made for an input of that
    kind); ``pays`` names, for each of the two outputs, the kind of input
    that will spend it (None: nobody's)."""

    def __init__(self, seed: int):
        self.rng = random.Random(f"unconf-test:{seed}")
        self.keys = secp.Chain(self.rng.getrandbits(256))
        self.nonces = secp.Chain(self.rng.getrandbits(256))
        self.nobody = gen.p2pkh_code(b"\x02" + self.rng.randbytes(32))
        self.funding: dict = {}  # outpoint (36 bytes) -> (amount, script)
        self.made: dict = {}  # txid -> Made

    def tx(self, spends: list, pays=(None, None), adv=None) -> Made:
        ins, amounts, signers = [], [], []
        for kind, src in spends:
            if src is None:
                txin = (self.rng.randbytes(32), self.rng.randrange(4))
                amounts.append(gen.synth_amount(*txin))
                signers.append(self.keys)
            else:
                parent, vout = src
                txin = (parent.txid, vout)
                amounts.append(parent.outs[vout][0])
                signers.append(_Held(list(parent.held[vout])))
            ins.append(txin + (b"", 0xFFFFFFFF))
        rest = sum(amounts) - FEE
        first = int(rest * self.rng.uniform(0.4, 0.6))
        outs, held = [], []
        for value, kind in zip((first, rest - first), pays):
            script, keys = ((self.nobody, []) if kind is None
                            else _script_for(kind, self.keys))
            outs.append((value, script))
            held.append(keys)
        mid = w.forkid_midstate(2, ins, outs, 0)
        signed, verdicts = [], ()
        for i, ((kind, src), txin) in enumerate(zip(spends, ins)):
            script, pscript, vs = gen._sign_input(
                kind, adv if i == 0 else None, signers[i], self.nonces, mid,
                txin, amounts[i])
            if src is None:
                key = txin[0] + txin[1].to_bytes(4, "little")
                self.funding[key] = (amounts[i],
                                     pscript or gen.synth_script(txin[0]))
            signed.append((txin[0], txin[1], script, txin[3]))
            verdicts += vs
        made = Made(w.ser_tx(2, signed, outs, 0), verdicts, outs, held)
        self.made[made.txid] = made
        return made

    def chain(self, kind: str, length: int = 3) -> list:
        """``length`` txs, each spending output 0 of the one before through
        an input of ``kind`` beside a funding input."""
        txs = [self.tx([("p2pkh", None), ("p2pkh", None)], (kind, None))]
        for _ in range(length - 1):
            txs.append(self.tx([(kind, (txs[-1], 0)), ("p2pkh", None)],
                               (kind, None)))
        return txs

    # ---- the truth about prevouts ---------------------------------------------

    def prevout(self, txid: bytes, vout: int):
        """Any outpoint's ``(amount, script)`` as the raw bytes give it."""
        if txid in self.made:
            return self.made[txid].outs[vout]
        return self.funding.get(txid + vout.to_bytes(4, "little"))

    def callback(self, txid: bytes, vout: int):
        """An embedder's index: funding outpoints, nothing unconfirmed."""
        return self.funding.get(txid + vout.to_bytes(4, "little"))

    def table(self, txs: list) -> dict:
        """The reference's table for ``txs``, as ``drivers/open_unconf``
        cuts it: a traffic output from its parent's raw bytes, a bare-P2PK
        funding outpoint's script."""
        out = {}
        for tx in txs:
            (_, ins, _, _), _ = w.parse_tx(tx.raw)
            for txid, vout, _, _ in ins:
                key = txid + vout.to_bytes(4, "little")
                if txid in self.made:
                    (_, _, outs, _), _ = w.parse_tx(self.made[txid].raw)
                    out[key] = outs[vout]
                elif self.funding[key][1] != gen.synth_script(txid):
                    out[key] = self.funding[key][1]
        return out

    def reference(self, txs: list, table=None) -> list:
        got = dict(reference_chain.check_job(
            {"raw": [t.raw for t in txs],
             "p2pk": self.table(txs) if table is None else table}))
        return [got[t.txid] for t in txs]


def block_of(txs: list, height: int = 1, prev: bytes = GENESIS) -> LazyBlock:
    """``txs`` (``Made``), in that order, under a coinbase and a header on
    ``prev``."""
    cb = w.coinbase(height)
    merkle = w.merkle_root([w.sha256d(cb)] + [t.txid for t in txs])
    hdr = w.mine_header(prev, merkle,
                        NETJ["genesis"]["timestamp"] + 600 * height,
                        NETJ["genesis"]["bits"])
    return LazyBlock(BlockHeader.deserialize(Reader(hdr)), len(txs) + 1,
                     cb + b"".join(t.raw for t in txs))


class Drive:
    """A node, its one fake peer, every ``TxVerdict`` in the order it was
    published."""

    def __init__(self, node, peer):
        self.node, self.peer = node, peer
        self.order: list = []
        self.hold: asyncio.Event | None = None  # set: verification waits
        plain = node.verify_engine.verify_raw

        async def held(items, **kw):
            if self.hold is not None:
                await self.hold.wait()
            return await plain(items, **kw)

        node.verify_engine.verify_raw = held

    @property
    def verdicts(self) -> dict:
        return {v.txid: v for v in self.order}

    def relay(self, tx: Wire) -> None:
        self.node._peer_pub.publish(PeerMessage(self.peer, MsgTx(tx.lazy)))

    async def know(self, blk: LazyBlock) -> None:
        self.node.chain.headers(self.peer, [blk.header])
        await poll_until(
            lambda: self.node.chain.get_block(blk.header.hash) is not None,
            what="header import")

    def give(self, blk: LazyBlock) -> None:
        self.node._peer_pub.publish(PeerMessage(self.peer, MsgBlock(blk)))

    async def count(self, n: int, timeout: float = 30) -> None:
        await poll_until(lambda: len(self.order) >= n, timeout=timeout,
                         what=f"{n} verdicts")


@contextlib.asynccontextmanager
async def a_node(lookup=None, *, relay=None, port: int = 17948,
                 utxo: bool = True, **mempool):
    """``bch-unconf``'s node at a test's size: a mempool, a UTXO set, the
    callback ``lookup``; one fake peer (``relay``: what it pushes and serves,
    ``fakenet.TxRelay``)."""
    pub = Publisher(name="unconf-test", maxsize=None)
    cfg = NodeConfig(
        net=BCH_REGTEST, store=MemoryKV(), pub=pub, peers=[f"[::1]:{port}"],
        connect=lambda sa: dummy_peer_connect(BCH_REGTEST, [], relay=relay),
        discover=False, utxo=utxo, prevout_lookup=lookup,
        mempool=MempoolConfig(**{"tick_interval": 0.05, **mempool}),
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
                        drive.order.append(ev)

            task = asyncio.ensure_future(collect())
            try:
                yield drive
            finally:
                task.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await task
