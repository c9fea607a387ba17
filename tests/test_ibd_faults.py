"""The fetch planner under a network's faults (ISSUE 36): a peer that stops
in the middle of a block, a connection reset with replies half sent, a slow
peer whose blocks arrive after they were asked of another, a block sent
twice — and eight healthy peers that serve no blocks at all, whom none of
it may touch.  Fakenet, CPU, seconds each.

The remotes here work on BYTES, as a TCP stream does: a peer that stops in
the middle of a block frame can send nothing behind it, its pongs
included, until it goes on.
"""

from __future__ import annotations

import asyncio
import contextlib
import random
import time

import pytest

from benchmarks.txgen import gen_chain
from tests.fakenet import (
    QueueConnection,
    _QueueReader,
    mock_peer_react,
    poll_until,
)
from tests.fixtures import all_blocks
from tests.test_ibd import NET, ibd_node
from tpunode import IbdConfig, TxVerdict
from tpunode.events import events
from tpunode.ibd import BlockFetcher
from tpunode.mempool import MempoolConfig
from tpunode.metrics import metrics
from tpunode.params import NODE_NETWORK
from tpunode.peer import PeerDisconnected, PeerStalling
from tpunode.store import MemoryKV
from tpunode.wire import (
    HEADER_SIZE,
    MsgBlock,
    MsgGetData,
    MsgHeaders,
    MsgPing,
    MsgVersion,
    NetworkAddress,
    decode_message,
    decode_message_header,
    encode_message,
)

RESET = b"<reset>"  # a chunk that stands for a TCP RST


class _Pipe(QueueConnection):
    async def read_chunk(self) -> bytes:
        chunk = await super().read_chunk()
        if chunk == RESET:
            raise ConnectionResetError("peer reset the connection")
        return chunk


class Remote:
    """One address's scripted peer, over every connection made to it.

    ``freeze_at`` / ``reset_at``: the peer's n-th ``block`` message
    (counted over its life, from 1) leaves half sent; then nothing more
    leaves (until :meth:`thaw`), or the connection is reset.  ``twice``:
    block heights it sends two copies of.  ``serve``: whether it has the
    blocks at all.  It keeps its chain to itself until :meth:`offer`."""

    def __init__(self, blocks, *, freeze_at=0, reset_at=0, twice=(),
                 serve=True, reset_with=RESET):
        self.blocks = blocks
        self.freeze_at, self.reset_at = freeze_at, reset_at
        self.reset_with = reset_with  # RESET, or b"": the stream just ends
        self.twice = {blocks[h - 1].header.hash for h in twice}
        self.serve = serve
        self.sent_blocks = 0
        self.held: list = []  # bytes behind a half-sent frame
        self.frozen = False
        self.to_node = None
        self.got: list = []  # (monotonic, message) from the node
        self.connections = 0
        self.offered = False

    def _emit(self, data: bytes) -> None:
        if self.frozen:
            self.held.append(data)
        else:
            self.to_node.put_nowait(data)

    def offer(self) -> None:
        """Announce the chain (the node then asks for the blocks)."""
        self.offered = True
        self._send(MsgHeaders(tuple((b.header, 0) for b in self.blocks)))

    def thaw(self) -> None:
        """The peer goes on where it stopped."""
        self.frozen = False
        for data in self.held:
            self.to_node.put_nowait(data)
        self.held = []

    def _send(self, msg) -> None:
        data = encode_message(NET, msg)
        if isinstance(msg, MsgBlock):
            self.sent_blocks += 1
            if self.sent_blocks == self.reset_at:
                self._emit(data[: len(data) // 2])
                self._emit(self.reset_with)
                raise EOFError
            if self.sent_blocks == self.freeze_at:
                self._emit(data[: len(data) // 2])
                self.frozen = True
                self._emit(data[len(data) // 2:])
                return
        self._emit(data)

    async def run(self, to_node, from_node) -> None:
        self.to_node, self.connections = to_node, self.connections + 1
        self.frozen, self.held = False, []
        addr = NetworkAddress.from_host_port("::1", 0, services=NODE_NETWORK)
        self._send(MsgVersion(
            version=70012, services=NODE_NETWORK, timestamp=int(time.time()),
            addr_recv=addr, addr_from=addr, nonce=random.getrandbits(64),
            user_agent=b"/faults:0/",
            start_height=len(self.blocks) if self.offered else 0,
            relay=True))
        reader = _QueueReader(from_node)
        try:
            while True:
                header = decode_message_header(
                    NET, await reader.read_exact(HEADER_SIZE))
                payload = (await reader.read_exact(header.length)
                           if header.length else b"")
                msg = decode_message(NET, header, payload)
                self.got.append((time.monotonic(), msg))
                for reply in mock_peer_react(
                        NET, self.blocks if self.offered else [], msg,
                        serve_blocks=self.serve):
                    self._send(reply)
                    if (isinstance(reply, MsgBlock)
                            and reply.block.header.hash in self.twice):
                        self._emit(encode_message(NET, reply))
        except EOFError:
            pass

    def asked_for(self, since: float = 0.0) -> list:
        """Block hashes the node asked this peer for, in order."""
        return [iv.hash for t, m in self.got
                if isinstance(m, MsgGetData) and t >= since for iv in m.invs]


def connect_to(remotes: dict):
    """``NodeConfig.connect`` over scripted remotes keyed by port."""

    def connect(sa):
        @contextlib.asynccontextmanager
        async def factory():
            to_node, from_node = asyncio.Queue(), asyncio.Queue()
            task = asyncio.get_running_loop().create_task(
                remotes[sa[1]].run(to_node, from_node))
            try:
                yield _Pipe(to_node, from_node)
            finally:
                task.cancel()
                with contextlib.suppress(asyncio.CancelledError, Exception):
                    await task

        return factory

    return connect


def peers_of(remotes: dict) -> list:
    return [f"[::1]:{port}" for port in remotes]


async def all_online(node, remotes: dict) -> None:
    """Dial every remote now (the connect loop draws 0.1-5 s a dial), and
    have them announce their chain once all are online."""
    await node.peer_mgr._started.wait()  # it takes no order before
    for port in remotes:
        node.peer_mgr.connect(("::1", port))
    await poll_until(lambda: len(node.peer_mgr.get_peers()) == len(remotes),
                     timeout=20, what="every peer online")
    for r in remotes.values():
        r.offer()


class Counters:
    def __init__(self, *names):
        self.base = {n: metrics.get(n) for n in names}

    def __getitem__(self, name: str) -> int:
        return int(metrics.get(name) - self.base[name])


COUNTERS = ("ibd.stalls", "ibd.blocks_rerequested", "ibd.batch_failures",
            "span.ibd.stall.count", "span.peer.reconnect.count",
            "node.block_duplicate_skipped", "node.block_replay_skipped",
            "peermgr.disconnects", "peermgr.timed_bans")


@pytest.mark.asyncio
async def test_a_peer_that_stops_mid_block_is_a_staller():
    """Detected within the timeout and a tick, only the blocks that did not
    arrive are asked of another peer, the staller is disconnected under
    ``PeerStalling`` (a timed ban) and never handed another batch."""
    blocks = all_blocks()  # 15
    remotes = {1: Remote(blocks, freeze_at=2), 2: Remote(blocks)}
    c = Counters(*COUNTERS)
    seen = len(events.tail(100000))
    ibd = IbdConfig(batch_blocks=4, tick_interval=0.05, stall_timeout=0.4)
    async with ibd_node(MemoryKV(), blocks, connect=connect_to(remotes),
                        peers=peers_of(remotes), ibd=ibd, max_peers=2) as (
            node, bus):
        gone = []

        async def watch():
            while True:
                ev = await bus.receive()
                if isinstance(ev, PeerDisconnected):
                    gone.append(ev.peer)

        task = asyncio.ensure_future(watch())  # asyncsan: disable=raw-spawn (test observer, cancelled below)
        try:
            await all_online(node, remotes)
            await poll_until(lambda: node.utxo.height == len(blocks),
                             what="sync past the staller")
            await poll_until(lambda: gone, what="the staller's disconnect")
        finally:
            task.cancel()
        assert node.ibd.stats()["stalls"] == 1
    stall = [e for e in events.tail(100000)[seen:] if e["type"] == "ibd.stall"]
    assert len(stall) == 1 and stall[0]["peer"] == "[::1]:1"
    assert 0.4 < stall[0]["idle"] < 0.4 + 0.05 + 0.25  # timeout + a tick
    assert c["ibd.stalls"] == 1 and c["span.ibd.stall.count"] == 1
    assert c["peermgr.timed_bans"] == 1 and [p.label for p in gone] == ["[::1]:1"]
    staller, healthy = remotes[1], remotes[2]
    # one block of the staller's batch was whole, one stopped half way:
    # what it was asked for and did not deliver went to the other peer,
    # block for block, and nothing that HAD arrived was asked for again
    owed = staller.asked_for()[1:]
    again = [h for h in healthy.asked_for() if h in set(staller.asked_for())]
    assert again == owed and c["ibd.blocks_rerequested"] == len(owed)
    assert len(set(healthy.asked_for())) == len(healthy.asked_for())
    t_stall = next(t for t, m in healthy.got if isinstance(m, MsgGetData)
                   and m.invs[0].hash == owed[0])
    assert staller.asked_for(since=t_stall - 0.01) == []


@pytest.mark.asyncio
@pytest.mark.parametrize("how", ["reset", "end of stream"])
async def test_a_reset_mid_batch_reassigns_at_once_and_the_peer_is_redialled(
        how):
    """A connection lost with a block half sent — by RST, or by a stream
    that just ends — is no protocol fault: no ban, the first backoff."""
    blocks = all_blocks()
    remotes = {1: Remote(blocks, reset_at=2,
                         reset_with=RESET if how == "reset" else b""),
               2: Remote(blocks)}
    c = Counters(*COUNTERS)
    seen = len(events.tail(100000))
    ibd = IbdConfig(batch_blocks=4, tick_interval=0.05, stall_timeout=5.0)
    async with ibd_node(MemoryKV(), blocks, connect=connect_to(remotes),
                        peers=peers_of(remotes), ibd=ibd, max_peers=2) as (
            node, _):
        await all_online(node, remotes)
        t0 = time.monotonic()
        await poll_until(lambda: node.utxo.height == len(blocks),
                         what="sync past the reset")
        synced = time.monotonic() - t0
        await poll_until(lambda: remotes[1].connections == 2, timeout=5,
                         what="the reset peer's redial")
        await poll_until(lambda: c["span.peer.reconnect.count"] == 1,
                         what="the reconnect's span")
    assert synced < 3.0  # no stall timeout was waited out
    assert c["ibd.stalls"] == 0 and c["peermgr.timed_bans"] == 0
    back = [e["seconds"] for e in events.tail(100000)[seen:]
            if e["type"] == "peer.reconnect"]
    assert len(back) == 1 and 0.5 <= back[0] < 1.5  # backoff 0.5 s + handshake
    owed = remotes[1].asked_for()[1:len(remotes[1].asked_for())]
    got_whole = remotes[1].asked_for()[0]
    assert got_whole not in remotes[2].asked_for()
    assert c["ibd.blocks_rerequested"] >= 3 and set(owed) & set(
        remotes[2].asked_for())


@pytest.mark.asyncio
@pytest.mark.parametrize("fault", ["slow peer wakes up", "block sent twice"])
async def test_a_block_delivered_again_is_dropped_and_counted(fault):
    """Exactly one verdict a tx, whatever arrives twice: a frozen peer whose
    batch timed out (it is no staller: ``stall_timeout`` is far off) goes
    on after its blocks were asked of another; a peer sends two copies."""
    blocks = gen_chain(NET, 20, 2, seed=0x1BD1, cache="ibd_t_20x2.bin")
    if fault == "block sent twice":
        remotes = {1: Remote(blocks, twice=range(1, 21))}
        ibd = IbdConfig(batch_blocks=4, tick_interval=0.05)
    else:
        remotes = {1: Remote(blocks, freeze_at=2), 2: Remote(blocks)}
        ibd = IbdConfig(batch_blocks=4, tick_interval=0.05,
                        fetch_timeout=0.3, stall_timeout=30.0)
    c = Counters(*COUNTERS)
    verdicts: dict[bytes, int] = {}
    async with ibd_node(MemoryKV(), blocks, verify=True,
                        connect=connect_to(remotes), peers=peers_of(remotes),
                        ibd=ibd, max_peers=len(remotes)) as (node, bus):
        async def watch():
            while True:
                ev = await bus.receive()
                if isinstance(ev, TxVerdict):
                    verdicts[ev.txid] = verdicts.get(ev.txid, 0) + 1

        task = asyncio.ensure_future(watch())  # asyncsan: disable=raw-spawn (test observer, cancelled below)
        try:
            await all_online(node, remotes)
            if fault == "slow peer wakes up":
                await poll_until(lambda: c["ibd.blocks_rerequested"] > 0,
                                 what="the frozen peer's batch timing out")
                held = sum(b"block" in d[:16] for d in remotes[1].held)
                remotes[1].thaw()
            await poll_until(lambda: node.utxo.height == 20, timeout=60,
                             what="ibd")
            await poll_until(lambda: len(verdicts) >= 60, timeout=30,
                             what="verdicts")
            await asyncio.sleep(0.3)  # a second verdict would show now
        finally:
            task.cancel()
    assert len(verdicts) == 60 and set(verdicts.values()) == {1}
    dropped = (c["node.block_duplicate_skipped"]
               + c["node.block_replay_skipped"])
    if fault == "block sent twice":
        assert dropped == 20 and c["node.block_duplicate_skipped"] > 0
    else:
        assert c["ibd.stalls"] == 0 and dropped >= held > 0


@pytest.mark.asyncio
async def test_eight_healthy_peers_that_serve_no_blocks_are_left_alone():
    """``bch-node.relay-open``'s shape — planner and mempool on, eight
    peers with nothing to fetch — for ten stall timeouts: the planner asks
    nobody for anything, pings nobody, and nobody is disconnected."""
    remotes = {port: Remote([]) for port in range(1, 9)}
    c = Counters(*COUNTERS)
    ibd = IbdConfig(batch_blocks=24, tick_interval=0.02, stall_timeout=0.1)
    async with ibd_node(MemoryKV(), [], connect=connect_to(remotes),
                        peers=peers_of(remotes), ibd=ibd, max_peers=8,
                        mempool=MempoolConfig()) as (node, _):
        mgr = node.peer_mgr
        await all_online(node, remotes)
        online = {o.peer for o in mgr.get_peers()}
        await asyncio.sleep(10 * ibd.stall_timeout)
        assert {o.peer for o in mgr.get_peers()} == online
        assert node.ibd.stats()["stalls"] == 0
        assert node.ibd.stats()["stall_timeout"] == ibd.stall_timeout
    assert c["ibd.stalls"] == c["peermgr.disconnects"] == 0
    assert c["ibd.batch_failures"] == c["peermgr.timed_bans"] == 0
    for r in remotes.values():
        assert r.connections == 1
        assert not [m for _, m in r.got if isinstance(m, (MsgGetData, MsgPing))]


# -- the planner alone, over stubs --------------------------------------------


class _Peer:
    def __init__(self, label):
        self.label, self.sent, self.killed = label, [], None

    def send_message(self, msg):
        self.sent.append(msg)

    def kill(self, err):
        self.killed = err


class _Online:
    def __init__(self, peer):
        self.peer = peer


class _Node:
    def __init__(self, height, hash_, prev):
        self.height, self.hash = height, hash_
        self.header = type("H", (), {"prev": prev})()


class _World:
    """A chain of ``n`` headers, a watermark, a fleet: what the planner
    asks of the node."""

    def __init__(self, n, peers):
        self.nodes = {}
        prev = b"\0" * 32
        for h in range(1, n + 1):
            hh = h.to_bytes(32, "big")
            self.nodes[hh] = _Node(h, hh, prev)
            prev = hh
        self.best = self.nodes[prev]
        self.height = 0  # the UTXO watermark
        self.peers = [_Online(p) for p in peers]
        self.pressed, self.pending = False, 0
        self.net = type("N", (), {"segwit": False})()

    get_best = lambda self: self.best
    get_block = lambda self, h: self.nodes.get(h)
    get_peers = lambda self: self.peers

    def planner(self, cfg, cap=64) -> BlockFetcher:
        f = BlockFetcher(
            cfg, self.net, self, self, self, lambda: self.pressed,
            pending=lambda: self.pending, pending_cap=cap)
        _PLANNERS.append(f)
        return f


_PLANNERS: list = []  # made outside a node: nobody exits them


@pytest.fixture(autouse=True)
def close_head_waits():
    """A planner driven by hand may leave its ``ibd.head_wait`` span open;
    an open span of one test is in the next one's ``trace._open``."""
    yield
    while _PLANNERS:
        _PLANNERS.pop()._set_head_wait(False)


def _asked(peer) -> list:
    return [int.from_bytes(iv.hash, "big") for m in peer.sent
            if isinstance(m, MsgGetData) for iv in m.invs]


def _deliver(f, peer, heights) -> None:
    for h in heights:
        f.block_arrived(peer, h.to_bytes(32, "big"))


def test_a_full_window_of_parked_blocks_is_no_pressure(tmp_path):
    """The planner's lead is bounded by the node's parking: 127 verified
    blocks waiting for the watermark's successor do not defer the plan that
    would ask for it, and a lead the parking cannot hold is refused."""
    from tpunode import BCH_REGTEST, Node, NodeConfig, Publisher

    def cfg(lead):
        return NodeConfig(net=BCH_REGTEST, store=MemoryKV(),
                          pub=Publisher(name="x"), utxo=True,
                          ibd=IbdConfig(max_lead=lead))

    node = Node(cfg(Node.MAX_UTXO_PENDING))
    node._utxo_pending.update({h: None for h in range(2, 129)})
    assert not node._ibd_pressure()
    node._verify_pending = Node.MAX_VERIFY_PENDING // 2
    assert node._ibd_pressure()
    with pytest.raises(ValueError, match="max_lead"):
        cfg(Node.MAX_UTXO_PENDING + 1)


def test_blocks_on_the_wire_count_against_the_nodes_shed_bound():
    peers = [_Peer(str(i)) for i in range(8)]
    w = _World(500, peers)
    f = w.planner(IbdConfig(batch_blocks=8, max_lead=128), cap=64)
    w.pending = 20
    f._plan()
    assert sum(len(_asked(p)) for p in peers) == 40  # 20 + 40 <= 64 < 20 + 48
    _deliver(f, peers[0], range(1, 9))
    w.pending = 28  # they are in verification now
    f._plan()
    assert sum(len(_asked(p)) for p in peers) == 40
    w.pending = 0
    f._plan()
    assert sum(len(_asked(p)) for p in peers) == 40 + 32  # on the wire: 64


def test_the_peer_that_served_fastest_is_asked_first(monkeypatch):
    from tpunode import ibd as ibd_mod

    clock = [100.0]
    monkeypatch.setattr(ibd_mod.time, "monotonic", lambda: clock[0])
    slow, fast = _Peer("slow"), _Peer("fast")
    w = _World(200, [slow, fast])
    f = w.planner(IbdConfig(batch_blocks=4, max_lead=16,
                            max_inflight_per_peer=1))
    f._plan()  # neither has served yet: both are tried, in the fleet's order
    assert _asked(slow) == [1, 2, 3, 4] and _asked(fast) == [5, 6, 7, 8]
    clock[0] += 0.1
    _deliver(f, fast, range(5, 9))
    clock[0] += 0.9
    _deliver(f, slow, range(1, 5))
    w.height = 8
    slow.sent.clear(), fast.sent.clear()
    f._plan()
    assert _asked(fast) == [9, 10, 11, 12] and _asked(slow) == [13, 14, 15, 16]


def test_time_the_loop_was_held_is_nobodys_silence(monkeypatch):
    from tpunode import ibd as ibd_mod

    clock = [100.0]
    monkeypatch.setattr(ibd_mod.time, "monotonic", lambda: clock[0])
    a, b = _Peer("a"), _Peer("b")
    w = _World(64, [a, b])
    f = w.planner(IbdConfig(batch_blocks=8, max_lead=16, stall_timeout=2.0,
                            max_inflight_per_peer=1))
    f._plan()
    clock[0] += 3.0  # ... of which the loop did not run for 2.5
    f._forgive(2.5)
    f._plan()
    assert a.killed is None and b.killed is None and f.stats()["stalls"] == 0
    _deliver(f, b, range(9, 17))
    clock[0] += 1.6  # a has now been silent for 2.1 s of loop time
    f._plan()
    assert isinstance(a.killed, PeerStalling) and b.killed is None
    assert f.stats()["stalls"] == 1 and f.stats()["stall_timeout"] == 4.0
    assert _asked(b)[-8:] == list(range(1, 9))  # a's batch, whole, from b
    f.peer_gone(a)
    for lo in range(17, 17 + 8 * 12, 8):  # completed batches bring it back
        w.height = lo - 1
        f._plan()
        _deliver(f, b, range(lo, lo + 8))
    assert f.stats()["stall_timeout"] == 2.0
