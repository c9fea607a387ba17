"""In-memory fake peer network.

Port of the reference's test seam (/root/reference/test/Haskoin/NodeSpec.hs:
``dummyPeerConnect`` :94-133 and ``mockPeerReact`` :135-147): the node's
transport hook is replaced with an in-memory duplex pipe; a background task
speaks the real wire format — it sends ``version`` first, then decodes frames
with the same 24-byte-header algorithm as production and replies from a
scripted protocol brain (ping->pong, version->verack, getheaders->the canned
chain, getdata->matching canned blocks).
"""

from __future__ import annotations

import asyncio
import contextlib
import random
import time

from tpunode.params import NODE_NETWORK, NODE_WITNESS, Network
from tpunode.util import Reader
from tpunode.wire import (
    Block,
    HEADER_SIZE,
    InvType,
    InvVector,
    MsgBlock,
    MsgGetData,
    MsgGetHeaders,
    MsgHeaders,
    MsgInv,
    MsgNotFound,
    MsgPing,
    MsgPong,
    MsgTx,
    MsgVerAck,
    MsgVersion,
    NetworkAddress,
    decode_message,
    decode_message_header,
    encode_message,
)


class TxRelay:
    """Configurable tx-relay behavior for one fake remote (the seam the
    mempool's inv-driven fetch pipeline is tested through).

    * ``announce``: txids pushed in an ``inv`` right after the handshake
      (the remote's reaction to the node's ``version``).
    * ``mode``:
        - ``"serve"``    — answer tx ``getdata`` with the matching ``tx``
          messages (unknown txids get a ``notfound``);
        - ``"notfound"`` — answer every tx ``getdata`` with ``notfound``
          (the retry-from-another-announcer path);
        - ``"stall"``    — never answer tx ``getdata`` (the trailing-ping
          sentinel of ``peer.get_data`` then bounds the node's wait).
    * ``push``: txs sent unsolicited as ``tx`` messages right after the
      handshake (the duplicate-push dedup path).
    """

    def __init__(self, txs=(), announce: bool = True, mode: str = "serve",
                 push=()):
        if mode not in ("serve", "notfound", "stall"):
            raise ValueError(f"unknown TxRelay mode: {mode!r}")
        self.txs = list(txs)
        self.announce = announce
        self.mode = mode
        self.push = list(push)


class QueueConnection:
    """One side of an in-memory duplex byte pipe."""

    def __init__(self, inbound: asyncio.Queue, outbound: asyncio.Queue):
        self._in = inbound
        self._out = outbound

    async def read_chunk(self) -> bytes:
        return await self._in.get()

    async def write(self, data: bytes) -> None:
        self._out.put_nowait(bytes(data))


class _QueueReader:
    def __init__(self, q: asyncio.Queue):
        self._q = q
        self._buf = bytearray()

    async def read_exact(self, n: int) -> bytes:
        while len(self._buf) < n:
            chunk = await self._q.get()
            if not chunk:
                raise EOFError
            self._buf.extend(chunk)
        out = bytes(self._buf[:n])
        del self._buf[:n]
        return out


def mock_peer_react(
    net: Network, blocks: list[Block], msg, getdata_blocks: list[Block] = (),
    relay: "TxRelay | None" = None, serve_blocks: bool = True,
) -> list:
    """Scripted protocol brain (reference ``mockPeerReact`` NodeSpec.hs:135-147).

    ``getdata_blocks`` are served on ``getdata`` only — never announced in
    ``headers`` — so a test can deliver a block with arbitrary txs (e.g.
    signed fixtures for the verify pipeline) without breaking the canned
    header chain's validation.  ``relay`` adds tx-relay behavior (inv
    announcements, tx serving/notfound/stall, unsolicited pushes) — see
    :class:`TxRelay`."""
    if isinstance(msg, MsgPing):
        return [MsgPong(msg.nonce)]
    if isinstance(msg, MsgVersion):
        out = [MsgVerAck()]
        if relay is not None:
            if relay.announce and relay.txs:
                out.append(
                    MsgInv(
                        tuple(
                            InvVector(InvType.TX, t.txid) for t in relay.txs
                        )
                    )
                )
            out.extend(MsgTx(t) for t in relay.push)
        return out
    if isinstance(msg, MsgGetHeaders):
        return [MsgHeaders(tuple((b.header, len(b.txs)) for b in blocks))]
    if isinstance(msg, MsgGetData):
        out = []
        by_hash = {b.header.hash: b for b in [*blocks, *getdata_blocks]}
        by_txid = (
            {t.txid: t for t in relay.txs} if relay is not None else {}
        )
        missing = []
        for iv in msg.invs:
            if iv.type in (InvType.BLOCK, InvType.WITNESS_BLOCK):
                if not serve_blocks:
                    continue  # block-stalling remote (IBD retry tests):
                    # headers flow, block getdata is never answered
                b = by_hash.get(iv.hash)
                if b is not None:
                    out.append(MsgBlock(b))
            elif iv.type in (InvType.TX, InvType.WITNESS_TX):
                if relay is None or relay.mode == "stall":
                    continue  # never answered; the ping sentinel bounds it
                t = by_txid.get(iv.hash)
                if relay.mode == "serve" and t is not None:
                    out.append(MsgTx(t))
                else:  # notfound mode, or a txid we don't have
                    missing.append(iv)
        if missing:
            out.append(MsgNotFound(tuple(missing)))
        return out
    return []


async def _fake_remote(
    net: Network,
    blocks: list[Block],
    to_node: asyncio.Queue,
    from_node: asyncio.Queue,
    send_version_first: bool = True,
    getdata_blocks: list[Block] = (),
    relay: "TxRelay | None" = None,
    serve_blocks: bool = True,
    services: "int | None" = None,
) -> None:
    """The remote endpoint: speaks real wire bytes over the pipe."""
    if send_version_first:
        if services is None:
            # a segwit network's node refuses a peer without the witness bit
            services = NODE_NETWORK | (NODE_WITNESS if net.segwit else 0)
        local = NetworkAddress.from_host_port("::1", 0, services=services)
        remote = NetworkAddress.from_host_port("::1", 0)
        ver = MsgVersion(
            version=70012,
            services=services,
            timestamp=int(time.time()),
            addr_recv=remote,
            addr_from=local,
            nonce=random.getrandbits(64),
            user_agent=b"/fakenet:0/",
            start_height=len(blocks),
            relay=True,
        )
        to_node.put_nowait(encode_message(net, ver))
    reader = _QueueReader(from_node)
    try:
        while True:
            raw_header = await reader.read_exact(HEADER_SIZE)
            header = decode_message_header(net, raw_header)
            payload = await reader.read_exact(header.length) if header.length else b""
            msg = decode_message(net, header, payload)
            for reply in mock_peer_react(
                net, blocks, msg, getdata_blocks, relay, serve_blocks
            ):
                to_node.put_nowait(encode_message(net, reply))
    except EOFError:
        pass


def dummy_peer_connect(
    net: Network,
    blocks: list[Block],
    send_version_first: bool = True,
    getdata_blocks: list[Block] = (),
    relay: "TxRelay | None" = None,
    serve_blocks: bool = True,
    services: "int | None" = None,
):
    """Transport factory injected as ``NodeConfig.connect``
    (reference ``dummyPeerConnect`` NodeSpec.hs:94-133).  ``relay`` gives
    the remote tx-relay behavior (inv announcements + tx serving); tests
    with several peers pass a distinct relay per dialed address by
    dispatching on the ``connect`` hook's SockAddr."""

    @contextlib.asynccontextmanager
    async def factory():
        to_node: asyncio.Queue = asyncio.Queue()
        from_node: asyncio.Queue = asyncio.Queue()
        task = asyncio.get_running_loop().create_task(
            _fake_remote(
                net, blocks, to_node, from_node, send_version_first,
                getdata_blocks, relay, serve_blocks, services,
            )
        )
        try:
            yield QueueConnection(to_node, from_node)
        finally:
            task.cancel()
            with contextlib.suppress(asyncio.CancelledError, Exception):
                await task

    return factory


async def poll_until(predicate, timeout: float = 10.0, what: str = "condition"):
    """Await a predicate with a deadline (shared fakenet test helper —
    used by the telemetry and asyncsan integration suites)."""

    async def loop():
        while not predicate():
            await asyncio.sleep(0.01)

    try:
        await asyncio.wait_for(loop(), timeout=timeout)
    except asyncio.TimeoutError:
        raise AssertionError(f"timed out waiting for {what}")


def silent_peer_connect():
    """A transport whose remote never says anything (for timeout tests)."""

    @contextlib.asynccontextmanager
    async def factory():
        to_node: asyncio.Queue = asyncio.Queue()
        from_node: asyncio.Queue = asyncio.Queue()
        yield QueueConnection(to_node, from_node)

    return factory
