"""Remote peers: wire-speaking servers on 127.0.0.1, sending pre-encoded
frames.

They run on the harness's event loop, in the node's process: a peer does
nothing but copy ready bytes to a loopback socket and answer ``ping``,
``getheaders`` and ``getdata``, so its share of the process's CPU is small
against the node's.  Closed loops need the verdicts, which arrive in this
process.  Loopback has no delay and no loss: latency here is the node's
own time, never a network's.
"""

from __future__ import annotations

import asyncio
import contextlib
import random
import time

from chipbench import wirefmt as w

HEADERS_PER_MSG = 2000


class Remote:
    """One listening peer.  ``headers``: the 80-byte headers of the chain
    it offers, in order after the genesis; ``blocks``: hash -> ``block``
    frame, served on ``getdata``.  ``on_ready(writer)`` is called once the
    node has acknowledged our version: the pump of a pushing peer."""

    def __init__(self, net: dict, headers=(), blocks=None, on_ready=None,
                 agent: bytes = b"/chipbench/"):
        self.magic = int(net["magic"], 16)
        self.headers = list(headers)
        self.hashes = [w.sha256d(h) for h in self.headers]
        self.index = {h: i for i, h in enumerate(self.hashes)}
        self.blocks = blocks or {}
        self.on_ready = on_ready
        self.agent = agent
        self.server = None
        self.writers: list = []
        self.tasks: list = []
        self.port = 0
        self.served: list = []  # block hashes sent, in order

    def offer(self, headers: list, hashes: list, blocks: dict) -> None:
        """The chain this peer offers from now on."""
        self.headers, self.hashes, self.blocks = headers, hashes, blocks
        self.index = {h: i for i, h in enumerate(hashes)}

    async def start(self) -> int:
        self.server = await asyncio.start_server(self._handle, "127.0.0.1", 0)
        self.port = self.server.sockets[0].getsockname()[1]
        return self.port

    async def close(self) -> None:
        for t in self.tasks:
            t.cancel()
        for t in self.tasks:
            with contextlib.suppress(asyncio.CancelledError, ConnectionError):
                await t
        for wr in self.writers:  # before wait_closed(): it waits for handlers
            wr.close()
        if self.server is not None:
            self.server.close()
            await self.server.wait_closed()

    def _headers_reply(self, locator: list) -> bytes:
        start = 0
        for h in locator:  # the first locator hash we know
            if h in self.index:
                start = self.index[h] + 1
                break
        chunk = self.headers[start:start + HEADERS_PER_MSG]
        return w.frame(self.magic, "headers",
                       w.varint(len(chunk)) + b"".join(h + b"\x00" for h in chunk))

    async def _handle(self, reader, writer) -> None:
        self.writers.append(writer)
        writer.write(w.frame(self.magic, "version", w.version_payload(
            random.getrandbits(64), len(self.headers), self.agent)))
        try:
            while True:
                cmd, length = w.parse_frame_header(
                    self.magic, await reader.readexactly(w.HEADER_SIZE))
                payload = await reader.readexactly(length) if length else b""
                if cmd == "ping":
                    writer.write(w.frame(self.magic, "pong", payload))
                elif cmd == "version":
                    writer.write(w.frame(self.magic, "verack", b""))
                elif cmd == "verack":
                    if self.on_ready is not None:
                        self.tasks.append(
                            asyncio.ensure_future(self.on_ready(writer)))
                elif cmd == "getheaders":
                    writer.write(self._headers_reply(w.parse_getheaders(payload)))
                elif cmd == "getdata":
                    for typ, h in w.parse_inv(payload):
                        frame = self.blocks.get(h) if typ == w.INV_BLOCK else None
                        if frame is not None:
                            writer.write(frame)
                            self.served.append(h)
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            writer.close()


class ClosedLoop:
    """At most ``outstanding`` frames sent and not yet answered.  ``sent``
    keeps the moment each frame's last byte was handed to the socket; a
    frame is in it from the moment its first byte is, so that a loop
    stopped in the middle of a write still knows what it offered."""

    def __init__(self, frames: list, keys: list, outstanding: int):
        self.frames, self.keys = frames, keys
        self.slots = asyncio.Semaphore(outstanding)
        self.sent: dict = {}
        self.next = 0
        self.stop = False

    def answered(self) -> None:
        self.slots.release()

    async def pump(self, writer) -> None:
        while not self.stop and self.next < len(self.frames):
            await self.slots.acquire()
            if self.stop:
                return
            i, self.next = self.next, self.next + 1
            self.sent[self.keys[i]] = time.monotonic()
            writer.write(self.frames[i])
            # a small frame leaves with the write; a 32 MB one when the
            # socket has taken it all
            if writer.transport.get_write_buffer_size() > 65536:
                await writer.drain()
            self.sent[self.keys[i]] = time.monotonic()
