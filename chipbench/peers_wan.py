"""Remote peers behind links: ``peers.Remote`` with a round-trip time, an
uplink and at most one fault each.

The link model is delay and rate, nothing else (no loss, no congestion
window, no jitter):

* a reply's first byte leaves one round-trip time after its request's
  last byte arrived (the greeting: one after the connection was accepted);
* a link carries one byte stream: replies leave in the order their
  requests came, ``pong`` and ``headers`` behind the blocks before them,
  at the uplink's rate, in pieces of ``PIECE`` bytes.  A piece is handed
  to the socket when its LAST byte would have left, and the next piece
  starts from the moment this one really went: a link that ran late does
  not catch up in a burst;
* a fault cuts a ``block`` frame in the middle — half of the piece that is
  due goes out — and then either nothing leaves on that connection again
  (``stall``: the socket stays open and the peer keeps reading, but what
  it would answer, its pongs too, lies behind a half-sent frame) or the
  socket is aborted (``reset``: the peer listens on and takes a new
  connection at once).  A staller treats every later connection alike.

A peer keeps the chain it was given to itself until :meth:`release`: the
node may dial all of them while it warms up, and learns of the chain from
every one at the same moment, by a ``headers`` announcement.

The peers run on the node's event loop: ``late_ms`` says how far each
piece ran behind its moment.  Everything a link did is in its ``log``, for
``reference_wan.py``, which trusts none of the arithmetic here.
"""

from __future__ import annotations

import asyncio
import random
import socket
import struct
import time

from chipbench import wirefmt as w
from chipbench.peers import Remote

PIECE = 16384


class WanRemote(Remote):
    """``link``: ``{"rtt_ms", "uplink_mbit_s", "fault"?: {"kind": "stall" |
    "reset", "at_s"}}``; ``at_s`` counts from :meth:`arm`."""

    def __init__(self, net: dict, link: dict, piece: int = PIECE):
        super().__init__(net)
        self.rtt = link["rtt_ms"] / 1e3
        self.rate = link["uplink_mbit_s"] * 1e6 / 8  # bytes a second
        self.piece = piece
        self.fault = link.get("fault")
        self.fault_at = None  # monotonic; None until armed, or once a reset fired
        self.connections = 0
        self.owed: dict = {}  # connection -> block replies queued or leaving
        self.ready: dict = {}  # connection -> its reply(), once handshaken
        self.held = None  # the chain, until release()
        # ("accept", t, conn) | ("request", t, conn, command, block hashes)
        # | ("piece", t, conn, kind, key, bytes, bytes of the frame so far,
        #    the frame's length)
        self.log: list = []
        self.late_ms: list = []  # (t, ms) of every piece

    @property
    def busy(self) -> bool:
        """Has a live connection blocks still to send?  (A connection
        that was reset or has stalled owes nothing any more.)"""
        return any(self.owed.values())

    def offer(self, headers: list, hashes: list, blocks: dict) -> None:
        self.held = (headers, hashes, blocks)

    def release(self) -> None:
        super().offer(*self.held)
        for reply in self.ready.values():
            reply(time.monotonic(),
                  [("headers", None, self._headers_reply([]))])

    def arm(self, t0: float) -> None:
        if self.fault is not None:
            self.fault_at = t0 + self.fault["at_s"]

    async def _handle(self, reader, writer) -> None:
        self.connections += 1
        conn = self.connections
        self.writers.append(writer)
        queue: asyncio.Queue = asyncio.Queue()
        sender = asyncio.ensure_future(self._send(conn, writer, queue))
        self.tasks.append(sender)

        self.owed[conn] = 0

        def reply(t: float, frames: list) -> None:
            queue.put_nowait((t, frames))

        t = time.monotonic()
        self.log.append(("accept", t, conn))
        reply(t, [("version", None, w.frame(self.magic, "version", w.version_payload(
            random.getrandbits(64), len(self.headers), self.agent)))])
        try:
            while True:
                cmd, length = w.parse_frame_header(
                    self.magic, await reader.readexactly(w.HEADER_SIZE))
                payload = await reader.readexactly(length) if length else b""
                t = time.monotonic()
                if cmd == "ping":
                    self.log.append(("request", t, conn, cmd, ()))
                    reply(t, [("pong", None, w.frame(self.magic, "pong", payload))])
                elif cmd == "version":
                    reply(t, [("verack", None, w.frame(self.magic, "verack", b""))])
                elif cmd == "verack":
                    self.ready[conn] = reply
                elif cmd == "getheaders":
                    reply(t, [("headers", None, self._headers_reply(
                        w.parse_getheaders(payload)))])
                elif cmd == "getdata":
                    have = [h for typ, h in w.parse_inv(payload)
                            if typ == w.INV_BLOCK and h in self.blocks]
                    self.log.append(("request", t, conn, cmd, tuple(have)))
                    if have:
                        if conn in self.owed:  # not once it has stalled
                            self.owed[conn] += 1
                        reply(t, [("block", h, self.blocks[h]) for h in have])
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            self.owed.pop(conn, None)
            self.ready.pop(conn, None)
            sender.cancel()
            writer.close()

    def _cut_now(self, kind: str, now: float) -> bool:
        return (kind == "block" and self.fault_at is not None
                and now >= self.fault_at)

    async def _send(self, conn: int, writer, queue: asyncio.Queue) -> None:
        free = 0.0  # when the piece before went
        while True:
            t_req, frames = await queue.get()
            for kind, key, data in frames:
                off = 0
                while off < len(data):
                    n = min(self.piece, len(data) - off)
                    due = max(t_req + self.rtt, free) + n / self.rate
                    await asyncio.sleep(due - time.monotonic())
                    now = time.monotonic()
                    cut = self._cut_now(kind, now)
                    if cut:
                        n = max(1, n // 2)  # the frame stays half sent
                    writer.write(data[off:off + n])
                    off += n
                    free = now
                    self.late_ms.append((now, (now - due) * 1e3))
                    self.log.append(("piece", now, conn, kind, key, n, off,
                                     len(data)))
                    if cut:
                        await self._silence(conn, writer)
                if kind == "block":
                    self.served.append(key)
            if kind == "block" and conn in self.owed:
                self.owed[conn] -= 1
            if writer.transport.get_write_buffer_size() > 1 << 20:
                await writer.drain()

    async def _silence(self, conn: int, writer) -> None:
        """After the half piece: nothing more, or an abort.  Does not
        return (the connection's end cancels it)."""
        self.owed.pop(conn, None)
        if self.fault["kind"] == "reset":
            self.fault_at = None  # once
            # linger 0: the close sends RST, as the table says, not FIN
            # (which a program may read as a protocol fault: the parent's
            # peer.py banned the address for it)
            writer.get_extra_info("socket").setsockopt(
                socket.SOL_SOCKET, socket.SO_LINGER, struct.pack("ii", 1, 0))
            writer.transport.abort()
        await asyncio.Event().wait()
