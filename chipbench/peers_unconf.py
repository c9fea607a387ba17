"""A relaying peer that holds what it relays: ``peers.Remote`` that answers
a ``getdata`` for a transaction with the transaction.

A node that is handed a child before its parent asks the peer that sent
the child for the parent (the mempool's want-list): a real peer that relays
a child holds its ancestors, so this one serves any transaction of the
traffic it is asked for, whichever peer is due to push it.  ``serve(txid)
-> tx frame or None`` is the driver's, which keeps count of what was served
and does not push it again.  The replies to one ``getdata`` leave in one
write, in the order asked, so nothing this peer pushes stands between them.
"""

from __future__ import annotations

import asyncio
import random

from chipbench import wirefmt as w
from chipbench.peers import Remote


class HoldingRemote(Remote):
    def __init__(self, net: dict, serve, **kw):
        super().__init__(net, **kw)
        self.serve = serve

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
                    out = []
                    for typ, h in w.parse_inv(payload):
                        if typ != w.INV_TX:
                            continue
                        frame = self.serve(h)
                        if frame is not None:
                            out.append(frame)
                    if out:
                        writer.write(b"".join(out))
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            writer.close()
