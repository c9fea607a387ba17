"""A serving peer of a segwit network: ``peers.Remote`` that advertises
``NODE_WITNESS`` beside ``NODE_NETWORK`` (the node refuses a peer without
it: ``PeerNoSegWit``) and keeps count of what it was asked for.

``wirefmt.version_payload`` states ``NODE_NETWORK`` alone, and the BCH
cells' peers are not to change, so the handshake is written out here."""

from __future__ import annotations

import asyncio
import random
import time

from chipbench import wirefmt as w
from chipbench.peers import Remote

NODE_WITNESS = 1 << 3
MSG_WITNESS_FLAG = 1 << 30


def version_payload(services: int, nonce: int, start_height: int,
                    agent: bytes) -> bytes:
    def netaddr(srv: int) -> bytes:
        return (srv.to_bytes(8, "little") + b"\x00" * 10 + b"\xff\xff"
                + bytes([127, 0, 0, 1]) + (0).to_bytes(2, "big"))

    return ((70016).to_bytes(4, "little") + services.to_bytes(8, "little")
            + int(time.time()).to_bytes(8, "little")
            + netaddr(0) + netaddr(services) + nonce.to_bytes(8, "little")
            + w.varint(len(agent)) + agent
            + start_height.to_bytes(4, "little") + b"\x01")


class WitnessRemote(Remote):
    """``blocks`` holds each block in its witness serialisation (BIP144),
    which is what a ``getdata`` for ``MSG_WITNESS_BLOCK`` is owed.  A
    request without the witness flag is owed the stripped block, which this
    peer does not keep: it is counted (``plain_requests``) and not served,
    and the cell's comparison holds the count to 0."""

    services = w.NODE_NETWORK | NODE_WITNESS

    def __init__(self, net: dict, **kw):
        super().__init__(net, **kw)
        self.plain_requests = 0

    async def _handle(self, reader, writer) -> None:
        self.writers.append(writer)
        writer.write(w.frame(self.magic, "version", version_payload(
            self.services, random.getrandbits(64), len(self.headers),
            self.agent)))
        try:
            while True:
                cmd, length = w.parse_frame_header(
                    self.magic, await reader.readexactly(w.HEADER_SIZE))
                payload = await reader.readexactly(length) if length else b""
                if cmd == "ping":
                    writer.write(w.frame(self.magic, "pong", payload))
                elif cmd == "version":
                    writer.write(w.frame(self.magic, "verack", b""))
                elif cmd == "getheaders":
                    writer.write(self._headers_reply(w.parse_getheaders(payload)))
                elif cmd == "getdata":
                    n, off = w.read_varint(payload, 0)
                    for i in range(n):
                        typ = int.from_bytes(
                            payload[off + 36 * i:off + 36 * i + 4], "little")
                        h = payload[off + 36 * i + 4:off + 36 * (i + 1)]
                        if typ & ~MSG_WITNESS_FLAG != w.INV_BLOCK:
                            continue
                        if not typ & MSG_WITNESS_FLAG:
                            self.plain_requests += 1
                            continue
                        frame = self.blocks.get(h)
                        if frame is not None:
                            writer.write(frame)
                            self.served.append(h)
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            writer.close()
