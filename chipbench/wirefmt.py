"""The Bitcoin wire format, as far as a benchmark peer needs it.

The benchmark's own copy: the remote peers and the generator frame their
bytes here, so that a change to the program's codec cannot move the
traffic.  Only what the peers send and have to understand is covered.
"""

from __future__ import annotations

import hashlib
import struct
import time

HEADER_SIZE = 24
NODE_NETWORK = 1
INV_TX, INV_BLOCK = 1, 2
SIGHASH_ALL_FORKID = 0x41


def sha256d(b: bytes) -> bytes:
    return hashlib.sha256(hashlib.sha256(b).digest()).digest()


def varint(n: int) -> bytes:
    if n < 0xFD:
        return bytes([n])
    if n <= 0xFFFF:
        return b"\xfd" + n.to_bytes(2, "little")
    if n <= 0xFFFFFFFF:
        return b"\xfe" + n.to_bytes(4, "little")
    return b"\xff" + n.to_bytes(8, "little")


def read_varint(b: bytes, off: int) -> tuple:
    v = b[off]
    if v < 0xFD:
        return v, off + 1
    size = {0xFD: 2, 0xFE: 4, 0xFF: 8}[v]
    return int.from_bytes(b[off + 1:off + 1 + size], "little"), off + 1 + size


def push(b: bytes) -> bytes:
    """Minimal script push."""
    if len(b) <= 75:
        return bytes([len(b)]) + b
    if len(b) <= 255:
        return b"\x4c" + bytes([len(b)]) + b
    return b"\x4d" + len(b).to_bytes(2, "little") + b


def frame(magic: int, command: str, payload: bytes) -> bytes:
    return (
        magic.to_bytes(4, "big")
        + command.encode().ljust(12, b"\x00")
        + len(payload).to_bytes(4, "little")
        + sha256d(payload)[:4]
        + payload
    )


def parse_frame_header(magic: int, raw: bytes) -> tuple:
    """24 envelope bytes -> (command, payload length)."""
    if int.from_bytes(raw[:4], "big") != magic:
        raise ValueError("bad magic from the node")
    return raw[4:16].rstrip(b"\x00").decode(), int.from_bytes(raw[16:20], "little")


def _netaddr(services: int) -> bytes:
    return (services.to_bytes(8, "little") + b"\x00" * 10 + b"\xff\xff"
            + bytes([127, 0, 0, 1]) + (0).to_bytes(2, "big"))


def version_payload(nonce: int, start_height: int, agent: bytes) -> bytes:
    return (
        (70012).to_bytes(4, "little")
        + NODE_NETWORK.to_bytes(8, "little")
        + int(time.time()).to_bytes(8, "little")
        + _netaddr(0) + _netaddr(NODE_NETWORK)
        + nonce.to_bytes(8, "little")
        + varint(len(agent)) + agent
        + start_height.to_bytes(4, "little")
        + b"\x01"
    )


def parse_getheaders(payload: bytes) -> list:
    """The locator hashes of a ``getheaders``."""
    n, off = read_varint(payload, 4)
    return [payload[off + 32 * i:off + 32 * (i + 1)] for i in range(n)]


def parse_inv(payload: bytes) -> list:
    """``getdata`` / ``inv`` -> [(type, hash)]."""
    n, off = read_varint(payload, 0)
    return [
        (int.from_bytes(payload[off + 36 * i:off + 36 * i + 4], "little")
         & 0x3FFFFFFF,
         payload[off + 36 * i + 4:off + 36 * (i + 1)])
        for i in range(n)
    ]


# ---- transactions -----------------------------------------------------------


def ser_tx(version: int, ins: list, outs: list, locktime: int = 0) -> bytes:
    """``ins``: (txid, vout, scriptSig, sequence); ``outs``: (value, script)."""
    parts = [version.to_bytes(4, "little"), varint(len(ins))]
    for txid, vout, script, seq in ins:
        parts += [txid, vout.to_bytes(4, "little"), varint(len(script)),
                  script, seq.to_bytes(4, "little")]
    parts.append(varint(len(outs)))
    for value, script in outs:
        parts += [value.to_bytes(8, "little"), varint(len(script)), script]
    parts.append(locktime.to_bytes(4, "little"))
    return b"".join(parts)


def parse_tx(raw: bytes, off: int = 0) -> tuple:
    """A legacy-serialized tx -> ((version, ins, outs, locktime), end)."""
    version = int.from_bytes(raw[off:off + 4], "little")
    n, off = read_varint(raw, off + 4)
    ins = []
    for _ in range(n):
        txid, vout = raw[off:off + 32], int.from_bytes(raw[off + 32:off + 36], "little")
        ln, off = read_varint(raw, off + 36)
        script = raw[off:off + ln]
        off += ln
        ins.append((txid, vout, script, int.from_bytes(raw[off:off + 4], "little")))
        off += 4
    n, off = read_varint(raw, off)
    outs = []
    for _ in range(n):
        value = int.from_bytes(raw[off:off + 8], "little")
        ln, off = read_varint(raw, off + 8)
        outs.append((value, raw[off:off + ln]))
        off += ln
    locktime = int.from_bytes(raw[off:off + 4], "little")
    return (version, ins, outs, locktime), off + 4


def forkid_midstate(version: int, ins: list, outs: list, locktime: int):
    """The three per-tx hashes of the BIP143-style FORKID digest."""
    hp = sha256d(b"".join(i[0] + i[1].to_bytes(4, "little") for i in ins))
    hs = sha256d(b"".join(i[3].to_bytes(4, "little") for i in ins))
    ho = sha256d(b"".join(
        v.to_bytes(8, "little") + varint(len(s)) + s for v, s in outs))
    return version.to_bytes(4, "little") + hp + hs, ho + locktime.to_bytes(4, "little")


def forkid_sighash(mid, txin, script_code: bytes, amount: int,
                   hashtype: int = SIGHASH_ALL_FORKID) -> int:
    """SIGHASH_ALL|FORKID digest of one input (BCH, Aug 2017)."""
    head, tail = mid
    pre = (head + txin[0] + txin[1].to_bytes(4, "little")
           + varint(len(script_code)) + script_code
           + amount.to_bytes(8, "little") + txin[3].to_bytes(4, "little")
           + tail + hashtype.to_bytes(4, "little"))
    return int.from_bytes(sha256d(pre), "big")


# ---- blocks -----------------------------------------------------------------


def merkle_root(txids: list) -> bytes:
    level = list(txids)
    while len(level) > 1:
        if len(level) & 1:
            level.append(level[-1])
        level = [sha256d(level[i] + level[i + 1]) for i in range(0, len(level), 2)]
    return level[0]


def coinbase(height: int) -> bytes:
    sig = bytes([4]) + height.to_bytes(4, "little")
    return ser_tx(1, [(b"\x00" * 32, 0xFFFFFFFF, sig, 0xFFFFFFFF)],
                  [(50 * 100_000_000, b"\x51")])


HDR = struct.Struct("<I32s32sIII")


def mine_header(prev: bytes, merkle: bytes, timestamp: int, bits: int) -> bytes:
    """An 80-byte header whose hash meets ``bits`` (regtest: a try or two)."""
    exp, mant = bits >> 24, bits & 0x7FFFFF
    target = mant << (8 * (exp - 3))
    nonce = 0
    while True:
        hdr = HDR.pack(0x20000000, prev, merkle, timestamp, bits, nonce)
        if int.from_bytes(sha256d(hdr), "little") <= target:
            return hdr
        nonce += 1


def genesis_header(net: dict) -> bytes:
    g = net["genesis"]
    return HDR.pack(g["version"], b"\x00" * 32,
                    bytes.fromhex(g["merkle"])[::-1], g["timestamp"],
                    g["bits"], g["nonce"])
