"""The plain reference of the BTC cells: raw transaction bytes and prevouts
-> per-signature verdicts, in Python integers.

Independent of the program: it shares no code with ``tpunode/`` or
``native/``, and with the generator (``gen_btc.py``) only ``secp.py`` /
``wirefmt.py`` primitives and the prevout data both are given
(``prevouts_btc.py``).  The digests below are written out from the BIPs a
second time on purpose; the generator signs with its own.

**What it states** — the semantics the program states: a signature
pre-verifier, not a script interpreter.  Per input, from the prevout's
scriptPubKey, it recognises a template, computes the consensus digest and
checks each signature; an input of no known template is ``unsupported`` and
contributes no verdict (the program counts it in ``ExtractStats.unsupported``;
the benchmark's mixes must have none).

* P2TR (``OP_1 <32>``), key path: witness ``[sig]`` (+ annex), BIP341 digest
  with ``ext_flag`` 0, BIP340 verification under the output key.
* P2TR, script path: witness ``[sig, <32-byte key> OP_CHECKSIG, control]``
  (+ annex) with a well-formed control block (leaf version 0xc0, 33 + 32k
  bytes): BIP341 digest with the BIP342 extension (tapleaf hash, key version
  0, codeseparator position 0xffffffff), BIP340 verification under the
  leaf's key.  Any other tapscript: unsupported.
* P2WPKH, P2SH-P2WPKH: ``[sig, pubkey]``, BIP143 with the P2PKH script code.
* P2WSH, P2SH-P2WSH: m-of-n ``[<>, sig.., script]`` with the consensus
  CHECKMULTISIG walk, or ``[sig, <key> OP_CHECKSIG]``; BIP143 over the
  witness script.
* P2PKH: scriptSig ``<sig> <pubkey>``, the legacy digest.  Bare P2PK and
  legacy P2SH multisig likewise.

**Departures from the BIPs, the program's own and stated by it**:
1. no script is executed, so what a script would check beyond its signatures
   is not checked: that a key or script hashes to the prevout's program
   (P2PKH, P2WPKH, P2SH, P2WSH), that a control block commits the leaf to
   the output key (BIP341's taproot tweak; the generator's control blocks do
   commit), CHECKMULTISIG's NULLDUMMY, sigops, sizes, amounts' sums;
2. ECDSA signatures are parsed as lax DER and no low-S rule applies (that is
   policy; ``high_s`` reads valid); a hash type other than those BIP341
   lists makes a taproot signature invalid, as the BIP says;
3. a taproot signature of 65 bytes ending 0x00, or of any length but 64 or
   65, is invalid (BIP341), and an output key that is no point's x makes
   the spend invalid (BIP340 ``lift_x`` fails): both are verdicts, not
   ``unsupported``;
4. SIGHASH_SINGLE with no matching output: invalid under BIP341 (as the BIP
   says); under the legacy digest the well-known ``1`` digest.

``Checks`` selects the control verifiers: a reference with one check off is
the fault ``correct`` has to see.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

from chipbench import secp
from chipbench import wirefmt as w

ANYONECANPAY, NONE, SINGLE = 0x80, 2, 3
TAPROOT_HASHTYPES = (0x00, 0x01, 0x02, 0x03, 0x81, 0x82, 0x83)


@dataclass(frozen=True)
class Checks:
    scalar_range: bool = True  # ECDSA 0 < r, s < n; BIP340 r < p, s < n
    on_curve: bool = True
    equation: bool = True
    parity: bool = True  # BIP340: y(R) is even
    amounts: bool = True  # BIP341: the digest commits to every amount

    def ecdsa(self) -> secp.Checks:
        return secp.Checks(scalar_range=self.scalar_range,
                           on_curve=self.on_curve, equation=self.equation)


FULL = Checks()


def _sha(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def _tagged(tag: bytes, data: bytes) -> bytes:
    t = _sha(tag)
    return _sha(t + t + data)


def _varstr(b: bytes) -> bytes:
    return w.varint(len(b)) + b


# ---- parsing -----------------------------------------------------------------


@dataclass
class Tx:
    version: int
    ins: list  # (txid, vout, scriptSig, sequence)
    outs: list  # (value, script)
    locktime: int
    wits: list  # per input: list of stack items ([] where none)
    txid: bytes


def parse_tx(raw: bytes, off: int = 0) -> tuple:
    """One tx in either serialisation (BIP144) -> (Tx, end offset)."""
    start = off
    witness = raw[off + 4:off + 6] == b"\x00\x01"
    body = off + 6 if witness else off + 4
    n, p = w.read_varint(raw, body)
    ins = []
    for _ in range(n):
        txid, vout = raw[p:p + 32], int.from_bytes(raw[p + 32:p + 36], "little")
        ln, p = w.read_varint(raw, p + 36)
        ins.append((txid, vout, raw[p:p + ln],
                    int.from_bytes(raw[p + ln:p + ln + 4], "little")))
        p += ln + 4
    n, p = w.read_varint(raw, p)
    outs = []
    for _ in range(n):
        value = int.from_bytes(raw[p:p + 8], "little")
        ln, p = w.read_varint(raw, p + 8)
        outs.append((value, raw[p:p + ln]))
        p += ln
    body_end = p
    wits = [[] for _ in ins]
    if witness:
        for st in wits:
            n, p = w.read_varint(raw, p)
            for _ in range(n):
                ln, p = w.read_varint(raw, p)
                st.append(raw[p:p + ln])
                p += ln
    locktime = int.from_bytes(raw[p:p + 4], "little")
    stripped = raw[start:start + 4] + raw[body:body_end] + raw[p:p + 4]
    tx = Tx(int.from_bytes(raw[start:start + 4], "little"), ins, outs,
            locktime, wits, w.sha256d(stripped))
    return tx, p + 4


def stripped(raw: bytes) -> bytes:
    """``raw`` without marker, flag and witnesses: what the txid hashes."""
    tx, _ = parse_tx(raw)
    return w.ser_tx(tx.version, tx.ins, tx.outs, tx.locktime)


def _pushes(script: bytes):
    """Direct and PUSHDATA1/2 pushes of a scriptSig, or None."""
    out, off = [], 0
    while off < len(script):
        op = script[off]
        off += 1
        if op == 0:
            out.append(b"")
            continue
        if op <= 75:
            ln = op
        elif op == 0x4C and off < len(script):
            ln, off = script[off], off + 1
        elif op == 0x4D and off + 1 < len(script):
            ln, off = int.from_bytes(script[off:off + 2], "little"), off + 2
        else:
            return None
        if off + ln > len(script):
            return None
        out.append(script[off:off + ln])
        off += ln
    return out


def _multisig(script: bytes):
    """``OP_m <key>*n OP_n OP_CHECKMULTISIG`` -> (m, keys)."""
    if len(script) < 3 or script[-1] != 0xAE:
        return None
    m, n = script[0] - 0x50, script[-2] - 0x50
    if not (1 <= m <= n <= 16):
        return None
    keys, off = [], 1
    while off < len(script) - 2:
        ln = script[off]
        if ln not in (33, 65) or off + 1 + ln > len(script) - 2:
            return None
        keys.append(script[off + 1:off + 1 + ln])
        off += 1 + ln
    return (m, keys) if len(keys) == n else None


def _p2pk_key(script: bytes):
    if len(script) in (35, 67) and script[0] == len(script) - 2 and script[-1] == 0xAC:
        return script[1:-1]
    return None


# ---- the three digests -------------------------------------------------------


def legacy_digest(tx: Tx, index: int, script_code: bytes, hashtype: int) -> int:
    """The original signature hash (no FindAndDelete: the templates here
    never hold their own signature)."""
    base = hashtype & 0x1F
    if base == SINGLE and index >= len(tx.outs):
        return 1
    ins = []
    for i, (txid, vout, _s, seq) in enumerate(tx.ins):
        if hashtype & ANYONECANPAY and i != index:
            continue
        if i != index and base in (NONE, SINGLE):
            seq = 0
        ins.append((txid, vout, script_code if i == index else b"", seq))
    if base == NONE:
        outs = []
    elif base == SINGLE:
        outs = [(0xFFFFFFFFFFFFFFFF, b"")] * index + [tx.outs[index]]
    else:
        outs = tx.outs
    pre = w.ser_tx(tx.version, ins, outs, tx.locktime) + hashtype.to_bytes(4, "little")
    return int.from_bytes(w.sha256d(pre), "big")


def bip143_digest(tx: Tx, index: int, script_code: bytes, amount: int,
                  hashtype: int) -> int:
    base, zero = hashtype & 0x1F, b"\x00" * 32
    acp = bool(hashtype & ANYONECANPAY)
    txid, vout, _s, seq = tx.ins[index]
    hp = zero if acp else w.sha256d(b"".join(
        i[0] + i[1].to_bytes(4, "little") for i in tx.ins))
    hs = zero if acp or base in (NONE, SINGLE) else w.sha256d(b"".join(
        i[3].to_bytes(4, "little") for i in tx.ins))
    ser = [v.to_bytes(8, "little") + _varstr(s) for v, s in tx.outs]
    if base not in (NONE, SINGLE):
        ho = w.sha256d(b"".join(ser))
    elif base == SINGLE and index < len(ser):
        ho = w.sha256d(ser[index])
    else:
        ho = zero
    pre = (tx.version.to_bytes(4, "little") + hp + hs + txid
           + vout.to_bytes(4, "little") + _varstr(script_code)
           + amount.to_bytes(8, "little") + seq.to_bytes(4, "little") + ho
           + tx.locktime.to_bytes(4, "little") + hashtype.to_bytes(4, "little"))
    return int.from_bytes(w.sha256d(pre), "big")


def bip341_digest(tx: Tx, index: int, hashtype: int, amounts: list,
                  scripts: list, annex, leaf, checks: Checks = FULL):
    """The taproot signature message's hash, or None where BIP341 calls the
    spend invalid (hash type, SIGHASH_SINGLE without its output)."""
    if hashtype not in TAPROOT_HASHTYPES:
        return None
    base, acp = hashtype & 3, bool(hashtype & ANYONECANPAY)
    if base == SINGLE and index >= len(tx.outs):
        return None
    msg = bytes([hashtype]) + tx.version.to_bytes(4, "little")
    msg += tx.locktime.to_bytes(4, "little")
    if not acp:
        msg += _sha(b"".join(i[0] + i[1].to_bytes(4, "little") for i in tx.ins))
        if checks.amounts:
            msg += _sha(b"".join(a.to_bytes(8, "little") for a in amounts))
        msg += _sha(b"".join(_varstr(s) for s in scripts))
        msg += _sha(b"".join(i[3].to_bytes(4, "little") for i in tx.ins))
    if base not in (NONE, SINGLE):
        msg += _sha(b"".join(v.to_bytes(8, "little") + _varstr(s)
                             for v, s in tx.outs))
    msg += bytes([2 * (leaf is not None) + (annex is not None)])
    if acp:
        txid, vout, _s, seq = tx.ins[index]
        msg += txid + vout.to_bytes(4, "little")
        if checks.amounts:
            msg += amounts[index].to_bytes(8, "little")
        msg += _varstr(scripts[index]) + seq.to_bytes(4, "little")
    else:
        msg += index.to_bytes(4, "little")
    if annex is not None:
        msg += _sha(_varstr(annex))
    if base == SINGLE:
        v, s = tx.outs[index]
        msg += _sha(v.to_bytes(8, "little") + _varstr(s))
    if leaf is not None:
        msg += leaf + b"\x00" + b"\xff\xff\xff\xff"
    return _tagged(b"TapSighash", b"\x00" + msg)


# ---- signature checks --------------------------------------------------------


def lift_x(x: int):
    """BIP340: the point of even y with this x, or None."""
    if x >= secp.P:
        return None
    c = (pow(x, 3, secp.P) + 7) % secp.P
    y = pow(c, (secp.P + 1) // 4, secp.P)
    if y * y % secp.P != c:
        return None
    return x, (y if y & 1 == 0 else secp.P - y)


def bip340_verify(key32: bytes, m: bytes, sig64: bytes,
                  checks: Checks = FULL) -> bool:
    pub = lift_x(int.from_bytes(key32, "big"))
    if pub is None:
        return False
    r = int.from_bytes(sig64[:32], "big")
    s = int.from_bytes(sig64[32:], "big")
    if checks.scalar_range and not (r < secp.P and s < secp.N):
        return False
    if not checks.equation:
        return True
    e = int.from_bytes(_tagged(b"BIP0340/challenge",
                               sig64[:32] + key32 + m), "big") % secp.N
    R = secp.add(secp.mul(s, secp.G), secp.mul(secp.N - e, pub))
    if R is None:
        return False
    if checks.parity and R[1] & 1:
        return False
    return R[0] == r


def _ecdsa(blob: bytes, key: bytes, digest, checks: Checks) -> bool:
    """``blob``: DER signature + hash type; ``digest(hashtype) -> int``."""
    if len(blob) < 9:
        return False
    rs = secp.parse_der(blob[:-1])
    pub = secp.decode_pubkey(key, checks.ecdsa())
    return rs is not None and secp.ecdsa_verify(
        pub, digest(blob[-1]) % secp.N, rs[0], rs[1], checks.ecdsa())


def _single(blob: bytes, key: bytes, digest, checks: Checks):
    """A single-signature template's verdict; a blob that is no DER
    signature makes the input unsupported, as the program has it."""
    if len(blob) < 9 or secp.parse_der(blob[:-1]) is None:
        return None
    return [_ecdsa(blob, key, digest, checks)]


def _walk(sigs: list, keys: list, digest, checks: Checks) -> list:
    """The consensus CHECKMULTISIG walk: per signature, whether it matched."""
    matched = [False] * len(sigs)
    i, j = len(sigs) - 1, len(keys) - 1
    while i >= 0 and j >= i:
        if _ecdsa(sigs[i], keys[j], digest, checks):
            matched[i] = True
            i -= 1
        j -= 1
    return matched


# ---- templates ---------------------------------------------------------------


def _taproot(tx: Tx, index: int, prevouts: list, checks: Checks):
    wit = list(tx.wits[index])
    annex = None
    if len(wit) >= 2 and wit[-1][:1] == b"\x50":
        annex = wit.pop()
    key = prevouts[index][1][2:]
    leaf = None
    if len(wit) == 3:
        sig, script, control = wit
        if not (len(script) == 34 and script[0] == 0x20 and script[33] == 0xAC
                and len(control) >= 33 and (len(control) - 33) % 32 == 0
                and len(control) <= 33 + 128 * 32 and control[0] & 0xFE == 0xC0):
            return None
        key = script[1:33]
        leaf = _tagged(b"TapLeaf", bytes([control[0] & 0xFE]) + _varstr(script))
    elif len(wit) != 1:
        return None
    else:
        sig = wit[0]
    if len(sig) == 64:
        hashtype = 0
    elif len(sig) == 65 and sig[64] != 0:
        hashtype = sig[64]
    else:
        return [False]
    m = bip341_digest(tx, index, hashtype, [p[0] for p in prevouts],
                      [p[1] for p in prevouts], annex, leaf, checks)
    if m is None:
        return [False]
    return [bip340_verify(key, m, sig[:64], checks)]


def _witness_v0(tx: Tx, index: int, program: bytes, amount: int,
                checks: Checks):
    wit = tx.wits[index]

    def digest_over(code):
        return lambda ht: bip143_digest(tx, index, code, amount, ht)

    if len(program) == 20:  # P2WPKH
        if len(wit) != 2 or len(wit[1]) not in (33, 65):
            return None
        h = hashlib.new("ripemd160", _sha(wit[1])).digest()
        code = b"\x76\xa9\x14" + h + b"\x88\xac"
        return _single(wit[0], wit[1], digest_over(code), checks)
    if len(wit) == 2 and (key := _p2pk_key(wit[1])) is not None:
        return _single(wit[0], key, digest_over(wit[1]), checks)
    if len(wit) >= 3 and wit[0] == b"" and (ms := _multisig(wit[-1])) \
            and len(wit) - 2 == ms[0]:
        return _walk(wit[1:-1], ms[1], digest_over(wit[-1]), checks)
    return None


def input_verdicts(tx: Tx, index: int, prevouts: list, checks: Checks = FULL):
    """One input's per-signature verdicts, or None: unsupported."""
    amount, spk = prevouts[index]
    script_sig, wit = tx.ins[index][2], tx.wits[index]
    if tx.ins[index][0] == b"\x00" * 32:
        return []  # a coinbase signs nothing
    if len(spk) == 34 and spk[:2] == b"\x51\x20":
        return _taproot(tx, index, prevouts, checks) if not script_sig else None
    if spk[:1] == b"\x00" and len(spk) in (22, 34) and spk[1] == len(spk) - 2:
        if script_sig:
            return None
        return _witness_v0(tx, index, spk[2:], amount, checks)
    pushes = _pushes(script_sig)
    if pushes is None:
        return None

    def legacy(code):
        return lambda ht: legacy_digest(tx, index, code, ht)

    if len(spk) == 23 and spk[:2] == b"\xa9\x14" and spk[22] == 0x87:  # P2SH
        if len(pushes) == 1 and len(pushes[0]) in (22, 34) \
                and pushes[0][0] == 0 and pushes[0][1] == len(pushes[0]) - 2:
            return _witness_v0(tx, index, pushes[0][2:], amount, checks)
        if wit:
            return None
        if len(pushes) >= 3 and pushes[0] == b"" \
                and (ms := _multisig(pushes[-1])) and len(pushes) - 2 == ms[0]:
            return _walk(pushes[1:-1], ms[1], legacy(pushes[-1]), checks)
        return None
    if wit:
        return None
    if len(spk) == 25 and spk[:3] == b"\x76\xa9\x14" and spk[23:] == b"\x88\xac":
        if len(pushes) != 2 or len(pushes[1]) not in (33, 65):
            return None
        h = hashlib.new("ripemd160", _sha(pushes[1])).digest()
        code = b"\x76\xa9\x14" + h + b"\x88\xac"
        return _single(pushes[0], pushes[1], legacy(code), checks)
    if (key := _p2pk_key(spk)) is not None and len(pushes) == 1:
        return _single(pushes[0], key, legacy(spk), checks)
    return None


def tx_verdicts(raw: bytes, oracle, checks: Checks = FULL) -> tuple:
    """Per-signature verdicts of one tx, in input order; an unsupported
    input contributes none (``unsupported_inputs`` counts them)."""
    tx, _ = parse_tx(raw)
    prevouts = [oracle(i[0], i[1]) for i in tx.ins]
    out = []
    for index in range(len(tx.ins)):
        out += input_verdicts(tx, index, prevouts, checks) or []
    return tuple(out)


def unsupported_inputs(raw: bytes, oracle) -> int:
    tx, _ = parse_tx(raw)
    prevouts = [oracle(i[0], i[1]) for i in tx.ins]
    return sum(input_verdicts(tx, i, prevouts) is None
               for i in range(len(tx.ins)))


def check_job(job: dict) -> list:
    """Worker entry (``harness.reference_module``): [(txid, verdicts)] for
    raw txs under the cells' prevout rule and ``checks``."""
    from chipbench.prevouts_btc import Oracle

    oracle = Oracle()
    checks = Checks(**job.get("checks", {}))
    return [(parse_tx(raw)[0].txid, tx_verdicts(raw, oracle, checks))
            for raw in job["raw"]]
