"""The plain reference: raw transaction bytes -> per-signature verdicts.

Independent of the program: it parses the bytes with ``wirefmt``, takes
prevout data from the same oracle the node is given, recognises the four
templates the generator emits, and verifies with ``secp``.  A template it
does not know yields ``None`` (the benchmark's mixes have none).

``checks`` selects the control verifiers (see ``secp.Checks``).
"""

from __future__ import annotations

from chipbench import secp
from chipbench import wirefmt as w
from chipbench.gen import p2pkh_code


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
        elif op == 0x4C:
            ln, off = script[off], off + 1
        elif op == 0x4D:
            ln, off = int.from_bytes(script[off:off + 2], "little"), off + 2
        else:
            return None
        out.append(script[off:off + ln])
        off += ln
    return out


def _multisig(redeem: bytes):
    """``OP_m <33-byte key>*n OP_n OP_CHECKMULTISIG`` -> (m, keys)."""
    if len(redeem) < 3 or redeem[-1] != 0xAE:
        return None
    m, n = redeem[0] - 0x50, redeem[-2] - 0x50
    if not (1 <= m <= n <= 16) or len(redeem) != 3 + 34 * n:
        return None
    return m, [redeem[2 + 34 * i:35 + 34 * i] for i in range(n)]


def _ecdsa(blob: bytes, pub, z: int, checks) -> bool:
    rs = secp.parse_der(blob[:-1])
    return rs is not None and secp.ecdsa_verify(pub, z, rs[0], rs[1], checks)


def tx_verdicts(raw: bytes, oracle, checks=secp.FULL):
    """Per-signature verdicts of one tx, in input order."""
    (version, ins, outs, locktime), _ = w.parse_tx(raw)
    mid = w.forkid_midstate(version, ins, outs, locktime)
    verdicts = []
    for txin in ins:
        amount, pscript = oracle(txin[0], txin[1])
        pushes = _pushes(txin[2])
        if pushes is None:
            return None
        if (len(pushes) == 1 and len(pscript) == 35 and pscript[0] == 0x21
                and pscript[-1] == 0xAC):  # bare P2PK
            pub = secp.decode_pubkey(pscript[1:34], checks)
            z = w.forkid_sighash(mid, txin, pscript, amount, pushes[0][-1])
            verdicts.append(_ecdsa(pushes[0], pub, z, checks))
        elif len(pushes) == 2 and len(pushes[1]) in (33, 65):  # P2PKH
            sig, blob = pushes
            pub = secp.decode_pubkey(blob, checks)
            z = w.forkid_sighash(mid, txin, p2pkh_code(blob), amount, sig[-1])
            if len(sig) == 65:  # BCH: 64 bytes + hashtype is Schnorr
                r = int.from_bytes(sig[:32], "big")
                s = int.from_bytes(sig[32:64], "big")
                verdicts.append(secp.schnorr_verify(pub, z, r, s, checks))
            else:
                verdicts.append(_ecdsa(sig, pub, z, checks))
        elif (len(pushes) >= 3 and pushes[0] == b""
              and (ms := _multisig(pushes[-1])) is not None
              and len(pushes) - 2 == ms[0]):  # P2SH m-of-n
            m, keys = ms
            sigs = pushes[1:-1]
            pubs = [secp.decode_pubkey(k, checks) for k in keys]
            matched = [False] * m
            i, j = m - 1, len(keys) - 1
            while i >= 0 and j >= i:  # the consensus CHECKMULTISIG walk
                z = w.forkid_sighash(mid, txin, pushes[-1], amount,
                                     sigs[i][-1])
                if _ecdsa(sigs[i], pubs[j], z, checks):
                    matched[i] = True
                    i -= 1
                j -= 1
            verdicts.extend(matched)
        else:
            return None
    return tuple(verdicts)


def check_job(job: dict) -> list:
    """Worker entry: [(txid, verdicts)] for raw txs under the given oracle
    table and checks."""
    from chipbench.gen import Oracle

    oracle = Oracle()
    oracle.p2pk = job["p2pk"]
    checks = secp.Checks(**job.get("checks", {}))
    return [(w.sha256d(raw), tx_verdicts(raw, oracle, checks))
            for raw in job["raw"]]
