"""The BTC transaction generator: segwit v0 and v1 (taproot) spends beside
legacy ones, stratified, seeded, fast.

As ``gen.py`` does for the BCH mixes, this module turns a traffic file's
``mix`` section into signed transactions whose per-signature verdicts are
known by construction: tx ``t`` takes ``pattern[t % len(pattern)]``, and of
every ``adversarial_every`` consecutive txs exactly one carries the next
kind of the ``adversarial`` list.  Every seed yields the same counts; the
seed moves keys, nonces, outpoints, amounts and which tx of a group is the
adversarial one.  Worker processes run ``blocks_job`` / ``gen_job`` and
import neither jax nor tpunode.

Input kinds, every digest the consensus one:

``p2tr``          key path: witness ``[sig64]``, SIGHASH_DEFAULT, BIP341
``p2tr_script``   script path: ``[sig65 (SIGHASH_ALL), <key> OP_CHECKSIG,
                  control]``, the control block the internal key alone (a
                  one-leaf tree; the output key is the real tweak
                  ``P + H_TapTweak(P || leaf)G``), BIP341 with the BIP342
                  extension
``p2wpkh``        ``[sig, pubkey]``, ECDSA, SIGHASH_ALL, BIP143
``p2sh_p2wpkh``   the same behind a 23-byte scriptSig
``p2wsh_msig``    2-of-3 ``[<>, sig, sig, script]``, two ECDSA signatures,
                  four device candidates, the signing pair rotating with the
                  key; BIP143 over the witness script
``p2pkh``         legacy scriptSig ``<sig> <pubkey>``, the legacy digest

ECDSA signatures are low-S, as every wallet's since 2015; ``high_s`` is the
adversarial twin.  Each tx has two inputs and two outputs (P2TR, P2WPKH)
that pay the inputs' sum less a fee.  Outpoints are made from the outputs
they stand for (``prevouts_btc``), so the prevout oracle needs no table.
"""

from __future__ import annotations

import array
import collections
import hashlib
import random

from chipbench import secp
from chipbench import wirefmt as w
from chipbench.prevouts_btc import FORMS, synth_amount, synth_script

SIGHASH_ALL = 1
MAX_BLOCK_WEIGHT = 4_000_000
HALF_N = secp.N // 2

# kind -> (prevout form, signatures, device items, algorithm, the widest the
# input can weigh: 4 x (outpoint 36 + scriptSig length 1 + scriptSig +
# sequence 4) + its witness stack, a byte a length).  A signature is at its
# widest: DER of a 33-byte r and a 32-byte (low) s, 71 bytes + hash type.
KINDS = {
    "p2tr": ("p2tr", 1, 1, "bip340", 164 + 1 + 65),
    "p2tr_script": ("p2tr", 1, 1, "bip340", 164 + 1 + 66 + 35 + 34),
    "p2wpkh": ("p2wpkh", 1, 1, "ecdsa", 164 + 1 + 73 + 34),
    "p2sh_p2wpkh": ("p2sh", 1, 1, "ecdsa", 164 + 4 * 23 + 1 + 73 + 34),
    "p2wsh_msig": ("p2wsh", 2, 4, "ecdsa", 164 + 1 + 1 + 73 + 73 + 106),
    "p2pkh": ("p2pkh", 1, 1, "ecdsa", 164 + 4 * (73 + 34) + 1),
}
# version, locktime, two counts: 10 bytes x 4; marker and flag: 2; outputs
# P2TR 43 bytes and P2WPKH 31 bytes x 4
TX_OVERHEAD = 42 + 4 * (43 + 31)

ECDSA_KINDS = ("p2wpkh", "p2sh_p2wpkh", "p2wsh_msig", "p2pkh")
ECDSA_SINGLE = ("p2wpkh", "p2sh_p2wpkh", "p2pkh")
BIP340_KINDS = ("p2tr", "p2tr_script")
# adversarial kind -> (input kinds it applies to, verdict of the corrupted
# signature).  ``high_s`` is the valid twin (r, n - s): this node reports
# verdicts and applies no low-S policy.
ADVERSARIAL = {
    "s_flip": (ECDSA_KINDS, False),
    "high_s": (ECDSA_SINGLE, True),
    "r_plus_n": (ECDSA_SINGLE, False),
    "bip340_s_flip": (BIP340_KINDS, False),
    # signed with the nonce point of odd y: x(R) = r, the parity fails
    "bip340_odd_r": (BIP340_KINDS, False),
    # the output key is no point's x
    "xonly_off_curve": (("p2tr",), False),
    # 65 bytes ending 0x00: BIP341 wants SIGHASH_DEFAULT left off
    "p2tr_sig65_type0": (("p2tr",), False),
    # signed over this prevout's amount plus one satoshi
    "p2tr_amount_off_by_one": (("p2tr",), False),
}


def sha256(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def hash160(b: bytes) -> bytes:
    return hashlib.new("ripemd160", sha256(b)).digest()


_TAGS: dict = {}


def tagged_hash(tag: str, data: bytes) -> bytes:
    """BIP340: SHA256(SHA256(tag) || SHA256(tag) || data)."""
    h = _TAGS.get(tag)
    if h is None:
        t = sha256(tag.encode())
        h = _TAGS[tag] = hashlib.sha256(t + t)
    h = h.copy()
    h.update(data)
    return h.digest()


# ---- serialisation and weight ------------------------------------------------


def ser_witness_tx(version: int, ins: list, outs: list, wits: list,
                   locktime: int = 0) -> bytes:
    """BIP144: marker and flag after the version, one stack an input before
    the locktime.  ``wits``: per input a list of stack items."""
    base = w.ser_tx(version, ins, outs, locktime)
    stacks = b"".join(
        w.varint(len(st)) + b"".join(w.varint(len(i)) + i for i in st)
        for st in wits)
    return base[:4] + b"\x00\x01" + base[4:-4] + stacks + base[-4:]


def tx_weight(stripped: bytes, full: bytes) -> int:
    return 3 * len(stripped) + len(full)


# ---- the three digests (the signing side's own copy) ------------------------


def legacy_sighash(version: int, ins: list, outs: list, locktime: int,
                   index: int, script_code: bytes) -> int:
    """SIGHASH_ALL over the re-serialised tx: every scriptSig empty but this
    input's, which is the script code."""
    blank = [(t, v, script_code if i == index else b"", seq)
             for i, (t, v, _s, seq) in enumerate(ins)]
    pre = w.ser_tx(version, blank, outs, locktime) + SIGHASH_ALL.to_bytes(4, "little")
    return int.from_bytes(w.sha256d(pre), "big")


def bip341_midstate(version: int, ins: list, outs: list, locktime: int,
                    amounts: list, scripts: list) -> bytes:
    """version .. sha_outputs of the BIP341 message for hash types DEFAULT
    and ALL."""
    return (
        version.to_bytes(4, "little") + locktime.to_bytes(4, "little")
        + sha256(b"".join(i[0] + i[1].to_bytes(4, "little") for i in ins))
        + sha256(b"".join(a.to_bytes(8, "little") for a in amounts))
        + sha256(b"".join(w.varint(len(s)) + s for s in scripts))
        + sha256(b"".join(i[3].to_bytes(4, "little") for i in ins))
        + sha256(b"".join(v.to_bytes(8, "little") + w.varint(len(s)) + s
                          for v, s in outs)))


def bip341_sighash(mid: bytes, index: int, hashtype: int,
                   leaf: bytes | None = None) -> bytes:
    msg = (b"\x00" + bytes([hashtype]) + mid
           + (b"\x02" if leaf is not None else b"\x00")
           + index.to_bytes(4, "little"))
    if leaf is not None:
        msg += leaf + b"\x00\xff\xff\xff\xff"
    return tagged_hash("TapSighash", msg)


def tapleaf_hash(script: bytes) -> bytes:
    return tagged_hash("TapLeaf", b"\xc0" + w.varint(len(script)) + script)


# ---- keys -------------------------------------------------------------------


_G_WINDOWS: list = []


def mul_g(k: int):
    """k*G from a table of 32 byte-windows: 32 mixed additions and one
    inversion, where ``secp.mul`` takes 256 doublings.  The generator needs
    one a script-path input, for the real output key."""
    if not _G_WINDOWS:
        base = secp.G
        for _ in range(32):
            row, J = [None], (base[0], base[1], 1)
            jac = []
            for _ in range(255):
                jac.append(J)
                J = secp._jadd_affine(*J, *base)
            zinv = secp._batch_inverse([j[2] for j in jac], secp.P)
            for (X, Y, _), zi in zip(jac, zinv):
                zz = zi * zi % secp.P
                row.append((X * zz % secp.P, Y * zz * zi % secp.P))
            _G_WINDOWS.append(row)
            zi = pow(J[2], -1, secp.P)  # 256 * base: the next window's
            base = (J[0] * zi * zi % secp.P, J[1] * zi * zi * zi % secp.P)
    X, Y, Z = 0, 1, 0
    k %= secp.N
    for row in _G_WINDOWS:
        pt = row[k & 0xFF]
        if pt is not None:
            X, Y, Z = secp._jadd_affine(X, Y, Z, *pt)
        k >>= 8
    if not Z:
        return None
    zi = pow(Z, -1, secp.P)
    return X * zi * zi % secp.P, Y * zi * zi * zi % secp.P


def even(d: int, pub: tuple) -> tuple:
    """BIP340's key: the secret whose point has even y."""
    return (d, pub) if pub[1] & 1 == 0 else (secp.N - d, (pub[0], secp.P - pub[1]))


def bip340_sign(d: int, px: int, m: bytes, k: int, kpt: tuple,
                odd_r: bool = False) -> tuple:
    """-> (r, s) under the even-y key ``d`` with a known nonce point.
    ``odd_r`` signs with the nonce whose point has odd y."""
    if (kpt[1] & 1 == 1) != odd_r:
        k = secp.N - k
    r = kpt[0]
    e = int.from_bytes(tagged_hash(
        "BIP0340/challenge",
        r.to_bytes(32, "big") + px.to_bytes(32, "big") + m), "big") % secp.N
    return r, (k + e * d) % secp.N


def x_with_no_point(x: int) -> int:
    while secp.is_residue((x * x * x + 7) % secp.P) or x >= secp.P:
        x = (x + 1) % secp.P
    return x


# ---- the plan and its counts ------------------------------------------------


def plan_adversarial(mix: dict, seed: int, first_tx: int, count: int,
                     total: int) -> dict:
    """tx index -> adversarial kind, as ``gen.plan_adversarial``: group ``g``
    carries kind ``adversarial[g % len]`` on one tx drawn from the seed among
    those with an input it applies to; a last group cut short carries none."""
    every, kinds = mix.get("adversarial_every", 0), mix.get("adversarial", [])
    if not every or not kinds:
        return {}
    pattern = mix["pattern"]
    plan = {}
    for g in range(first_tx // every, (first_tx + count - 1) // every + 1):
        if (g + 1) * every > total:
            break
        kind = kinds[g % len(kinds)]
        applies = ADVERSARIAL[kind][0]
        fits = [t for t in range(g * every, (g + 1) * every)
                if any(k in applies for k in pattern[t % len(pattern)])]
        t = random.Random(f"{seed}:adv:{g}").choice(fits)
        if first_tx <= t < first_tx + count:
            plan[t] = kind
    return plan


def totals(mix: dict, count: int) -> dict:
    """What ``count`` txs of this mix hold, for any seed."""
    tot = collections.Counter()
    pattern = mix["pattern"]
    turns, rest = divmod(count, len(pattern))
    for p, kinds in enumerate(pattern):
        n = turns + (p < rest)
        for kind in kinds:
            _form, sigs, items, algo, _wu = KINDS[kind]
            tot["inputs"] += n
            tot["sigs"] += n * sigs
            tot["items"] += n * items
            tot["items." + algo] += n * items
            tot["sigs." + algo] += n * sigs
            tot["in." + kind] += n
    every, kinds = mix.get("adversarial_every", 0), mix.get("adversarial", [])
    if every and kinds:
        rounds, more = divmod(count // every, len(kinds))
        for i, kind in enumerate(kinds):
            tot["adv." + kind] += rounds + (i < more)
    return {**{k: v for k, v in tot.items() if v}, "txs": count}


def coinbase(height: int, commitment: bytes) -> tuple:
    """-> (stripped, full): BIP34 height in the scriptSig, the BIP141
    commitment output, the witness reserved value as its one stack item."""
    sig = bytes([4]) + height.to_bytes(4, "little")
    ins = [(b"\x00" * 32, 0xFFFFFFFF, sig, 0xFFFFFFFF)]
    outs = [(50 * 100_000_000, b"\x00\x14" + b"\x11" * 20),
            (0, b"\x6a\x24\xaa\x21\xa9\xed" + commitment)]
    return (w.ser_tx(2, ins, outs),
            ser_witness_tx(2, ins, outs, [[b"\x00" * 32]]))


FIXED_BLOCK_WEIGHT = 4 * (80 + 3) + tx_weight(*coinbase(1, b"\x00" * 32))


def txs_that_fit(mix: dict, max_weight: int = MAX_BLOCK_WEIGHT) -> int:
    """The txs of the most whole turns of the pattern that weigh no more
    than ``max_weight`` with header, count and coinbase, every input at its
    widest (so that the count holds for any seed), and every adversarial tx
    a byte wider still (``p2tr_sig65_type0``, ``high_s``: four weight units
    where the byte is a scriptSig's)."""
    pattern = mix["pattern"]
    turn = sum(TX_OVERHEAD + sum(KINDS[k][4] for k in kinds)
               for kinds in pattern)
    n = len(pattern)
    turns = (max_weight - FIXED_BLOCK_WEIGHT) // turn
    every = mix.get("adversarial_every", 0)
    while every and turns * turn + 4 * -(-turns * n // every) > (
            max_weight - FIXED_BLOCK_WEIGHT):
        turns -= 1
    return turns * n


# ---- signing ----------------------------------------------------------------


def _ecdsa(d, z, nonces, adv):
    k, kpt, kinv = nonces.next()
    r, s = secp.ecdsa_sign(d, z, kinv, kpt)
    if s > HALF_N:
        s = secp.N - s
    if adv == "s_flip":
        s = (s + 1) % secp.N or 1
    elif adv == "high_s":
        s = secp.N - s
    elif adv == "r_plus_n":
        r += secp.N
    return secp.der(r, s) + bytes([SIGHASH_ALL])


def _draw_input(kind: str, adv, keys, rng) -> dict:
    """Keys, scripts and the outpoint of one input, before any signature."""
    d, pub, _ = keys.next()
    form = KINDS[kind][0]
    vout = FORMS.index(form) + 5 * rng.randrange(4)
    inp = {"kind": kind, "adv": adv, "d": d, "pub": pub, "script_sig": b""}
    if kind == "p2tr":
        inp["d"], inp["pub"] = d, pub = even(d, pub)
        x = pub[0]
        if adv == "xonly_off_curve":
            x = x_with_no_point(x)
        txid = x.to_bytes(32, "big")
    elif kind == "p2tr_script":
        inp["d"], inp["pub"] = d, pub = even(d, pub)  # the leaf's key
        _di, internal, _ = keys.next()
        internal = even(0, internal)[1]
        leaf_script = b"\x20" + pub[0].to_bytes(32, "big") + b"\xac"
        inp["leaf"] = tapleaf_hash(leaf_script)
        ix = internal[0].to_bytes(32, "big")
        tweak = int.from_bytes(tagged_hash("TapTweak", ix + inp["leaf"]), "big")
        out_key = secp.add(internal, mul_g(tweak))
        inp["tail"] = [leaf_script, bytes([0xC0 | (out_key[1] & 1)]) + ix]
        txid = out_key[0].to_bytes(32, "big")
    elif kind == "p2wsh_msig":
        trio = [(d, pub, secp.compress(pub))]
        for _ in range(2):
            d2, pub2, _ = keys.next()
            trio.append((d2, pub2, secp.compress(pub2)))
        inp["trio"] = trio
        inp["code"] = (b"\x52" + b"".join(b"\x21" + t[2] for t in trio)
                       + b"\x53\xae")
        txid = sha256(inp["code"])
    else:
        blob = inp["blob"] = secp.compress(pub)
        h = hash160(blob)
        inp["code"] = b"\x76\xa9\x14" + h + b"\x88\xac"
        if kind == "p2sh_p2wpkh":
            redeem = b"\x00\x14" + h
            inp["script_sig"] = w.push(redeem)
            h = hash160(redeem)
        txid = h + rng.randbytes(12)
    inp["txin"] = (txid, vout, inp["script_sig"], 0xFFFFFFFD)
    return inp


def _sign_input(inp: dict, index: int, tx: tuple, nonces) -> tuple:
    """-> (scriptSig, witness stack, per-signature verdicts)."""
    version, ins, outs, locktime, amounts, scripts, mids = tx
    kind, adv, d = inp["kind"], inp["adv"], inp["d"]
    ok = adv is None or ADVERSARIAL[adv][1]
    if kind in BIP340_KINDS:
        if adv == "p2tr_amount_off_by_one":
            wrong = list(amounts)
            wrong[index] += 1
            mid = bip341_midstate(version, ins, outs, locktime, wrong, scripts)
        else:
            if "bip341" not in mids:
                mids["bip341"] = bip341_midstate(version, ins, outs, locktime,
                                                 amounts, scripts)
            mid = mids["bip341"]
        script_path = kind == "p2tr_script"
        m = bip341_sighash(mid, index, SIGHASH_ALL if script_path else 0,
                           inp["leaf"] if script_path else None)
        k, kpt, _ = nonces.next()
        r, s = bip340_sign(d, inp["pub"][0], m, k, kpt,
                           odd_r=adv == "bip340_odd_r")
        if adv == "bip340_s_flip":
            s = (s + 1) % secp.N
        sig = r.to_bytes(32, "big") + s.to_bytes(32, "big")
        if script_path:
            return b"", [sig + bytes([SIGHASH_ALL])] + inp["tail"], (ok,)
        if adv == "p2tr_sig65_type0":
            sig += b"\x00"
        return b"", [sig], (ok,)
    if kind == "p2pkh":
        z = legacy_sighash(version, ins, outs, locktime, index, inp["code"])
        sig = _ecdsa(d, z, nonces, adv)
        return w.push(sig) + w.push(inp["blob"]), [], (ok,)
    if "bip143" not in mids:
        mids["bip143"] = w.forkid_midstate(version, ins, outs, locktime)
    z = w.forkid_sighash(mids["bip143"], ins[index], inp["code"],
                         amounts[index], SIGHASH_ALL)
    if kind == "p2wsh_msig":
        trio = inp["trio"]
        # which ordered pair signs rotates with the key, so the consensus
        # walk has to skip keys
        pair = ((0, 1), (0, 2), (1, 2))[d % 3]
        sigs = [_ecdsa(trio[ki][0], z, nonces, adv if which == 0 else None)
                for which, ki in enumerate(pair)]
        # walking from the last signature down, a bad first signature
        # leaves the second matched
        return b"", [b""] + sigs + [inp["code"]], (adv is None, True)
    return inp["script_sig"], [_ecdsa(d, z, nonces, adv), inp["blob"]], (ok,)


def gen_job(job: dict) -> dict:
    """``count`` txs of ``total``, from global index ``first_tx``: raw txs
    in their wire form (with witnesses), txids, wtxids, weights and the
    per-tx expected per-signature verdicts."""
    mix, seed = job["mix"], job["seed"]
    first, count = job["first_tx"], job["count"]
    rng = random.Random(f"{seed}:job:{first}")
    keys = secp.Chain(rng.getrandbits(256))
    nonces = secp.Chain(rng.getrandbits(256))
    pattern = mix["pattern"]
    plan = plan_adversarial(mix, seed, first, count, job["total"])
    raws, txids, wtxids, weights, expect = [], [], [], [], []
    for t in range(first, first + count):
        kinds = pattern[t % len(pattern)]
        adv = plan.get(t)
        hit = None
        if adv is not None:
            hit = next(i for i, k in enumerate(kinds)
                       if k in ADVERSARIAL[adv][0])
        drawn = [_draw_input(kind, adv if i == hit else None, keys, rng)
                 for i, kind in enumerate(kinds)]
        ins = [inp["txin"] for inp in drawn]
        amounts = [synth_amount(i[0], i[1]) for i in ins]
        scripts = [synth_script(i[0], i[1]) for i in ins]
        _d, okey, _ = keys.next()
        pay = sum(amounts) - 300 - t % 700
        outs = [(pay - pay // 3, b"\x51\x20" + okey[0].to_bytes(32, "big")),
                (pay // 3, b"\x00\x14" + hash160(secp.compress(okey)))]
        tx = (2, ins, outs, 0, amounts, scripts, {})
        wits, verdicts = [], ()
        for i, inp in enumerate(drawn):
            _sig, stack, vs = _sign_input(inp, i, tx, nonces)
            ins[i] = (ins[i][0], ins[i][1], _sig, ins[i][3])
            wits.append(stack)
            verdicts += vs
        stripped = w.ser_tx(2, ins, outs, 0)
        full = (ser_witness_tx(2, ins, outs, wits) if any(wits)
                else stripped)
        raws.append(full)
        txids.append(w.sha256d(stripped))
        wtxids.append(w.sha256d(full))
        weights.append(tx_weight(stripped, full))
        expect.append(verdicts)
    return {"first_tx": first, "raw": raws, "txids": txids, "wtxids": wtxids,
            "weights": weights, "expect": expect, "adversarial": plan}


def blocks_job(job: dict) -> dict:
    """``gen_job`` packed into block bodies of ``txs_per_block`` txs under a
    coinbase that carries the BIP141 commitment (heights from
    ``first_height``).  A body is what follows the 80-byte header; its txs
    are not returned a second time: ``offsets`` (one ``array('I')`` a block,
    ``txs_per_block + 1`` entries) cuts them out of it."""
    out = gen_job(job)
    per, bodies, offsets, weights = job["txs_per_block"], [], [], []
    for b in range(job["count"] // per):
        lo, hi = b * per, (b + 1) * per
        root = w.merkle_root([b"\x00" * 32] + out["wtxids"][lo:hi])
        cb, cb_full = coinbase(job["first_height"] + b,
                               w.sha256d(root + b"\x00" * 32))
        cb_txid = w.sha256d(cb)
        head = w.varint(per + 1) + cb_full
        offs = array.array("I", [len(head)])
        for raw in out["raw"][lo:hi]:
            offs.append(offs[-1] + len(raw))
        bodies.append((w.merkle_root([cb_txid] + out["txids"][lo:hi]),
                       cb_txid, head + b"".join(out["raw"][lo:hi])))
        offsets.append(offs)
        weights.append(4 * (80 + len(w.varint(per + 1)))
                       + tx_weight(cb, cb_full) + sum(out["weights"][lo:hi]))
    return {"first_tx": out["first_tx"], "txids": out["txids"],
            "expect": out["expect"], "bodies": bodies, "offsets": offsets,
            "block_weights": weights, "adversarial": out["adversarial"]}
