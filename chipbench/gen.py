"""The one general transaction generator: stratified, seeded, fast.

A traffic mix is data (``chipbench/traffic/<name>.json``); this module
turns its ``mix`` section into signed BCH transactions.  Stratified means
every seed yields exactly the same number of transactions, inputs,
signatures per script type and adversarial items: tx ``t`` takes
``pattern[t % len(pattern)]``, and of every ``adversarial_every``
consecutive txs exactly one carries the next kind of the ``adversarial``
list.  The seed changes keys, nonces, outpoints, and which tx of a group
is the adversarial one.

The expected verdict of every signature is known by construction and
returned beside the bytes; ``reference.py`` re-derives it from the bytes
alone.  Worker processes run ``gen_job`` and never import jax or tpunode.

Input kinds (all SIGHASH_ALL|FORKID, BCH rules with Schnorr):
``p2pkh`` ECDSA, ``schnorr`` (P2PKH, 65-byte signature), ``p2pk`` (key in
the prevout script, which only the prevout oracle knows), ``msig`` (P2SH
2-of-3, two ECDSA signatures, four candidate pairs on the device).
"""

from __future__ import annotations

import collections
import hashlib
import random

from chipbench import secp
from chipbench import wirefmt as w

SIGS = {"p2pkh": 1, "schnorr": 1, "p2pk": 1, "msig": 2}
# device candidates per input: a 2-of-3 tries sig i against keys i, i+1
ITEMS = {"p2pkh": 1, "schnorr": 1, "p2pk": 1, "msig": 4}

# adversarial kind -> (input kinds it applies to, verdict of the
# corrupted signature).  ``high_s`` is the valid twin (r, n - s): this
# node reports verdicts and applies no low-S policy, so rejecting it
# would be as wrong as accepting the others.
ADVERSARIAL = {
    "s_flip": (("p2pkh", "p2pk", "msig"), False),
    "high_s": (("p2pkh", "p2pk"), True),
    "r_plus_n": (("p2pkh", "p2pk"), False),
    "s_plus_n": (("p2pkh", "p2pk"), False),
    "off_curve_key": (("p2pkh",), False),
    "schnorr_s_flip": (("schnorr",), False),
    "schnorr_residue_twin": (("schnorr",), False),
}

HT = bytes([w.SIGHASH_ALL_FORKID])


def hash160(b: bytes) -> bytes:
    return hashlib.new("ripemd160", hashlib.sha256(b).digest()).digest()


def p2pkh_code(blob: bytes) -> bytes:
    return b"\x76\xa9\x14" + hash160(blob) + b"\x88\xac"


def synth_amount(txid: bytes, vout: int) -> int:
    """The prevout oracle's amount: a function of the outpoint, so that
    generator, node-side oracle and reference need no table for it."""
    return 10_000 + (int.from_bytes(txid[:6], "little") ^ vout) % 5_000_000


def synth_script(txid: bytes) -> bytes:
    """The oracle's script for every outpoint that is not bare P2PK."""
    return b"\x76\xa9\x14" + txid[:20] + b"\x88\xac"


def plan_adversarial(mix: dict, seed: int, first_tx: int, count: int,
                     total: int) -> dict:
    """tx index -> adversarial kind, for txs ``first_tx .. first_tx+count``
    of ``total``.  Group ``g`` (``adversarial_every`` consecutive txs)
    carries kind ``adversarial[g % len]`` on one tx drawn from the seed
    among those whose pattern has an input the kind applies to; a last
    group cut short by ``total`` carries none, so that the counts do not
    depend on where the seed puts it."""
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
    """What ``count`` txs of this mix hold, for any seed: whole turns of
    the pattern, the txs of a last turn cut short, and one adversarial tx
    for every whole group, kinds in rotation (``plan_adversarial``)."""
    tot = collections.Counter()
    pattern = mix["pattern"]
    turns, rest = divmod(count, len(pattern))
    for p, kinds in enumerate(pattern):
        n = turns + (p < rest)
        for kind in kinds:
            tot["inputs"] += n
            tot["sigs"] += n * SIGS[kind]
            tot["items"] += n * ITEMS[kind]
            tot["in." + kind] += n
    every, kinds = mix.get("adversarial_every", 0), mix.get("adversarial", [])
    if every and kinds:
        rounds, more = divmod(count // every, len(kinds))
        for i, kind in enumerate(kinds):
            tot["adv." + kind] += rounds + (i < more)
    return {**{k: v for k, v in tot.items() if v}, "txs": count}


def _sign_input(kind, adv, keys, nonces, mid, txin, amount):
    """-> (scriptSig, prevout script or None, per-signature verdicts)."""
    k, kpt, kinv = nonces.next()
    d, pub, _ = keys.next()
    blob = secp.compress(pub)
    if kind == "schnorr":
        z = w.forkid_sighash(mid, txin, p2pkh_code(blob), amount)
        r, s = secp.schnorr_sign(d, pub, z, k, kpt,
                                 residue=adv != "schnorr_residue_twin")
        if adv == "schnorr_s_flip":
            s = (s + 1) % secp.N
        sig = r.to_bytes(32, "big") + s.to_bytes(32, "big") + HT
        return w.push(sig) + w.push(blob), None, (adv is None,)
    if kind == "msig":
        trio = [(d, pub, blob)]
        for _ in range(2):
            d2, pub2, _ = keys.next()
            trio.append((d2, pub2, secp.compress(pub2)))
        redeem = (b"\x52" + b"".join(b"\x21" + t[2] for t in trio)
                  + b"\x53\xae")
        z = w.forkid_sighash(mid, txin, redeem, amount)
        # which ordered pair signs rotates with the key, so the consensus
        # walk has to skip keys
        pair = ((0, 1), (0, 2), (1, 2))[d % 3]
        sigs = []
        for which, ki in enumerate(pair):
            if which:
                k, kpt, kinv = nonces.next()
            r, s = secp.ecdsa_sign(trio[ki][0], z, kinv, kpt)
            if adv == "s_flip" and which == 0:
                s = (s + 1) % secp.N or 1
            sigs.append(w.push(secp.der(r, s) + HT))
        # walking from the last signature down, a bad first signature
        # leaves the second matched
        verdicts = (adv is None, True)
        return b"\x00" + b"".join(sigs) + w.push(redeem), None, verdicts
    pscript = None
    if kind == "p2pk":
        pscript = code = b"\x21" + blob + b"\xac"
    else:
        code = p2pkh_code(blob)
        if adv == "off_curve_key":
            # an uncompressed key one off the curve: the digest commits
            # to the blob, so sign over what is sent
            blob = (b"\x04" + pub[0].to_bytes(32, "big")
                    + ((pub[1] + 1) % secp.P).to_bytes(32, "big"))
            code = p2pkh_code(blob)
    z = w.forkid_sighash(mid, txin, code, amount)
    r, s = secp.ecdsa_sign(d, z, kinv, kpt)
    if adv == "s_flip":
        s = (s + 1) % secp.N or 1
    elif adv == "high_s":
        s = secp.N - s
    elif adv == "r_plus_n":
        r += secp.N
    elif adv == "s_plus_n":
        s += secp.N
    sig = w.push(secp.der(r, s) + HT)
    ok = adv is None or ADVERSARIAL[adv][1]
    return (sig if kind == "p2pk" else sig + w.push(blob)), pscript, (ok,)


def gen_job(job: dict) -> dict:
    """``count`` txs of ``total``, from global index ``first_tx``.  Returns raw
    txs, txids, per-tx expected per-signature verdicts, and the bare-P2PK
    prevout scripts the oracle has to know."""
    mix, seed = job["mix"], job["seed"]
    first, count = job["first_tx"], job["count"]
    rng = random.Random(f"{seed}:job:{first}")
    keys = secp.Chain(rng.getrandbits(256))
    nonces = secp.Chain(rng.getrandbits(256))
    pattern = mix["pattern"]
    plan = plan_adversarial(mix, seed, first, count, job["total"])
    out_script = p2pkh_code(b"\x02" + rng.randbytes(32))
    raws, txids, expect, p2pk = [], [], [], {}
    for t in range(first, first + count):
        kinds = pattern[t % len(pattern)]
        adv = plan.get(t)
        hit = None
        if adv is not None:
            hit = next(i for i, k in enumerate(kinds)
                       if k in ADVERSARIAL[adv][0])
        ins = [(rng.randbytes(32), rng.randrange(4), b"", 0xFFFFFFFF)
               for _ in kinds]
        outs = [(50_000 + t % 1000, out_script)]
        mid = w.forkid_midstate(2, ins, outs, 0)
        signed, verdicts = [], ()
        for i, (kind, txin) in enumerate(zip(kinds, ins)):
            script, pscript, vs = _sign_input(
                kind, adv if i == hit else None, keys, nonces, mid, txin,
                synth_amount(txin[0], txin[1]))
            if pscript is not None:
                p2pk[txin[0] + txin[1].to_bytes(4, "little")] = pscript
            signed.append((txin[0], txin[1], script, txin[3]))
            verdicts += vs
        raw = w.ser_tx(2, signed, outs, 0)
        raws.append(raw)
        txids.append(w.sha256d(raw))
        expect.append(verdicts)
    return {"first_tx": first, "raw": raws, "txids": txids,
            "expect": expect, "p2pk": p2pk, "adversarial": plan}


def jobs_for(mix: dict, seed: int, count: int, per_job: int) -> list:
    return [{"mix": mix, "seed": seed, "first_tx": lo, "total": count,
             "count": min(per_job, count - lo)}
            for lo in range(0, count, per_job)]


class Oracle:
    """The embedder's prevout lookup (``NodeConfig.prevout_lookup``):
    bare-P2PK outpoints from the generator's table, everything else a
    function of the outpoint."""

    def __init__(self):
        self.p2pk: dict = {}

    def __call__(self, txid: bytes, vout: int):
        script = self.p2pk.get(txid + vout.to_bytes(4, "little"))
        return synth_amount(txid, vout), script or synth_script(txid)


# ---- worker jobs that also frame what they made ----------------------------


def tx_frames_job(job: dict) -> dict:
    """``gen_job`` plus each tx as a ready ``tx`` frame."""
    out = gen_job(job)
    out["frames"] = [w.frame(job["magic"], "tx", raw) for raw in out["raw"]]
    return out


def block_bodies_job(job: dict) -> dict:
    """``gen_job`` packed into block bodies of ``txs_per_block`` txs under
    a coinbase each (heights from ``first_height``): what follows the
    80-byte header, and the merkle root the header has to carry."""
    out = gen_job(job)
    per, bodies = job["txs_per_block"], []
    for b in range(job["count"] // per):
        cb = w.coinbase(job["first_height"] + b)
        raws = out["raw"][b * per:(b + 1) * per]
        txids = [w.sha256d(cb)] + out["txids"][b * per:(b + 1) * per]
        bodies.append((w.merkle_root(txids), w.sha256d(cb),
                       w.varint(per + 1) + cb + b"".join(raws)))
    out["bodies"] = bodies
    return out


def permuted_body_job(job: dict) -> tuple:
    """One big block: the pool file's txs in an order drawn from the seed,
    under a fresh coinbase.  -> (merkle root, coinbase txid, body)."""
    with open(job["pool_file"], "rb") as f:
        blob = f.read()
    offs = job["offsets"]
    order = list(range(len(offs) - 1))
    random.Random(f"{job['seed']}:perm:{job['height']}").shuffle(order)
    cb = w.coinbase(job["height"])
    raws = [blob[offs[i]:offs[i + 1]] for i in order]
    txids = [w.sha256d(cb)] + [job["txids"][i] for i in order]
    return (w.merkle_root(txids), w.sha256d(cb),
            w.varint(len(order) + 1) + cb + b"".join(raws))


def chain_frames(net: dict, bodies: list) -> tuple:
    """Headers and ``block`` frames over ``(merkle, _, body)`` triples, on
    top of the genesis.  -> (headers, [hash], {hash: frame})."""
    magic, g = int(net["magic"], 16), net["genesis"]
    prev = w.sha256d(w.genesis_header(net))
    headers, frames, hashes = [], {}, []
    for h, (merkle, _cb, body) in enumerate(bodies):
        hdr = w.mine_header(prev, merkle, g["timestamp"] + 600 * (h + 1),
                            g["bits"])
        prev = w.sha256d(hdr)
        headers.append(hdr)
        hashes.append(prev)
        frames[prev] = w.frame(magic, "block", hdr + body)
    return headers, hashes, frames
