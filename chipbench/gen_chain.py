"""A chain that spends its own outputs: ``gen.py``'s mix and signatures
over transactions of two inputs and two outputs whose prevouts come from
four places — an earlier tx of the same block, a block a few back, a block
long connected, the UTXO snapshot — in the shares and ages the traffic
file's ``chain`` section states, every amount the true one.

A child cannot be signed before its parent has a txid, so the chain is cut
into independent *strands*: strand ``s`` of ``S`` makes txs ``s * per ..
(s + 1) * per`` of every block (``per`` = txs a block / ``S``) and spends
only the snapshot and its own strand's outputs.  A worker makes a strand
from the seed alone; the driver weaves the strands into blocks.  The
signing, the adversarial plan and the counts a mix holds are ``gen.py``'s,
by import; a worker never imports jax or tpunode.

A strand is made in two passes.  The *plan* (no signature, no hash) draws
every input's source and, for a source in the chain, claims one output
slot ``(block, tx, vout)`` of the strand that nobody claimed before; an
output slot that is claimed knows the kind of input that will spend it.
The *build* then walks the blocks in order: a claimed output carries the
script its spender's template needs under fresh keys, which are kept until
the spender signs with them; an output nobody claims pays to a key hash
nobody holds.
"""

from __future__ import annotations

import collections
import math
import random

from chipbench import gen, secp
from chipbench import wirefmt as w

SOURCES = ("in_block", "recent", "old", "snapshot")
FEE = 300


def _age(chain: dict, source: str, rng) -> int:
    """Blocks back, for a source that is an earlier block."""
    if source == "recent":
        lo, mid, hi = chain["recent_blocks"]  # half lo..mid, half mid+1..hi
        return (rng.randint(lo, mid) if rng.random() < 0.5
                else rng.randint(mid + 1, hi))
    lo, hi = chain["old_blocks"]  # log-uniform
    return min(hi, int(math.exp(rng.uniform(math.log(lo), math.log(hi + 1)))))


def _bounds(chain: dict, source: str, age: int) -> tuple:
    """The ages a claim may slide to when the block it drew has no output
    left: the half of ``recent`` the draw fell in, all of ``old``."""
    if source == "old":
        return tuple(chain["old_blocks"])
    lo, mid, hi = chain["recent_blocks"]
    return (lo, mid) if age <= mid else (mid + 1, hi)


def _outward(age: int, lo: int, hi: int):
    """``age``, then the ages of ``lo..hi`` by their distance from it."""
    yield age
    for d in range(1, hi - lo + 1):
        if age - d >= lo:
            yield age - d
        if age + d <= hi:
            yield age + d


def plan_strand(chain: dict, seed: int, strand: int, n_blocks: int,
                per: int, outputs: int = 2, inputs: int = 2) -> dict:
    """-> ``{"source": {(block, tx, input): (block, tx, vout) or None},
    "claims": {(block, tx, vout): (block, tx, input)}, "drawn": Counter,
    "got": Counter, "ages": Counter}``.  ``drawn`` counts what the seed
    drew, ``got`` what came of it (a draw that reaches below height 1, or
    that finds every output of its blocks claimed, falls to the snapshot);
    ``ages`` counts the blocks back of every in-chain spend.

    A source is drawn an input: ``in_block`` only by a strand's second tx
    and later (the first has no earlier tx of its strand to spend), at
    ``share * per / (per - 1)``, so that the share over all inputs is the
    file's; the other three in the file's proportions of what is left."""
    rng = random.Random(f"{seed}:plan:{strand}")
    share = chain["sources"]
    p_in = share["in_block"] * per / (per - 1) if per > 1 else 0.0
    rest = SOURCES[1:]
    weights = [share[s] for s in rest]
    free: dict = {}  # block -> its unclaimed (tx, vout) slots
    source, claims = {}, {}
    drawn, got, ages = (collections.Counter() for _ in range(3))

    def slots(b: int) -> list:
        if b not in free:
            free[b] = [(j, v) for j in range(per) for v in range(outputs)]
        return free[b]

    def claim(b: int, earlier_than=None):
        have = slots(b)
        if earlier_than is not None:
            have = [s for s in have if s[0] < earlier_than]
        if not have:
            return None
        pick = rng.choice(have)
        free[b].remove(pick)
        return (b,) + pick

    for b in range(1, n_blocks + 1):
        for j in range(per):
            for i in range(inputs):
                want = ("in_block" if j and rng.random() < p_in
                        else rng.choices(rest, weights)[0])
                drawn[want] += 1
                parent = None
                if want == "in_block":
                    parent = claim(b, earlier_than=j)
                elif want != "snapshot":
                    age = _age(chain, want, rng)
                    lo, hi = _bounds(chain, want, age)
                    # an age that reaches below height 1 falls to the
                    # snapshot; else the drawn block, then its neighbours
                    # inside the range
                    for a in _outward(age, lo, min(hi, b - 1)) if age < b else ():
                        parent = claim(b - a)
                        if parent is not None:
                            break
                source[(b, j, i)] = parent
                if parent is None:
                    got["snapshot"] += 1
                else:
                    got[want] += 1
                    ages[b - parent[0]] += 1
                    claims[parent] = (b, j, i)
        free.pop(b - chain["old_blocks"][1] - 1, None)  # out of every reach
    return {"source": source, "claims": claims, "drawn": drawn, "got": got,
            "ages": ages}


class _Held:
    """The keys an output was made under, handed to ``gen._sign_input`` in
    the order it asks for them."""

    def __init__(self, keys: list):
        self.keys = keys[::-1]

    def next(self):
        return self.keys.pop()


def _script_for(kind: str, keys) -> tuple:
    """An output that an input of ``kind`` can spend -> (script, the keys
    its spender signs with, as ``secp.Chain.next`` gives them)."""
    if kind == "msig":
        trio = [keys.next() for _ in range(3)]
        redeem = (b"\x52" + b"".join(b"\x21" + secp.compress(t[1]) for t in trio)
                  + b"\x53\xae")
        return b"\xa9\x14" + gen.hash160(redeem) + b"\x87", trio
    key = keys.next()
    blob = secp.compress(key[1])
    if kind == "p2pk":
        return b"\x21" + blob + b"\xac", [key]
    return gen.p2pkh_code(blob), [key]  # p2pkh, schnorr


def strand_job(job: dict) -> dict:
    """One strand of the whole chain.  -> per block, in order: ``raw`` (its
    ``per`` txs), ``txids``, ``expect``; and over the strand: ``snapshot``
    (the outpoints the snapshot has to hold, 36 bytes each, joined),
    ``p2pk`` (the bare-P2PK ones' scripts), ``drawn`` / ``got`` / ``ages``
    (the plan's counts)."""
    mix, chain, seed = job["mix"], job["chain"], job["seed"]
    strand, n_blocks = job["strand"], job["blocks"]
    per_block = job["txs_per_block"]
    per = per_block // chain["strands"]
    pattern = mix["pattern"]
    plan = plan_strand(chain, seed, strand, n_blocks, per)
    source, claims = plan["source"], plan["claims"]
    adversarial = gen.plan_adversarial(
        mix, seed, 0, n_blocks * per_block, n_blocks * per_block)
    rng = random.Random(f"{seed}:strand:{strand}")
    keys = secp.Chain(rng.getrandbits(256))
    nonces = secp.Chain(rng.getrandbits(256))
    nobody = gen.p2pkh_code(b"\x02" + rng.randbytes(32))
    held: dict = {}  # claimed slot -> (amount, script, keys)
    txid_of: dict = {}  # (block, tx) of this strand -> txid
    raws, txids, expect, snapshot, p2pk = [], [], [], [], {}
    for b in range(1, n_blocks + 1):
        for j in range(per):
            t = (b - 1) * per_block + strand * per + j  # as gen_job counts
            kinds = pattern[t % len(pattern)]
            adv = adversarial.get(t)
            hit = None
            if adv is not None:
                hit = next(i for i, k in enumerate(kinds)
                           if k in gen.ADVERSARIAL[adv][0])
            ins, amounts, signers = [], [], []
            for i in range(len(kinds)):
                parent = source[(b, j, i)]
                if parent is None:
                    txin = (rng.randbytes(32), rng.randrange(4))
                    amounts.append(gen.synth_amount(*txin))
                    signers.append(keys)
                    snapshot.append(txin[0] + txin[1].to_bytes(4, "little"))
                else:
                    amount, _, made = held.pop(parent)
                    txin = (txid_of[parent[:2]], parent[2])
                    amounts.append(amount)
                    signers.append(_Held(made))
                ins.append(txin + (b"", 0xFFFFFFFF))
            total = sum(amounts)
            rest = total - min(FEE, total // 4)
            first = max(1, int(rest * rng.uniform(0.4, 0.6)))
            outs = []
            for v, value in enumerate((first, rest - first)):
                spender = claims.get((b, j, v))
                if spender is None:
                    outs.append((value, nobody))
                    continue
                sb, sj, si = spender
                kind = pattern[((sb - 1) * per_block + strand * per + sj)
                               % len(pattern)][si]
                script, made = _script_for(kind, keys)
                held[(b, j, v)] = (value, script, made)
                outs.append((value, script))
            mid = w.forkid_midstate(2, ins, outs, 0)
            signed, verdicts = [], ()
            for i, (kind, txin) in enumerate(zip(kinds, ins)):
                script, pscript, vs = gen._sign_input(
                    kind, adv if i == hit else None, signers[i], nonces, mid,
                    txin, amounts[i])
                if pscript is not None and source[(b, j, i)] is None:
                    p2pk[txin[0] + txin[1].to_bytes(4, "little")] = pscript
                signed.append((txin[0], txin[1], script, txin[3]))
                verdicts += vs
            raw = w.ser_tx(2, signed, outs, 0)
            txid = w.sha256d(raw)
            txid_of[(b, j)] = txid
            raws.append(raw)
            txids.append(txid)
            expect.append(verdicts)
        for j in range(per):  # spent or not, no later tx names it by slot
            txid_of.pop((b - chain["old_blocks"][1] - 1, j), None)
    return {"strand": strand, "raw": raws, "txids": txids, "expect": expect,
            "snapshot": b"".join(snapshot), "p2pk": p2pk,
            "drawn": dict(plan["drawn"]), "got": dict(plan["got"]),
            "ages": dict(plan["ages"])}


def jobs_for(traffic: dict, seed: int, n_blocks: int) -> list:
    """A job a strand."""
    return [{"mix": traffic["mix"], "chain": traffic["chain"], "seed": seed,
             "strand": s, "blocks": n_blocks,
             "txs_per_block": traffic["txs_per_block"]}
            for s in range(traffic["chain"]["strands"])]


def weave(parts: list, n_blocks: int, per_block: int) -> tuple:
    """The strands' txs as blocks: block ``b`` holds, under its coinbase,
    strand 0's txs of that block, then strand 1's, ...  -> (bodies as
    ``gen.chain_frames`` takes them, per block its txids without the
    coinbase, per block each tx's offsets into the body)."""
    parts = sorted(parts, key=lambda p: p["strand"])
    per = per_block // len(parts)
    bodies, txids, offsets = [], [], []
    for b in range(n_blocks):
        cb = w.coinbase(b + 1)
        raws = [r for p in parts for r in p["raw"][b * per:(b + 1) * per]]
        ids = [t for p in parts for t in p["txids"][b * per:(b + 1) * per]]
        head = w.varint(per_block + 1) + cb
        offs, at = [], len(head)
        for r in raws:
            offs.append(at)
            at += len(r)
        offs.append(at)
        bodies.append((w.merkle_root([w.sha256d(cb)] + ids), w.sha256d(cb),
                       head + b"".join(raws)))
        txids.append(ids)
        offsets.append(offs)
    return bodies, txids, offsets
