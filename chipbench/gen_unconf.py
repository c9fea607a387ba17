"""Relay traffic that spends unconfirmed outputs: ``gen.py``'s mix and
signatures over ``gen_chain.py``'s transactions of two inputs and two
outputs with true amounts, on a schedule of due times — so that the mempool
answers prevouts, children can be due before their parents, and the blocks
pushed over the relayed transactions spend what they themselves hold.

Every transaction has a *time*: a relayed one its due time on the schedule
(``drivers/open.py`` draws it), a never-pushed one a moment inside the
interval of the block that holds it.  An input's prevout is drawn an input
from the traffic file's ``unconf`` section, stratified (every ``period``
consecutive inputs hold exactly the section's shares, in an order drawn
from the seed):

``near`` / ``far``
    an output of a relayed tx due ``near_s`` / ``far_s`` before the spender
    arrives that no pushed block holds at that moment: the mempool answers;
``disorder``
    an output of a tx due ``lead_s`` *after* the spender, relayed through
    another peer (or never pushed at all): the spender arrives first and
    waits for it;
``confirmed``
    an output of a tx of one of the last ``blocks`` blocks pushed at least
    ``settle_s`` before the spender arrives: the view or the set answers;
``funding``
    an outpoint nobody made, its amount a function of itself
    (``gen.synth_amount``): only the embedder's callback knows it.

A child cannot be signed before its parent has a txid, so the traffic is
cut into independent *strands* (tx ``g`` is of strand ``g % strands``) and
a tx spends only its own strand's outputs: a worker makes a strand from the
job alone.  Within a strand every tx has a *rank*, the moment it reaches
the node: its time — or, for a tx that a ``disorder`` input names, a
moment before the earliest spender that does, because a peer that is asked
for it serves it then.  A tx spends only txs of lower rank (so no cycle),
and the ages above are counted from the spender's rank.  The plan makes two
passes (the ``disorder`` claims in order of time, which settles the ranks;
the rest in order of rank) and the build a third, in order of rank, as
``gen_chain.strand_job`` does: a claimed output carries the script its
spender's template needs under keys kept until the spender signs.

Every output is spent at most once; no output of an adversarial tx is
spent.  A draw that finds no output left where it looks falls to the next
kind (``disorder`` -> ``near`` -> ``far`` -> ``funding``; ``confirmed`` ->
``funding``): ``drawn`` counts what the pattern asked, ``got`` what came of
it.  A worker never imports jax or tpunode.
"""

from __future__ import annotations

import bisect
import collections
import random

from chipbench import gen, secp
from chipbench import wirefmt as w
from chipbench.gen_chain import FEE, _Held, _script_for

KINDS = ("near", "far", "disorder", "confirmed", "funding")
NEVER = float("inf")


def period_counts(unconf: dict) -> dict:
    """How many inputs of each kind a period holds; the shares have to come
    out whole."""
    n = unconf["period"]
    share = unconf["sources"]
    num, den = unconf["disorder"]["of_unconfirmed"]
    parts = {"unconfirmed": share["unconfirmed"] * n,
             "confirmed": share["confirmed"] * n,
             "funding": share["funding"] * n}
    whole = {k: round(v) for k, v in parts.items()}
    disorder = whole["unconfirmed"] * num / den
    rest = whole["unconfirmed"] - round(disorder)
    if (any(abs(v - whole[k]) > 1e-9 for k, v in parts.items())
            or sum(whole.values()) != n or disorder != round(disorder)
            or rest % 2):
        raise ValueError(f"the shares of 'unconf' do not divide a period of {n}")
    return {"near": rest // 2, "far": rest // 2, "disorder": round(disorder),
            "confirmed": whole["confirmed"], "funding": whole["funding"]}


def labels(unconf: dict, seed: int, n_inputs: int) -> list:
    """Input ``q`` (input ``q % 2`` of tx ``q // 2``) -> the kind it draws."""
    counts = period_counts(unconf)
    base = [k for k in KINDS for _ in range(counts[k])]
    out = []
    for p in range(-(-n_inputs // len(base))):
        turn = list(base)
        random.Random(f"{seed}:src:{p}").shuffle(turn)
        out += turn
    return out[:n_inputs]


class _Strand:
    """One strand's txs in order of time, and the search for an output."""

    def __init__(self, job: dict):
        u = job["unconf"]
        self.every, self.lag = job["block_every_s"], job["known_lag_s"]
        self.time, self.peer = job["time"], job["peer"]
        self.block = job["block"]  # tx -> its block (0-based), None: none
        s, n = job["strand"], job["strands"]
        self.mine = sorted(range(s, len(self.time), n),
                           key=lambda g: (self.time[g], g))
        self.at = [self.time[g] for g in self.mine]  # for bisect
        self.rank = {g: self.time[g] for g in self.mine}
        self.rng = random.Random(f"{job['seed']}:unconf:{s}")
        self.n_relay = job["relay"]
        total = len(self.time)
        self.adversarial = gen.plan_adversarial(job["mix"], job["seed"], 0,
                                                total, total)
        self.free = {g: [0, 1] for g in self.mine if g not in self.adversarial}
        self.source: dict = {}  # (tx, input) -> (parent tx, vout) or None
        self.claims: dict = {}  # (parent tx, vout) -> (tx, input)
        self.kind: dict = {}  # (tx, input) -> what came of its draw
        self.near, self.far = u["near_s"], u["far_s"]
        self.lead = u["disorder"]["lead_s"]
        self.blocks, self.settle = u["confirmed"]["blocks"], u["confirmed"]["settle_s"]

    def pushed(self, g: int) -> float:
        """When the block that holds ``g`` is pushed."""
        b = self.block[g]
        return NEVER if b is None else self.every * (b + 1)

    def pick(self, lo: float, hi: float, ok):
        """An unclaimed output of a tx of this strand with ``lo <= time <
        hi`` that ``ok`` admits: from a place drawn from the seed outward."""
        i0, i1 = bisect.bisect_left(self.at, lo), bisect.bisect_left(self.at, hi)
        if i0 >= i1:
            return None
        start = self.rng.randrange(i0, i1)
        for d in range(i1 - i0):
            for j in ((start + d,) if not d else (start - d, start + d)):
                if i0 <= j < i1:
                    g = self.mine[j]
                    outs = self.free.get(g)
                    if outs and ok(g):
                        return g, outs.pop(self.rng.randrange(len(outs)))
        return None

    def claim(self, y: int, i: int, kind: str, got) -> None:
        self.source[(y, i)] = got
        self.kind[(y, i)] = kind
        if got is not None:
            self.claims[got] = (y, i)

    def plan(self, label: list) -> None:
        relay = self.n_relay
        lead_lo, lead_hi = self.lead
        later: list = []  # (tx, input, kind) left for the second pass
        # the disorder claims, in order of time: a tx's rank is final when
        # its turn comes, for only an earlier spender can lower it
        for y in self.mine:
            for i in (0, 1):
                kind = label[2 * y + i]
                # a never-pushed tx arrives with its block or when asked
                # for: it cannot be due before anything
                if kind == "disorder" and y >= relay:
                    kind = "near"
                if kind == "disorder":
                    t = self.time[y]
                    got = self.pick(
                        t + lead_lo, t + lead_hi,
                        lambda g: g != y and self.peer[g] != self.peer[y])
                    if got is not None:
                        self.claim(y, i, "disorder", got)
                        p = got[0]
                        self.rank[p] = min(self.rank[p], self.rank[y] - 1e-9)
                        continue
                    kind = "near"
                later.append((y, i, kind))
        # the rest, in order of rank: what has a lower rank is here already
        later.sort(key=lambda e: (self.rank[e[0]], e[0], e[1]))
        for y, i, kind in later:
            T = self.rank[y]

            def here(g):  # relayed, arrived, in no pushed block
                return (g < relay and g != y and self.rank[g] < T
                        and self.pushed(g) > T)

            got = None
            if kind == "near":
                got = self.pick(T - self.near[1], T - self.near[0], here)
                if got is None:
                    kind = "far"
            if kind == "far":
                got = self.pick(T - self.far[1], T - self.far[0], here)
                if got is None:
                    kind = "funding"
            if kind == "confirmed":
                # the newest block pushed settle_s before T, 0-based
                last = int((T - self.settle) // self.every) - 1
                if last >= 0:
                    got = self.pick(
                        self.every * max(0, last - self.blocks + 1) - self.lag,
                        self.every * (last + 1) - self.lag,
                        lambda g: (self.block[g] is not None
                                   and last - self.blocks < self.block[g] <= last))
                if got is None:
                    kind = "funding"
            self.claim(y, i, kind, got)

    def depth(self) -> int:
        """The longest chain of ancestors that were unconfirmed when their
        spender arrived."""
        deep: dict = {}
        for y in sorted(self.mine, key=lambda g: (self.rank[g], g)):
            deep[y] = max(
                [deep[self.source[(y, i)][0]] + 1 for i in (0, 1)
                 if self.kind[(y, i)] in ("near", "far", "disorder")],
                default=0)
        return max(deep.values(), default=0)


def strand_job(job: dict) -> dict:
    """One strand.  -> over its txs, in ascending tx number: ``g``, ``raw``,
    ``txids``, ``expect``, ``frames`` (each as a ``tx`` message);
    ``funding`` (the outpoints only the callback knows, 36 bytes each,
    joined), ``p2pk`` (the bare-P2PK ones' scripts); ``drawn`` / ``got``
    (inputs by kind: the pattern's, and what came of it), ``waits`` (relayed
    txs with an input that names a tx still to come), ``depth``,
    ``small_fee`` (txs whose inputs held under four fees, and paid a
    quarter of them instead)."""
    st = _Strand(job)
    mix, pattern = job["mix"], job["mix"]["pattern"]
    label = labels(job["unconf"], job["seed"], 2 * len(st.time))
    st.plan(label)
    rng = random.Random(f"{job['seed']}:strand:{job['strand']}")
    keys = secp.Chain(rng.getrandbits(256))
    nonces = secp.Chain(rng.getrandbits(256))
    nobody = gen.p2pkh_code(b"\x02" + rng.randbytes(32))
    held: dict = {}  # claimed output -> (amount, script, keys)
    txid_of: dict = {}
    built: dict = {}
    funding, p2pk, small_fee = [], {}, 0
    for y in sorted(st.mine, key=lambda g: (st.rank[g], g)):
        kinds = pattern[y % len(pattern)]
        adv = st.adversarial.get(y)
        hit = None
        if adv is not None:
            hit = next(i for i, k in enumerate(kinds)
                       if k in gen.ADVERSARIAL[adv][0])
        ins, amounts, signers = [], [], []
        for i in range(len(kinds)):
            parent = st.source[(y, i)]
            if parent is None:
                txin = (rng.randbytes(32), rng.randrange(4))
                amounts.append(gen.synth_amount(*txin))
                signers.append(keys)
                funding.append(txin[0] + txin[1].to_bytes(4, "little"))
            else:
                amount, _, made = held.pop(parent)
                txin = (txid_of[parent[0]], parent[1])
                amounts.append(amount)
                signers.append(_Held(made))
            ins.append(txin + (b"", 0xFFFFFFFF))
        total = sum(amounts)
        rest = total - min(FEE, total // 4)
        small_fee += total < 4 * FEE
        first = max(1, int(rest * rng.uniform(0.4, 0.6)))
        outs = []
        for v, value in enumerate((first, rest - first)):
            spender = st.claims.get((y, v))
            if spender is None:
                outs.append((value, nobody))
                continue
            script, made = _script_for(
                pattern[spender[0] % len(pattern)][spender[1]], keys)
            held[(y, v)] = (value, script, made)
            outs.append((value, script))
        mid = w.forkid_midstate(2, ins, outs, 0)
        signed, verdicts = [], ()
        for i, (kind, txin) in enumerate(zip(kinds, ins)):
            script, pscript, vs = gen._sign_input(
                kind, adv if i == hit else None, signers[i], nonces, mid,
                txin, amounts[i])
            if pscript is not None and st.source[(y, i)] is None:
                p2pk[txin[0] + txin[1].to_bytes(4, "little")] = pscript
            signed.append((txin[0], txin[1], script, txin[3]))
            verdicts += vs
        raw = w.ser_tx(2, signed, outs, 0)
        txid_of[y] = w.sha256d(raw)
        built[y] = (raw, verdicts)
    order = sorted(st.mine)
    magic = job["magic"]
    return {
        "g": order, "raw": [built[g][0] for g in order],
        "txids": [txid_of[g] for g in order],
        "expect": [built[g][1] for g in order],
        "frames": [w.frame(magic, "tx", built[g][0]) for g in order],
        "funding": b"".join(funding), "p2pk": p2pk,
        "drawn": dict(collections.Counter(label[2 * g + i]
                                          for g in order for i in (0, 1))),
        "got": dict(collections.Counter(st.kind.values())),
        "waits": len({y for (y, _), k in st.kind.items() if k == "disorder"}),
        "depth": st.depth(), "small_fee": small_fee,
    }


def jobs_for(traffic: dict, seed: int, magic: int, time: list, peer: list,
             block: list, relay: int) -> list:
    """A job a strand.  ``time`` / ``peer`` / ``block``: over all txs, the
    ``relay`` relayed ones first (in the schedule's order), then the
    never-pushed (``peer`` -1)."""
    return [{"mix": traffic["mix"], "unconf": traffic["unconf"], "seed": seed,
             "magic": magic, "strand": s,
             "strands": traffic["unconf"]["strands"],
             "block_every_s": traffic["block_every_s"],
             "known_lag_s": traffic["known_lag_s"],
             "time": time, "peer": peer, "block": block, "relay": relay}
            for s in range(traffic["unconf"]["strands"])]


def canonical(txs: list) -> list:
    """``(txid, raw)`` pairs in a block's canonical order (BCH, November
    2018): ascending by txid read as a number, so a child stands before its
    parent about half the time."""
    return sorted(txs, key=lambda t: t[0][::-1])
