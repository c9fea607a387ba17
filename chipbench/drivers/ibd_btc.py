"""Initial block download of a segwit / taproot chain: ``drivers/ibd.py``'s
loop, window and statistic over ``gen_btc``'s blocks.

One peer (``peers_btc.WitnessRemote``) holds a chain of blocks filled to
the weight limit; the node's own planner fetches it.  What differs from the
BCH driver is what the chain is made of and how it is kept: a block is 1.6
MB, so a transaction's bytes are cut out of its block's frame when the
reference asks for them (``Raw``) instead of being held a second time, and
the prevout oracle is a function of the outpoint (``prevouts_btc``)."""

from __future__ import annotations

import math

from chipbench import gen, gen_btc, harness
from chipbench.drivers import ibd
from chipbench.peers_btc import WitnessRemote
from chipbench.prevouts_btc import Oracle

BLOCK_HEAD = 24 + 80  # the frame's envelope and the block's header

# adversarial kinds whose item the extractor marks auto-invalid (no key to
# verify under, or no signature of a valid form): it enters the engine
# under no algorithm
AUTO_INVALID = ("xonly_off_curve", "p2tr_sig65_type0")


def txs_per_block(traffic: dict) -> int:
    """The file's count where it states one (the rehearsal), else the txs
    that fill a block to its weight limit."""
    return traffic.get("txs_per_block") or gen_btc.txs_that_fit(
        traffic["mix"], traffic["block"]["max_weight"])


def backlog(traffic: dict, seconds: float) -> dict:
    """``drivers/ibd.backlog`` over this mix's blocks."""
    b = traffic["backlog"]
    sigs_block = gen_btc.totals(traffic["mix"], txs_per_block(traffic))["sigs"]
    share, ramp = traffic["steady_until_share"], traffic["ramp_seconds"]
    blocks = max(b["min_blocks"], math.ceil(
        b["holds_to_sigs_per_s"] * (seconds + ramp) / (share * sigs_block)))
    steady = share * blocks * sigs_block
    span = harness.capture_seconds(traffic, seconds)
    return {"blocks": blocks, "sigs": blocks * sigs_block,
            "window_holds_to": steady / (seconds + ramp),
            "capture_holds_to": steady / (seconds + ramp - span),
            "measured": b["measured_sigs_per_s"]}


def _counters() -> dict:
    from tpunode.metrics import metrics

    return metrics.snapshot()


class Raw:
    """txid -> the tx's wire bytes, cut out of the served frame."""

    def __init__(self, driver):
        self.d = driver

    def __contains__(self, txid) -> bool:
        return txid in self.d.block_of and txid not in self.d.coinbases

    def __getitem__(self, txid) -> bytes:
        d = self.d
        b = d.block_of[txid]
        i = d.txids[b].index(txid)
        offs = d.offsets[b]
        frame = d.frames[d.hashes[b]]
        return frame[BLOCK_HEAD + offs[i]:BLOCK_HEAD + offs[i + 1]]

    def __iter__(self):
        return (t for ts in self.d.txids for t in ts)

    def __len__(self) -> int:
        return sum(len(ts) for ts in self.d.txids)


class Driver(ibd.Driver):
    def __init__(self, ctx):
        self.ctx = ctx
        self.oracle = Oracle()
        self.remote = WitnessRemote(ctx.config["network"])
        self.offered = harness.Offered({}, {}, Raw(self), self.oracle.p2pk)
        t = ctx.traffic
        self.per_block = txs_per_block(t)
        self.n_blocks = backlog(t, ctx.seconds)["blocks"]
        self.block_of: dict = {}  # txid -> block index
        self.coinbases: set = set()
        self.txids: list = []  # per block, in block order (no coinbase)
        self.offsets: list = []  # per block: array('I') into its body
        self.adversarial: dict = {}  # tx index -> kind
        self.frames: dict = {}
        self.hashes: list = []
        self.first_verdict = None

    async def prepare(self) -> None:
        ctx, t = self.ctx, self.ctx.traffic
        per_job, per = t["blocks_per_job"], self.per_block
        jobs = []
        for lo in range(0, self.n_blocks, per_job):
            n = min(per_job, self.n_blocks - lo)
            jobs.append({"mix": t["mix"], "seed": ctx.seed,
                         "first_tx": lo * per, "count": n * per,
                         "total": self.n_blocks * per, "txs_per_block": per,
                         "first_height": lo + 1})
        parts = await harness.gather_jobs(ctx, gen_btc.blocks_job, jobs)
        bodies, heaviest = [], 0
        for part in parts:
            self.offered.expect.update(zip(part["txids"], part["expect"]))
            self.adversarial.update(part["adversarial"])
            heaviest = max([heaviest] + part["block_weights"])
            for k, body in enumerate(part["bodies"]):
                b = len(bodies)
                txids = part["txids"][k * per:(k + 1) * per]
                self.block_of.update(dict.fromkeys(txids, b))
                self.block_of[body[1]] = b
                self.coinbases.add(body[1])
                self.offered.expect[body[1]] = ()  # a coinbase signs nothing
                self.txids.append(txids)
                self.offsets.append(part["offsets"][k])
                bodies.append(body)
            part.clear()
        headers, self.hashes, self.frames = gen.chain_frames(
            ctx.config["network"], bodies)
        del bodies, parts
        self.remote.offer(headers, self.hashes, self.frames)
        self.totals = gen_btc.totals(t["mix"], self.n_blocks * per)
        self.block_totals = gen_btc.totals(t["mix"], per)
        harness.line("traffic", blocks=self.n_blocks, txs_per_block=per,
                     heaviest_block_weight=heaviest,
                     block_bytes=len(self.frames[self.hashes[0]]) - 24,
                     **self.totals)
        if heaviest > t["block"]["max_weight"]:
            raise SystemExit(f"chipbench: a block weighs {heaviest}")

    async def ramp(self, node, sink) -> None:
        self.base = _counters()  # the node is up, no block is here
        await super().ramp(node, sink)

    async def drain(self, node, sink) -> None:
        """The program's counters over the whole job (ramp, window, drain),
        as the verdicts are compared."""
        await super().drain(node, sink)
        self.counters = {k: v - self.base.get(k, 0)
                         for k, v in _counters().items()}

    def _algorithms_off(self) -> int:
        """How far the device items the engine counted by algorithm lie
        from what the served blocks hold (0 where the program keeps no such
        count: the verdicts are compared either way)."""
        got = {a: self.counters.get(f'verify.items_in{{algo="{a}"}}')
               for a in ("none", "ecdsa", "schnorr", "bip340")}
        if None in got.values():
            return 0
        served, per = set(self.served), self.per_block
        n = len(served)
        absent = sum(kind in AUTO_INVALID for t, kind in self.adversarial.items()
                     if self.hashes[t // per] in served)
        want = {"none": absent, "schnorr": 0,
                "ecdsa": n * self.block_totals["items.ecdsa"],
                "bip340": n * self.block_totals["items.bip340"] - absent}
        return int(sum(abs(got[a] - want[a]) for a in want))

    def extra_checks(self) -> list:
        c = self.counters
        return super().extra_checks() + [
            ("inputs_the_extractor_called_unsupported",
             int(c.get("extract.unsupported_inputs", 0))),
            ("prevout_rows_no_source_answered",
             int(c.get("node.resolve_missing", 0))),
            ("device_items_by_algorithm_off_the_mix", self._algorithms_off()),
            ("blocks_asked_for_without_their_witnesses",
             self.remote.plain_requests),
        ]

    def end_to_end(self, sink, opened, closed) -> tuple:
        e2e, samples = super().end_to_end(sink, opened, closed)
        c = self.counters

        def per(kind: str) -> dict:
            n = c.get(f'extract.digest_inputs{{kind="{kind}"}}', 0)
            s = c.get(f'extract.digest_seconds{{kind="{kind}"}}', 0.0)
            return {"inputs": int(n), "us_per_input": 1e6 * s / n if n else None}

        harness.line(
            "extract", whole_job=True,
            digest={k: per(k) for k in ("legacy", "bip143", "bip341")},
            lift_calls=int(c.get("extract.lift_calls", 0)),
            lift_cache_hits=int(c.get("extract.lift_cache_hits", 0)),
            items_in={a: int(c.get(f'verify.items_in{{algo="{a}"}}', 0))
                      for a in ("none", "ecdsa", "schnorr", "bip340")})
        return e2e, samples
