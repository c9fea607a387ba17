"""Relay on a schedule, open loop, and optionally a block every few seconds
made of what was relayed: a synced node following the tip.

Every relay transaction has a due time drawn before the run (exponential
gaps from the seed, the rate split evenly over the relay peers) and is
written to its peer's socket when that time comes, whatever the node has
answered: nothing waits for a verdict.  Latency runs **from the due
time**, so time a frame spent waiting for the generator counts, and
``late_ms`` (send - due) says how far the generator itself ran behind.

With ``block_every_s`` a further peer pushes block *k* at ``T0 + k *
block_every_s``: in an order drawn from the seed, under a fresh header and
coinbase, every relay transaction due in the interval that ended
``known_lag_s`` before the block and one never-relayed transaction of the
same mix per ``unseen_per_known`` of those.  Headers are offered before the
run as ``drivers/blocks.py`` does; the bodies are built in ``prepare`` (the
schedule is known from the seed).  A block is answered by its last
``TxVerdict``.

The peers share the harness's loop (``peers.py``), so one task walks the
merged schedule and writes each frame to its own peer's socket; the block
peer has a task of its own, so that a block's megabyte never holds a
transaction back.  A transaction's first verdict is taken for its relay
verdict and its second for its block's; where a block overtakes a relay
verdict still in flight the two swap, and the block's completion waits for
both either way.
"""

from __future__ import annotations

import asyncio
import bisect
import random
import time

from chipbench import gen, harness
from chipbench import wirefmt as w
from chipbench.peers import Remote


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.oracle = gen.Oracle()
        self.offered = harness.Offered({}, {}, {}, self.oracle.p2pk)
        self.every = t.get("block_every_s")  # absent: no blocks
        # relay peers push nothing before ``ramp`` says go, so the node may
        # dial them while the engine warms up; a block peer's headers exist
        # only once the traffic is made
        self.CONNECT_EARLY = self.every is None
        self.ramp_blocks = t.get("ramp_blocks", 0) if self.every else 0
        self.length = (t["ramp_seconds"] + ctx.seconds + t["schedule_slack_s"]
                       + self.ramp_blocks * (self.every or 0.0))
        # the schedule: the same number of txs for every seed, each peer's
        # gaps exponential; tx k of the generator's order is the k-th due
        n_peers = t["peers"]
        per_peer = round(t["txs_per_s"] * self.length / n_peers)
        rng = ctx.rng("schedule")
        events = []
        for p in range(n_peers):
            x = 0.0
            for _ in range(per_peer):
                x += rng.expovariate(t["txs_per_s"] / n_peers)
                events.append((x, p))
        events.sort()
        self.due = [x for x, _ in events]  # seconds after T0
        self.peer_of = [p for _, p in events]
        self.n_txs = len(events)
        # which relay txs block k carries (k from 1), and how many unseen
        self.known: list = []  # per block: (first, last+1) of self.due
        self.n_unseen: list = []
        if self.every:
            num, den = t["unseen_per_known"]
            for k in range(1, int(self.length / self.every) + 1):
                lo = bisect.bisect_left(
                    self.due, self.every * (k - 1) - t["known_lag_s"])
                hi = bisect.bisect_left(
                    self.due, self.every * k - t["known_lag_s"])
                self.known.append((lo, hi))
                self.n_unseen.append((hi - lo) * num // den)
        self.n_blocks = len(self.known)
        self.relays = [Remote(ctx.config["network"], on_ready=self._ready(p))
                       for p in range(n_peers)]
        self.block_peer = (Remote(ctx.config["network"],
                                  on_ready=self._block_pump)
                           if self.every else None)
        self.writers: list = [None] * n_peers
        self.ready = 0
        self.all_ready = asyncio.Event()
        self.go = asyncio.Event()
        self.t0 = 0.0
        self.stop = False
        self.pumps_out = 0  # pumps that have seen ``stop`` and left
        # what was sent, and when its first byte went to the socket
        self.frames: list = []
        self.txids: list = []
        self.sent_at: list = [None] * self.n_txs
        self.next = 0
        self.block_frames: list = []
        self.block_sent: list = []  # per block sent: first byte's moment
        # what came back
        self.k_of: dict = {}  # relay txid -> its place in the schedule
        self.count = [0] * self.n_txs  # verdicts so far, per relay tx
        self.block_of_relay = [None] * self.n_txs
        self.block_of_other: dict = {}  # coinbase or unseen txid -> block
        self.need: list = []  # per block: verdicts still due
        self.latency: list = []  # (verdict time, seconds from due): relay
        self.done: list = []  # (block, completion time, seconds from due)
        self.compiles_at_done: list = []

    def remotes(self) -> list:
        return self.relays + ([self.block_peer] if self.block_peer else [])

    # ---- set-up -------------------------------------------------------------

    async def prepare(self) -> None:
        ctx, t = self.ctx, self.ctx.traffic
        magic = int(ctx.config["network"]["magic"], 16)
        jobs = gen.jobs_for(t["mix"], ctx.seed, self.n_txs, t["txs_per_job"])
        relay_jobs = len(jobs)
        unseen_total = sum(self.n_unseen)
        if unseen_total:  # a pool of their own, under another seed
            jobs += gen.jobs_for(t["mix"], ctx.rng("unseen").getrandbits(31),
                                 unseen_total, t["txs_per_job"])
        parts = await harness.gather_jobs(
            ctx, gen.tx_frames_job, [dict(j, magic=magic) for j in jobs])
        unseen: list = []  # (txid, raw)
        for j, part in enumerate(parts):
            self.oracle.p2pk.update(part["p2pk"])
            relayed = j < relay_jobs
            for txid, raw, exp, frame in zip(
                    part["txids"], part["raw"], part["expect"], part["frames"]):
                self.offered.expect[txid] = exp
                self.offered.raw[txid] = raw
                if relayed:
                    self.k_of[txid] = len(self.txids)
                    self.txids.append(txid)
                    self.frames.append(frame)
                else:
                    unseen.append((txid, raw))
        self.totals = gen.totals(t["mix"], self.n_txs)
        if self.every:
            self._build_blocks(unseen)
        harness.line("traffic", peers=len(self.relays), blocks=self.n_blocks,
                     schedule_s=self.length, unseen_txs=unseen_total,
                     block_txs=[hi - lo + u + 1 for (lo, hi), u
                                in zip(self.known, self.n_unseen)],
                     block_bytes=[len(f) for f in self.block_frames],
                     **self.totals)

    def _build_blocks(self, unseen: list) -> None:
        ctx = self.ctx
        bodies, at = [], 0
        for b, ((lo, hi), n_un) in enumerate(zip(self.known, self.n_unseen)):
            txs = [(self.txids[i], self.offered.raw[self.txids[i]])
                   for i in range(lo, hi)] + unseen[at:at + n_un]
            for i in range(lo, hi):
                self.block_of_relay[i] = b
            for txid, _ in unseen[at:at + n_un]:
                self.block_of_other[txid] = b
            at += n_un
            random.Random(f"{ctx.seed}:perm:{b + 1}").shuffle(txs)
            cb = w.coinbase(b + 1)
            cb_txid = w.sha256d(cb)
            self.block_of_other[cb_txid] = b
            self.offered.expect[cb_txid] = ()
            self.need.append(len(txs) + 1)
            bodies.append((
                w.merkle_root([cb_txid] + [txid for txid, _ in txs]), cb_txid,
                w.varint(len(txs) + 1) + cb + b"".join(r for _, r in txs)))
        headers, hashes, frames = gen.chain_frames(ctx.config["network"], bodies)
        self.block_peer.offer(headers, hashes, {})  # pushed, not served
        self.block_frames = [frames[h] for h in hashes]

    # ---- the pumps ----------------------------------------------------------

    def _ready(self, p: int):
        """Every relay peer hands in its writer; the first also carries the
        one task that walks the merged schedule."""
        async def start(writer) -> None:
            self.writers[p] = writer
            self._one_ready()
            if p == 0:
                await self._relay_pump()

        return start

    def _one_ready(self) -> None:
        self.ready += 1
        if self.ready == len(self.remotes()):
            self.all_ready.set()

    async def _sleep_until(self, when: float) -> bool:
        """False once the run has said stop."""
        while not self.stop:
            wait = when - time.monotonic()
            if wait <= 0:
                return True
            await asyncio.sleep(min(wait, 0.25))
        return False

    async def _relay_pump(self) -> None:
        await self.all_ready.wait()
        await self.go.wait()
        try:
            while self.next < self.n_txs:
                i = self.next
                if not await self._sleep_until(self.t0 + self.due[i]):
                    return
                # everything due by now goes in one pass over the loop
                now = time.monotonic()
                while i < self.n_txs and self.t0 + self.due[i] <= now:
                    self.writers[self.peer_of[i]].write(self.frames[i])
                    self.sent_at[i] = now
                    i += 1
                self.next = i
        finally:
            self.pumps_out += 1

    async def _block_pump(self, writer) -> None:
        self._one_ready()
        await self.go.wait()
        try:
            for b, frame in enumerate(self.block_frames):
                if not await self._sleep_until(self.t0 + self.every * (b + 1)):
                    return
                self.block_sent.append(time.monotonic())
                writer.write(frame)
                await writer.drain()
        finally:
            self.pumps_out += 1

    # ---- what comes back ----------------------------------------------------

    def on_verdict(self, txid: bytes, now: float) -> None:
        k = self.k_of.get(txid)
        if k is None:
            b = self.block_of_other.get(txid)
        else:
            c = self.count[k]
            self.count[k] = c + 1
            if c == 0:
                self.latency.append((now, now - self.t0 - self.due[k]))
                return
            b = self.block_of_relay[k] if c == 1 else None
        if b is None:
            return
        self.need[b] -= 1
        if self.need[b] == 0:
            self.done.append(
                (b, now, now - self.t0 - self.every * (b + 1)))
            self.compiles_at_done.append(len(self.ctx.compiles))

    # ---- the run ------------------------------------------------------------

    async def ramp(self, node, sink) -> None:
        await asyncio.wait_for(self.all_ready.wait(), 240)
        if self.every:
            await harness.until(
                lambda: node.chain.get_best().height >= self.n_blocks, 120,
                "header sync")
        self.t0 = time.monotonic() + 0.05
        self.go.set()
        await harness.until(lambda: len(sink.t) > 0, 120, "the first verdict")
        await asyncio.sleep(self.ctx.traffic["ramp_seconds"])

        def warm() -> bool:  # the block path too, with no compilation left
            n = len(self.done)
            return n >= self.ramp_blocks and (
                n < 2 or self.compiles_at_done[-1] == self.compiles_at_done[-2])

        await harness.until(warm, 120, "the ramp blocks")

    def closed_early(self, sink) -> bool:
        return self.next >= self.n_txs  # the schedule ran out

    async def drain(self, node, sink) -> None:
        self.stop = True
        pumps = 1 + (self.block_peer is not None)
        await harness.until(lambda: self.pumps_out >= pumps, 30,
                            "the pumps to stop")
        sent_blocks = len(self.block_sent)
        times = {}
        for i in range(self.n_txs):
            b = self.block_of_relay[i]
            n = (i < self.next) + (b is not None and b < sent_blocks)
            if n:
                times[self.txids[i]] = n
        for txid, b in self.block_of_other.items():
            if b < sent_blocks:
                times[txid] = 1
        self.offered.times = times
        due = sum(times.values())
        await harness.until(lambda: len(sink.t) >= due, 90,
                            f"outstanding verdicts ({len(sink.t)}/{due})")
        self.utxo_behind = 0
        if self.every:
            try:
                await harness.until(lambda: node.utxo.height >= sent_blocks,
                                    60, "the UTXO watermark")
            except SystemExit:
                pass
            self.utxo_behind = max(0, sent_blocks - node.utxo.height)
        self.dedup_hits = node.mempool.stats()["dedup_hits"]

    def extra_checks(self) -> list:
        checks = [("mempool.dedup_hits", int(self.dedup_hits))]
        if self.every:
            checks.append(("utxo_watermark_behind_last_verified",
                           int(self.utxo_behind)))
        return checks

    def end_to_end(self, sink, opened, closed) -> tuple:
        t = self.ctx.traffic
        secs = closed.t - opened.t
        inside = [(ts, n) for ts, n in zip(sink.t, sink.nsigs)
                  if opened.t <= ts < closed.t]
        sigs = sum(n for _, n in inside)
        cpu = closed.cpu - opened.cpu
        lat = [1e3 * d for ts, d in self.latency if opened.t <= ts < closed.t]
        late = [1e3 * (s - self.t0 - d)
                for s, d in zip(self.sent_at[:self.next], self.due)
                if opened.t <= s < closed.t]
        late += [1e3 * (s - self.t0 - self.every * (b + 1))
                 for b, s in enumerate(self.block_sent)
                 if opened.t <= s < closed.t]
        blocks = [1e3 * d for _, ts, d in self.done
                  if opened.t <= ts < closed.t]
        moved = {k: closed.counters.get(k, 0) - opened.counters.get(k, 0)
                 for k in ("node.reuse_lookups", "node.reuse_hits",
                           "node.reuse_pending", "node.reuse_unfit")}

        def spread(values: list) -> dict:
            if not values:
                return {}
            return {"n": len(values), "p50": harness.quantile(values, 0.5),
                    "p90": harness.quantile(values, 0.9),
                    "p99": harness.quantile(values, 0.99), "max": max(values)}

        harness.line(
            "open", window_s=secs, sigs_in_window=sigs,
            verdicts_in_window=len(inside), offered_txs_per_s=t["txs_per_s"],
            relay_sent=self.next, schedule_txs=self.n_txs,
            blocks_sent=len(self.block_sent), blocks_done=len(self.done),
            blocks_in_window=len(blocks), reuse=moved,
            per_second_sigs=harness.per_second_rates(
                sink.t, sink.nsigs, opened.t, closed.t),
            per_second_relay_verdicts=harness.per_second_rates(
                [ts for ts, _ in self.latency], [1] * len(self.latency),
                opened.t, closed.t),
            verdict_ms=spread(lat), block_ms=spread(blocks),
            late_ms=spread(late), block_ms_each=blocks)
        if not lat or not sigs:
            raise SystemExit("chipbench: no relay verdict inside the window")
        if self.every and len(blocks) < 3:
            raise SystemExit("chipbench: under 3 blocks finished in the window")
        e2e = {"sigs_per_s": sigs / secs,
               "verdict_p50_ms": harness.quantile(
                   blocks if self.every else lat, 0.5),
               "host_cpu_ms_per_ksig": cpu * 1e6 / sigs}
        samples = {"verdict_ms": lat, "late_ms": late, "sigs_in_window": sigs}
        if self.every:
            samples["block_ms"] = blocks
        # a cell reports the end-to-end metrics its traffic file lists: one
        # whose sets of runs spread too widely stays a per-layer reading
        return {k: e2e[k] for k in t["end_to_end"]}, samples
