"""Mempool relay, closed loop: a few peers each push transactions and keep
at most ``outstanding`` of them unanswered by a verdict.  No transaction
repeats, so neither the verdict cache nor the mempool's dedup sees one
twice.  Latency runs from the frame's write to its verdict on the bus."""

from __future__ import annotations

import asyncio
import math

from chipbench import gen, harness
from chipbench.peers import ClosedLoop, Remote


class Driver:
    CONNECT_EARLY = True  # the peers push nothing before ``ramp`` says go

    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.oracle = gen.Oracle()
        self.offered = harness.Offered({}, {}, {}, self.oracle.p2pk)
        self.loops = [ClosedLoop([], [], t["outstanding_per_peer"])
                      for _ in range(t["peers"])]
        self.peers = [Remote(ctx.config["network"], on_ready=self._pump(lp))
                      for lp in self.loops]
        self.ready = 0
        self.all_ready = asyncio.Event()
        self.go = asyncio.Event()
        self.loop_of: dict = {}
        self.latency: list = []  # (verdict time, seconds)
        self.n_txs = math.ceil(
            t["pool"]["parent_txs_per_s"] * t["pool"]["factor"]
            * (ctx.seconds + t["ramp_seconds"])) + t["pool"]["extra_txs"]

    def remotes(self) -> list:
        return self.peers

    def _pump(self, lp):
        """The node dials its peers one by one (a jittered loop): no peer
        pushes before all are connected, so the loops start together."""
        async def start(writer) -> None:
            self.ready += 1
            if self.ready == len(self.loops):
                self.all_ready.set()
            await self.go.wait()
            await lp.pump(writer)

        return start

    async def prepare(self) -> None:
        ctx, t = self.ctx, self.ctx.traffic
        magic = int(ctx.config["network"]["magic"], 16)
        jobs = [dict(j, magic=magic) for j in gen.jobs_for(
            t["mix"], ctx.seed, self.n_txs, t["txs_per_job"])]
        k = 0
        for part in await harness.gather_jobs(ctx, gen.tx_frames_job, jobs):
            self.oracle.p2pk.update(part["p2pk"])
            for txid, raw, exp, frame in zip(
                    part["txids"], part["raw"], part["expect"], part["frames"]):
                lp = self.loops[k % len(self.loops)]
                lp.frames.append(frame)
                lp.keys.append(txid)
                self.loop_of[txid] = lp
                self.offered.expect[txid] = exp
                self.offered.raw[txid] = raw
                k += 1
        self.totals = gen.totals(t["mix"], self.n_txs)
        harness.line("traffic", peers=len(self.loops), **self.totals)

    def on_verdict(self, txid: bytes, now: float) -> None:
        lp = self.loop_of.get(txid)
        if lp is not None:
            self.latency.append((now, now - lp.sent.get(txid, now)))
            lp.answered()

    async def ramp(self, node, sink) -> None:
        await asyncio.wait_for(self.all_ready.wait(), 240)
        self.go.set()
        await harness.until(lambda: len(sink.t) > 0, 120, "the first verdict")
        await asyncio.sleep(self.ctx.traffic["ramp_seconds"])

    def closed_early(self, sink) -> bool:
        return any(lp.next >= len(lp.frames) for lp in self.loops)

    async def drain(self, node, sink) -> None:
        for lp in self.loops:
            lp.stop = True
            lp.answered()  # wake a pump waiting for a slot
        due = sum(len(lp.sent) for lp in self.loops)
        await harness.until(lambda: len(sink.t) >= due, 60,
                            f"outstanding verdicts ({len(sink.t)}/{due})")
        self.offered.times = {t: 1 for lp in self.loops for t in lp.sent}
        self.dedup_hits = node.mempool.stats()["dedup_hits"]

    def extra_checks(self) -> list:
        return [("mempool.dedup_hits", int(self.dedup_hits))]

    def end_to_end(self, sink, opened, closed) -> tuple:
        secs = closed.t - opened.t
        sigs = sum(n for t, n in zip(sink.t, sink.nsigs)
                   if opened.t <= t < closed.t)
        lat = [1e3 * d for t, d in self.latency if opened.t <= t < closed.t]
        rates = harness.per_second_rates(sink.t, sink.nsigs, opened.t, closed.t)
        harness.line("relay", window_s=secs, sigs_in_window=sigs,
                     verdicts_in_window=len(lat), per_second_sigs=rates,
                     offered_txs=sum(len(lp.sent) for lp in self.loops),
                     pool_txs=self.n_txs,
                     p50_ms=harness.quantile(lat, 0.5),
                     p90_ms=harness.quantile(lat, 0.9),
                     p99_ms=harness.quantile(lat, 0.99),
                     max_ms=max(lat),
                     verdicts_per_50ms_of_latency=[
                         sum(50 * k <= x < 50 * (k + 1) for x in lat)
                         for k in range(int(max(lat) // 50) + 1)][:60])
        out = {"sigs_per_s": sigs / secs,
               "verdict_p50_ms": harness.quantile(lat, 0.5)}
        # the tail is a per-layer reading of these samples: in a closed
        # loop it swings with how many rounds took two lanes (PERF.md)
        return out, {"verdict_ms": lat, "sigs_in_window": sigs}
