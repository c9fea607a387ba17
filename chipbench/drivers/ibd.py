"""Initial block download: one peer holds a chain, the node's own planner
fetches it.  The chain is a backlog sized to outlast the window, and the
rate is every signature of a steady interval of it over all of that
interval's time: no ramp-up, no drain, and a stall inside it shows."""

from __future__ import annotations

import asyncio
import math
import statistics

from chipbench import gen, harness
from chipbench.peers import Remote


def backlog(traffic: dict, seconds: float) -> dict:
    """The chain a run of ``seconds`` is given, from the traffic file alone.

    The window opens ``ramp_seconds`` after the first verdict and closes
    early once ``steady_until_share`` of the chain's verdicts are out, so a
    node that verifies R signatures a second closes it at window second
    ``share * chain / R - ramp``.  The chain is the shortest whose window
    runs its ``seconds`` at ``backlog.holds_to_sigs_per_s``.  Returned
    beside its length: the rate up to which the window runs whole, the rate
    up to which it is still open when a traced run's capture begins, and
    the rate the file says was measured (all in signatures a second)."""
    b = traffic["backlog"]
    sigs_block = gen.totals(traffic["mix"], traffic["txs_per_block"])["sigs"]
    share, ramp = traffic["steady_until_share"], traffic["ramp_seconds"]
    blocks = max(b["min_blocks"], math.ceil(
        b["holds_to_sigs_per_s"] * (seconds + ramp) / (share * sigs_block)))
    steady = share * blocks * sigs_block
    span = harness.capture_seconds(traffic, seconds)
    return {"blocks": blocks, "sigs": blocks * sigs_block,
            "window_holds_to": steady / (seconds + ramp),
            "capture_holds_to": steady / (seconds + ramp - span),
            "measured": b["measured_sigs_per_s"]}


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.oracle = gen.Oracle()
        self.remote = Remote(ctx.config["network"])
        self.offered = harness.Offered({}, {}, {}, self.oracle.p2pk)
        t = ctx.traffic
        self.per_block = t["txs_per_block"]
        self.n_blocks = backlog(t, ctx.seconds)["blocks"]
        self.block_of: dict = {}  # txid -> height
        self.first_verdict = None

    def remotes(self) -> list:
        return [self.remote]

    async def prepare(self) -> None:
        ctx, t = self.ctx, self.ctx.traffic
        per_job = t["blocks_per_job"]
        jobs = []
        for lo in range(0, self.n_blocks, per_job):
            n = min(per_job, self.n_blocks - lo)
            jobs.append({"mix": t["mix"], "seed": ctx.seed,
                         "first_tx": lo * self.per_block,
                         "count": n * self.per_block,
                         "total": self.n_blocks * self.per_block,
                         "txs_per_block": self.per_block,
                         "first_height": lo + 1})
        parts = await harness.gather_jobs(ctx, gen.block_bodies_job, jobs)
        bodies = []
        for part in parts:
            # whole-column updates: 0.8M txs a chain, on the loop the
            # engine's warm-up shares
            self.oracle.p2pk.update(part["p2pk"])
            self.offered.expect.update(zip(part["txids"], part["expect"]))
            self.offered.raw.update(zip(part["txids"], part["raw"]))
            first = part["first_tx"] // self.per_block
            self.block_of.update(zip(part["txids"], (
                b for b in range(first, first + len(part["bodies"]))
                for _ in range(self.per_block))))
            for body in part["bodies"]:
                self.block_of[body[1]] = len(bodies)
                self.offered.expect[body[1]] = ()  # a coinbase signs nothing
                bodies.append(body)
        self.remote.offer(*gen.chain_frames(ctx.config["network"], bodies))
        self.totals = gen.totals(t["mix"], self.n_blocks * self.per_block)
        harness.line("traffic", blocks=self.n_blocks, **self.totals)

    def on_verdict(self, txid: bytes, now: float) -> None:
        if self.first_verdict is None:
            self.first_verdict = now

    async def ramp(self, node, sink) -> None:
        await harness.until(
            lambda: node.chain.get_best().height >= self.n_blocks, 120,
            "header sync")
        await harness.until(lambda: self.first_verdict is not None, 120,
                            "the first block's verdict")
        await asyncio.sleep(self.ctx.traffic["ramp_seconds"])

    def closed_early(self, sink) -> bool:
        share = self.ctx.traffic["steady_until_share"]
        return len(sink.t) >= share * len(self.offered.expect)

    async def drain(self, node, sink) -> None:
        """Stop serving, then wait for every served block's verdicts and
        for the UTXO watermark."""
        served, self.remote.blocks = list(self.remote.served), {}
        self.served = served
        due = len(served) * (self.per_block + 1)
        await harness.until(lambda: len(sink.t) >= due, 60,
                            f"the served blocks' verdicts ({len(sink.t)}/{due})")
        heights = {self.remote.index[h] + 1 for h in served}
        self.offered.times = {
            t: 1 for t, b in self.block_of.items() if b + 1 in heights}
        self.top = max(heights) if heights == set(
            range(1, len(heights) + 1)) else -1
        try:
            await harness.until(lambda: node.utxo.height >= self.top, 30,
                                "the UTXO watermark")
        except SystemExit:
            pass
        self.utxo_height = node.utxo.height
        self.ibd_stats = node.ibd.stats()

    def extra_checks(self) -> list:
        return [
            ("served_blocks_not_a_prefix", int(self.top < 0)),
            ("utxo_watermark_behind_last_verified",
             max(0, self.top - self.utxo_height)),
        ]

    def end_to_end(self, sink, opened, closed) -> tuple:
        """-> (metrics, samples)."""
        secs = closed.t - opened.t
        bins = harness.per_second_rates(sink.t, sink.nsigs, opened.t, closed.t)
        in_window = sum(n for t, n in zip(sink.t, sink.nsigs)
                        if opened.t <= t < closed.t)
        whole = sum(sink.nsigs) / (sink.t[-1] - sink.t[0])
        harness.line(
            "ibd", window_s=secs, per_second_sigs=bins,
            median_of_per_second_sigs=statistics.median(bins),
            sigs_in_window=in_window,
            whole_job_sigs_per_s=whole, verdicts=len(sink.t),
            chain_share_verified=len(sink.t) / len(self.offered.expect),
            served_blocks=len(self.served), utxo_height=self.utxo_height,
            refetches=self.ibd_stats.get("refetches"),
            first_verdict_after_s=self.first_verdict - self.ctx.t_start)
        if secs < 3:
            raise SystemExit("chipbench: the steady interval is under 3 s")
        return ({
            "sigs_per_s": in_window / secs,
            "host_cpu_ms_per_ksig":
                (closed.cpu - opened.cpu) * 1e6 / in_window,
        }, {"sigs_in_window": in_window, "blocks_in_window":
            in_window / (self.totals["sigs"] / self.n_blocks),
            "backlog_left_share": [100.0 * (
                1 - closed.n_verdicts / len(self.offered.expect))]})
