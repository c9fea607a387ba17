"""Big blocks, closed loop: one peer pushes blocks in chain order and keeps
``outstanding`` of them unanswered (one verifying while the next is
received and extracted).  Every block is one block's worth of unique
signed transactions in an order drawn from the seed, under a fresh header
and coinbase.  A block is answered by its last ``TxVerdict``."""

from __future__ import annotations

import math
import os

from chipbench import gen, harness
from chipbench.peers import ClosedLoop, Remote


def backlog(traffic: dict, seconds: float) -> dict:
    """The blocks a run of ``seconds`` is given, from the traffic file alone.

    The window closes early once the last frame is sent.  When it opens,
    the ramp's blocks are done and ``outstanding`` more are sent; from then
    on one is sent for each that is answered, so a node that answers R
    blocks a second sends the last at window second ``left / R``.  Made
    are the blocks a window of ``seconds`` sends at
    ``backlog.holds_to_blocks_per_s``, ``backlog.ramp_allowance_blocks``
    for the ramp (it takes ``ramp_blocks``, and one more for every block
    that still compiled) and ``outstanding``.  Returned beside the count:
    the rate up to which the window runs whole, the rate up to which it is
    still open when a traced run's capture begins, and the rate the file
    says was measured (all in signatures a second)."""
    b = traffic["backlog"]
    sigs_block = gen.totals(traffic["mix"], traffic["txs_per_block"])["sigs"]
    left = math.ceil(b["holds_to_blocks_per_s"] * seconds)
    made = left + b["ramp_allowance_blocks"] + traffic["outstanding"]
    span = harness.capture_seconds(traffic, seconds)
    return {"blocks": made, "sigs": made * sigs_block,
            "window_holds_to": left * sigs_block / seconds,
            "capture_holds_to": left * sigs_block / (seconds - span),
            "measured": b["measured_sigs_per_s"]}


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.oracle = gen.Oracle()
        self.offered = harness.Offered({}, {}, {}, self.oracle.p2pk)
        self.loop = ClosedLoop([], [], t["outstanding"])
        self.remote = Remote(ctx.config["network"], on_ready=self.loop.pump)
        self.n_txs = t["txs_per_block"]
        self.n_blocks = backlog(t, ctx.seconds)["blocks"]
        self.counts: dict = {}  # txid -> verdicts so far
        self.have: list = []  # per block: how many txids have reached it
        self.coinbase: dict = {}  # coinbase txid -> block
        self.cb_seen: set = set()
        self.done: list = []  # (block, completion time, process CPU then)
        self.compiles_at_done: list = []

    def remotes(self) -> list:
        return [self.remote]

    async def prepare(self) -> None:
        ctx, t = self.ctx, self.ctx.traffic
        parts = await harness.gather_jobs(ctx, gen.gen_job, gen.jobs_for(
            t["mix"], ctx.seed, self.n_txs, t["txs_per_job"]))
        raws, txids = [], []
        for part in parts:
            self.oracle.p2pk.update(part["p2pk"])
            raws += part["raw"]
            txids += part["txids"]
            for txid, raw, exp in zip(part["txids"], part["raw"], part["expect"]):
                self.offered.expect[txid] = exp
                self.offered.raw[txid] = raw
        offsets = [0]
        for raw in raws:
            offsets.append(offsets[-1] + len(raw))
        pool_file = os.path.join(ctx.run_dir, "pool.bin")
        with open(pool_file, "wb") as f:
            f.write(b"".join(raws))
        bodies = await harness.gather_jobs(ctx, gen.permuted_body_job, [
            {"pool_file": pool_file, "offsets": offsets, "txids": txids,
             "seed": ctx.seed, "height": h + 1} for h in range(self.n_blocks)])
        headers, hashes, frames = gen.chain_frames(ctx.config["network"], bodies)
        self.remote.offer(headers, hashes, {})  # blocks are pushed, not served
        for k, (h, body) in enumerate(zip(hashes, bodies)):
            self.loop.frames.append(frames[h])
            self.loop.keys.append(k)
            self.coinbase[body[1]] = k
            self.offered.expect[body[1]] = ()
        self.have = [0] * self.n_blocks
        self.totals = gen.totals(t["mix"], self.n_txs)
        harness.line("traffic", blocks=self.n_blocks,
                     block_bytes=len(self.loop.frames[0]), **self.totals)

    def on_verdict(self, txid: bytes, now: float) -> None:
        if txid in self.coinbase:
            self.cb_seen.add(self.coinbase[txid])
            k = self.coinbase[txid]
        else:
            k = self.counts.get(txid, 0)  # its n-th verdict is block n's
            self.counts[txid] = k + 1
            if k >= self.n_blocks:
                return
            self.have[k] += 1
        nxt = len(self.done)
        while (nxt < self.n_blocks and self.have[nxt] == self.n_txs
               and nxt in self.cb_seen):
            self.done.append((nxt, now, harness.cpu_seconds()))
            self.compiles_at_done.append(len(self.ctx.compiles))
            self.loop.answered()
            nxt += 1

    async def ramp(self, node, sink) -> None:
        """The window opens once ``ramp_blocks`` blocks are through and the
        last of them finished with no compilation since the one before:
        every program, mesh programs too, is then warm."""
        want = self.ctx.traffic["ramp_blocks"]
        await harness.until(
            lambda: node.chain.get_best().height >= self.n_blocks, 120,
            "header sync")

        def warm() -> bool:
            n = len(self.done)
            return (n >= want and n >= 2
                    and self.compiles_at_done[-1] == self.compiles_at_done[-2])

        await harness.until(warm, 900, "the ramp blocks")
        if len(self.done) > self.n_blocks // 2:
            raise SystemExit("chipbench: the ramp used up half the backlog")

    def closed_early(self, sink) -> bool:
        return self.loop.next >= len(self.loop.frames)

    async def drain(self, node, sink) -> None:
        self.loop.stop = True
        self.loop.answered()
        sent = len(self.loop.sent)
        await harness.until(lambda: len(self.done) >= sent, 120,
                            f"the outstanding blocks ({len(self.done)}/{sent})")
        self.offered.times = {t: sent for t in self.counts}
        self.offered.times.update(
            {cb: 1 for cb, k in self.coinbase.items() if k < sent})
        try:
            await harness.until(lambda: node.utxo.height >= sent, 60,
                                "the UTXO watermark")
        except SystemExit:
            pass
        self.utxo_height = node.utxo.height
        self.sent = sent

    def extra_checks(self) -> list:
        return [("utxo_watermark_behind_last_verified",
                 max(0, self.sent - self.utxo_height))]

    def end_to_end(self, sink, opened, closed) -> tuple:
        """Rate and CPU over the whole blocks completed inside the window:
        from the first completion in it to the last."""
        inside = [d for d in self.done if opened.t <= d[1] <= closed.t]
        lat = [1e3 * (t - self.loop.sent[k]) for k, t, _ in inside]
        sigs_block = self.totals["sigs"]
        secs = closed.t - opened.t
        sent = [sum(t <= edge.t for t in self.loop.sent.values())
                for edge in (opened, closed)]
        harness.line("blocks", window_s=secs, blocks_in_window=len(inside),
                     blocks_done=len(self.done), blocks_made=self.n_blocks,
                     sent_at_open=sent[0], sent_at_close=sent[1],
                     sigs_per_block=sigs_block, verdict_ms=lat,
                     utxo_height=self.utxo_height)
        if len(inside) < 3:
            raise SystemExit("chipbench: under 3 blocks finished in the window")
        # whole blocks over the time they took: first to last completion
        span = inside[-1][1] - inside[0][1]
        n = len(inside) - 1
        cpu = inside[-1][2] - inside[0][2]
        return ({
            "sigs_per_s": n * sigs_block / span,
            "verdict_p50_ms": harness.quantile(lat, 0.5),
            "host_cpu_ms_per_ksig": cpu * 1e6 / (n * sigs_block),
        }, {"verdict_ms": lat, "sigs_in_window": n * sigs_block,
            "backlog_left_share": [100.0 * (1 - sent[1] / self.n_blocks)]})
