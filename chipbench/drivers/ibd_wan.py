"""Initial block download from a network: ``drivers/ibd.py`` with eight
serving peers behind links (``peers_wan.py``) in place of one on loopback.
Every peer holds the whole chain; each has the round-trip time, the uplink
and the fault the configuration's ``links`` table gives it.  The faults'
moments count from the window's opening.

Everything else is the ``ibd`` driver's, by import: the chain, the
planner's pulls, the window, the statistic, its two checks.  Added: the
network's own reference (``reference_wan.py``) over every link's log, the
longest gap between two verdicts of the window, and how late the links
ran.
"""

from __future__ import annotations

import time

from chipbench import harness, reference_wan
from chipbench.drivers import ibd
from chipbench.peers_wan import WanRemote


backlog = ibd.backlog  # the same chain, sized the same way


class Driver(ibd.Driver):
    CONNECT_EARLY = True  # eight dials take the connect loop ~20 s

    def __init__(self, ctx):
        from tpunode.ibd import IbdConfig

        if "stall_timeout" not in IbdConfig.__dataclass_fields__:
            raise SystemExit(
                "chipbench: this program's fetch planner has no stall "
                "timeout (IbdConfig.stall_timeout): a peer that stops "
                "sending would hold its window for longer than the run, so "
                "it cannot run " + ctx.workload["name"])
        super().__init__(ctx)
        if ctx.rehearsal is not None:
            self._cut_to_a_rehearsal(ctx)
        self.links = ctx.config["links"]
        self.wan = [WanRemote(ctx.config["network"], link,
                              self.links["piece_bytes"])
                    for link in self.links["peers"]]
        self.remote = self.wan[0]  # ibd's prepare() hands it the chain
        self.armed_at = None

    @staticmethod
    def _cut_to_a_rehearsal(ctx) -> None:
        """The traffic file's ``rehearsal`` section over the configuration
        (tests only: the C++ rung verifies too slowly to keep eight real
        uplinks busy, and a test's window is a tenth of a run's)."""
        tiny = ctx.traffic["rehearsal"]
        for link, mbit in zip(ctx.config["links"]["peers"],
                              tiny["uplink_mbit_s"]):
            link["uplink_mbit_s"] = mbit
            if "fault" in link:
                link["fault"]["at_s"] *= tiny["fault_scale"]
        ctx.config["node"]["ibd"]["stall_timeout"] = tiny["stall_timeout"]

    def remotes(self) -> list:
        return self.wan

    async def prepare(self) -> None:
        await super().prepare()
        for r in self.wan[1:]:
            r.offer(*self.remote.held)

    async def ramp(self, node, sink) -> None:
        await harness.until(lambda: all(r.ready for r in self.wan), 120,
                            "every peer's handshake")
        for r in self.wan:
            r.release()
        await super().ramp(node, sink)

    def closed_early(self, sink) -> bool:
        if self.armed_at is None:  # the window has just opened
            self.armed_at = time.monotonic()
            for r in self.wan:
                r.arm(self.armed_at)
        return super().closed_early(sink)

    async def drain(self, node, sink) -> None:
        """Every peer stops serving above the highest block asked of any;
        the node fetches what is missing below it, past the faults, and the
        run ends when all of that prefix is verified and connected."""
        index = self.remote.index
        top = 1 + max(index[h] for r in self.wan for ev in r.log
                      if ev[0] == "request" for h in ev[4])
        keep = {h: f for h, f in self.remote.blocks.items() if index[h] < top}
        for r in self.wan:
            r.blocks = keep
        due = top * (self.per_block + 1)
        await harness.until(
            lambda: len(sink.t) >= due and not any(r.busy for r in self.wan),
            60, f"the first {top} blocks' verdicts ({len(sink.t)}/{due})")
        served = {h for r in self.wan for h in r.served}
        self.served = sorted(served, key=index.get)
        heights = {index[h] + 1 for h in served}
        self.offered.times = {
            t: 1 for t, b in self.block_of.items() if b + 1 in heights}
        self.top = top if heights == set(range(1, top + 1)) else -1
        try:
            await harness.until(lambda: node.utxo.height >= self.top, 30,
                                "the UTXO watermark")
        except SystemExit:
            pass
        self.utxo_height = node.utxo.height
        self.ibd_stats = node.ibd.stats()

    def extra_checks(self) -> list:
        return (super().extra_checks()
                + sorted(self.network.items())
                + [("verdict_gaps_over_the_stall_timeout_plus_1s",
                    int(self.longest_gap > self.gap_limit))])

    def end_to_end(self, sink, opened, closed) -> tuple:
        e2e, samples = super().end_to_end(sink, opened, closed)
        self.network = reference_wan.check(
            self.links["peers"], [r.log for r in self.wan], self.armed_at,
            (opened.t, closed.t))
        inside = [opened.t] + [t for t in sink.t if opened.t <= t <= closed.t]
        inside.append(closed.t)
        self.longest_gap = max(b - a for a, b in zip(inside, inside[1:]))
        self.gap_limit = self.ctx.config["node"]["ibd"]["stall_timeout"] + 1.0
        late = [ms for r in self.wan for t, ms in r.late_ms
                if opened.t <= t <= closed.t]
        harness.line(
            "wan", longest_verdict_gap_s=self.longest_gap,
            stalls=self.ibd_stats.get("stalls"),
            stall_timeout_at_the_end=self.ibd_stats.get("stall_timeout"),
            connections=[r.connections for r in self.wan],
            blocks_by_peer=[len(r.served) for r in self.wan],
            pieces=len(late), **self.network)
        samples.update(verdict_gap_s=[self.longest_gap], wan_late_ms=late)
        return e2e, samples
