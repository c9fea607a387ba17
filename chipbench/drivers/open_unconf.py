"""A synced node whose relay traffic spends unconfirmed outputs:
``drivers/open.py`` — the schedule, the pumps, the ramp, latency from due
times, a pushed block every few seconds — over ``gen_unconf``'s
transactions, with peers that hold what they relay.

What differs from ``open`` is where prevouts come from and the order
transactions arrive in.  Most inputs spend an output of the traffic itself:
of a transaction the node has been handed and no block holds yet (the
mempool answers), of a recent block (the view or the set answers), or of a
transaction that is due only *after* its spender, through another peer —
the spender then waits in the orphan pool, the node asks the peer that sent
it for the parent (``getdata``), and the peer serves it
(``peers_unconf.HoldingRemote``).  A transaction served that way is not
pushed again when its due time comes, and its latency runs from the moment
it was served; a never-pushed block transaction that is served gets a relay
verdict it would not have had, and is owed one more.  The embedder's
callback (``Callback``) answers the funding outpoints and ``None`` for
everything else, and counts what it was asked.  Blocks stand in canonical
order.  The program is asked for the orphan gate by name before any traffic
is made: one that lacks it stops here with a line that says so.

Before the ramp every thread of the node's extract pool runs the node's own
parse job over the first block's bytes twice (``warm_pool``; set-up): a
thread's first parse of a block took 51-129 ms on the chip's machine where
its later ones take 12, and the two of the four first uses that the ramp's
two blocks leave stood among the window's first blocks in every run.

What the reference may know of a prevout is cut out of the parent's raw
bytes (``Prevouts``), for the transactions it is asked about (``Raw``):
never anything the program said.
"""

from __future__ import annotations

import asyncio
import threading
import time

from chipbench import gen, gen_unconf, harness
from chipbench import wirefmt as w
from chipbench.drivers import open as open_loop
from chipbench.peers_unconf import HoldingRemote

# counters the run is held to, or that its detail line reports
WATCHED = ("extract.unsupported_inputs", "node.resolve_missing",
           "mempool.orphaned", "mempool.orphan_resolved",
           "mempool.orphan_evicted", "mempool.orphan_expired",
           "mempool.fetched", "mempool.fetch_failures", "mempool.admitted",
           'mempool.orphan_resolved_by{how="push"}',
           'mempool.orphan_resolved_by{how="fetch"}',
           'mempool.orphan_resolved_by{how="block"}')
# a never-pushed tx served this close to its block's push, or after it, may
# reach the node behind the block: it is then a duplicate, and gets no
# relay verdict
NEAR_ITS_BLOCK_S = 0.25


class Callback:
    """``NodeConfig.prevout_lookup`` of an embedder whose index holds
    confirmed funding outputs and nothing unconfirmed."""

    def __init__(self):
        self.funding: set = set()  # outpoints, 36 bytes
        self.p2pk: dict = {}  # of them, the bare-P2PK ones' scripts
        self.asked = self.answered = self.beyond = 0

    def answer(self, key: bytes, txid: bytes, vout: int):
        if key not in self.funding:
            return None
        return (gen.synth_amount(txid, vout),
                self.p2pk.get(key) or gen.synth_script(txid))

    def __call__(self, txid: bytes, vout: int):
        key = txid + vout.to_bytes(4, "little")
        res = self.answer(key, txid, vout)
        self.asked += 1
        if res is not None:
            self.answered += 1
            self.beyond += key not in self.funding
        return res


class Raw(dict):
    """txid -> the tx's wire bytes; what the reference asked for (``[]``)
    is remembered, for ``Prevouts``; the driver's own reads go by
    ``get``."""

    def __init__(self):
        super().__init__()
        self.asked: list = []

    def __getitem__(self, txid) -> bytes:
        raw = super().__getitem__(txid)
        self.asked.append(raw)
        return raw


class Prevouts:
    """The reference's prevout table, made when it is pickled for a worker:
    outpoint -> ``(amount, script)`` for every input of the txs ``Raw`` was
    asked for that spends a tx of the traffic, cut from the parent's raw
    bytes; outpoint -> script for a bare-P2PK funding outpoint.  What is in
    neither is a funding outpoint and a function of itself."""

    def __init__(self, raw: Raw, callback: Callback):
        self.raw, self.callback = raw, callback

    def table(self) -> dict:
        out = {}
        for raw in self.raw.asked:
            (_, ins, _, _), _ = w.parse_tx(raw)
            for txid, vout, _, _ in ins:
                key = txid + vout.to_bytes(4, "little")
                if txid in self.raw:
                    (_, _, outs, _), _ = w.parse_tx(self.raw.get(txid))
                    out[key] = outs[vout]
                elif key in self.callback.p2pk:
                    out[key] = self.callback.p2pk[key]
        return out

    def __reduce__(self):
        return dict, (self.table(),)


class Driver(open_loop.Driver):
    def __init__(self, ctx):
        from tpunode import mempool

        if not hasattr(mempool.Mempool, "orphaned"):
            raise SystemExit(
                "chipbench: this program has no tpunode.mempool.Mempool."
                "orphaned: on a FORKID network its mempool parks no "
                "transaction for a missing parent, so a child that arrives "
                "before its parent would be verified by nothing there (and "
                "published valid), and it cannot run " + ctx.workload["name"])
        super().__init__(ctx)
        self.oracle = Callback()
        raw = Raw()
        self.offered = harness.Offered({}, {}, raw, Prevouts(raw, self.oracle))
        net = ctx.config["network"]
        self.relays = [HoldingRemote(net, self.serve, on_ready=r.on_ready)
                       for r in self.relays]
        self.n_unseen_all = sum(self.n_unseen)
        self.g_of: dict = {}  # txid -> its number (relay first, then unseen)
        self.all_frames: list = []
        self.unseen_block: list = []  # never-pushed tx -> its block
        self.unseen_served: list = [None] * self.n_unseen_all
        self.unseen_seen = [0] * self.n_unseen_all  # verdicts so far
        self.relay_first: set = set()  # never-pushed, relay verdict first
        self.unsure: set = set()  # never-pushed, served near its block
        self.sent_relay = 0  # relayed txs handed over, pushed or served
        self.served = {"relay": 0, "unseen": 0, "again": 0, "unknown": 0}
        self.block_size: list = []  # per block: its txs, the coinbase too
        self.first_region = (b"", 0)  # block 1's tx region and its tx count
        self.plan: dict = {}
        self.moved: dict = {}

    # ---- set-up -------------------------------------------------------------

    async def prepare(self) -> None:
        ctx, t = self.ctx, self.ctx.traffic
        magic = int(ctx.config["network"]["magic"], 16)
        # every tx's time: a relayed one's due time, a never-pushed one's a
        # moment of the interval its block covers
        rng = ctx.rng("unseen times")
        times, peers = list(self.due), list(self.peer_of)
        block = [None] * self.n_txs
        for b, ((lo, hi), n_un) in enumerate(zip(self.known, self.n_unseen)):
            block[lo:hi] = [b] * (hi - lo)
            start = self.every * b - t["known_lag_s"]
            times += sorted(start + rng.random() * self.every
                            for _ in range(n_un))
            self.unseen_block += [b] * n_un
        peers += [-1] * self.n_unseen_all
        block += self.unseen_block
        parts = await harness.gather_jobs(
            ctx, gen_unconf.strand_job,
            gen_unconf.jobs_for(t, ctx.seed, magic, times, peers, block,
                                self.n_txs))
        total = self.n_txs + self.n_unseen_all
        txids, raws = [None] * total, [None] * total
        self.all_frames = [None] * total
        drawn, got = {}, {}
        for part in parts:
            for g, raw, txid, exp, frame in zip(
                    part["g"], part["raw"], part["txids"], part["expect"],
                    part["frames"]):
                txids[g], raws[g], self.all_frames[g] = txid, raw, frame
                self.offered.expect[txid] = exp
                self.offered.raw[txid] = raw
            f = part["funding"]
            self.oracle.funding.update(f[i:i + 36] for i in range(0, len(f), 36))
            self.oracle.p2pk.update(part["p2pk"])
            for into, counts in ((drawn, part["drawn"]), (got, part["got"])):
                for k, n in counts.items():
                    into[k] = into.get(k, 0) + n
        self.plan = {"drawn": drawn, "got": got,
                     "waits": sum(p["waits"] for p in parts),
                     "depth": max(p["depth"] for p in parts),
                     "small_fee_txs": sum(p["small_fee"] for p in parts)}
        self.g_of = {txid: g for g, txid in enumerate(txids)}
        self.txids, self.frames = txids[:self.n_txs], self.all_frames[:self.n_txs]
        self.k_of = {txid: k for k, txid in enumerate(self.txids)}
        self.totals = gen.totals(t["mix"], total)
        self._build_blocks(list(zip(txids[self.n_txs:], raws[self.n_txs:])))
        harness.line("traffic", peers=len(self.relays), blocks=self.n_blocks,
                     schedule_s=self.length, unseen_txs=self.n_unseen_all,
                     block_txs=[hi - lo + u + 1 for (lo, hi), u
                                in zip(self.known, self.n_unseen)],
                     block_bytes=[len(f) for f in self.block_frames],
                     funding_outpoints=len(self.oracle.funding),
                     prevouts=self.plan, **self.totals)

    def _build_blocks(self, unseen: list) -> None:
        """``open``'s blocks in canonical order: the coinbase, then
        ascending by txid."""
        ctx = self.ctx
        bodies, at = [], 0
        for b, ((lo, hi), n_un) in enumerate(zip(self.known, self.n_unseen)):
            txs = gen_unconf.canonical(
                [(self.txids[i], self.offered.raw.get(self.txids[i]))
                 for i in range(lo, hi)] + unseen[at:at + n_un])
            for i in range(lo, hi):
                self.block_of_relay[i] = b
            for txid, _ in unseen[at:at + n_un]:
                self.block_of_other[txid] = b
            at += n_un
            cb = w.coinbase(b + 1)
            cb_txid = w.sha256d(cb)
            self.block_of_other[cb_txid] = b
            self.offered.expect[cb_txid] = ()
            self.need.append(len(txs) + 1)
            self.block_size.append(len(txs) + 1)
            region = cb + b"".join(r for _, r in txs)
            if b == 0:
                self.first_region = (region, len(txs) + 1)
            bodies.append((
                w.merkle_root([cb_txid] + [txid for txid, _ in txs]), cb_txid,
                w.varint(len(txs) + 1) + region))
        headers, hashes, frames = gen.chain_frames(ctx.config["network"], bodies)
        self.block_peer.offer(headers, hashes, {})  # pushed, not served
        self.block_frames = [frames[h] for h in hashes]

    # ---- the pumps ----------------------------------------------------------

    def serve(self, txid: bytes):
        """A peer was asked for ``txid``: its frame, and it is not pushed
        again."""
        g = self.g_of.get(txid)
        if g is None:
            self.served["unknown"] += 1
            return None
        now = time.monotonic()
        if g < self.n_txs:
            if self.sent_at[g] is None:
                self.sent_at[g] = now
                self.due[g] = now - self.t0  # its latency runs from here
                self.sent_relay += 1
                self.served["relay"] += 1
            else:
                self.served["again"] += 1
        else:
            u = g - self.n_txs
            if self.unseen_served[u] is None:
                self.unseen_served[u] = now
                self.served["unseen"] += 1
                push = self.t0 + self.every * (self.unseen_block[u] + 1)
                if now > push - NEAR_ITS_BLOCK_S:
                    self.unsure.add(txid)
            else:
                self.served["again"] += 1
        return self.all_frames[g]

    async def _relay_pump(self) -> None:
        await self.all_ready.wait()
        await self.go.wait()
        try:
            while self.next < self.n_txs:
                i = self.next
                if not await self._sleep_until(self.t0 + self.due[i]):
                    return
                # everything due by now goes in one pass over the loop,
                # but what a peer has served already
                now = time.monotonic()
                while i < self.n_txs and (self.sent_at[i] is not None
                                          or self.t0 + self.due[i] <= now):
                    if self.sent_at[i] is None:
                        self.writers[self.peer_of[i]].write(self.frames[i])
                        self.sent_at[i] = now
                        self.sent_relay += 1
                    i += 1
                self.next = i
        finally:
            self.pumps_out += 1

    # ---- what comes back ----------------------------------------------------

    def on_verdict(self, txid: bytes, now: float) -> None:
        g = self.g_of.get(txid)
        if g is None or g < self.n_txs:
            return super().on_verdict(txid, now)  # relayed, or a coinbase
        u = g - self.n_txs
        seen = self.unseen_seen[u]
        self.unseen_seen[u] = seen + 1
        if seen == 0 and self.unseen_served[u] is not None:
            # served before any verdict: the first is taken for its relay
            # verdict, the second for its block's, as for a relayed tx
            self.relay_first.add(u)
            self.latency.append((now, now - self.unseen_served[u]))
            return
        if seen == 0 or (seen == 1 and u in self.relay_first):
            super().on_verdict(txid, now)  # its block's

    # ---- the run ------------------------------------------------------------

    def _counters(self) -> dict:
        from tpunode.metrics import metrics

        snap = metrics.snapshot()
        return {k: snap.get(k, 0) for k in WATCHED}

    async def warm_pool(self, node) -> None:
        """Every thread of the node's extract pool runs the parse job of a
        block (the region, its UTXO delta, its wire hashes; nothing is
        published) over the first block's bytes, twice, one thread after
        the other: what a thread's first block parse pays is paid here, in
        set-up, and the detail line says what it was.  A program whose pool
        or parse job go by other names is not warmed, and the line says
        so."""
        from tpunode import node as program

        raw, n_txs = self.first_region
        try:
            pool, parse = node._extract_pool, program._parse_region
            n = pool._max_workers
        except AttributeError as e:
            harness.line("pool_warmup", threads=0, missing=str(e))
            return
        here = threading.Barrier(n)  # n jobs at once: n threads
        turn = threading.Lock()

        def job() -> tuple:
            here.wait(30)
            ms = []
            with turn:
                for _ in range(2):
                    t = time.perf_counter()
                    parse(raw, n_txs, True, True)[0].close()
                    ms.append(1e3 * (time.perf_counter() - t))
            return threading.current_thread().name, ms

        loop = asyncio.get_running_loop()
        out = await asyncio.gather(
            *(loop.run_in_executor(pool, job) for _ in range(n)))
        harness.line("pool_warmup", threads=len({name for name, _ in out}),
                     region_txs=n_txs, region_bytes=len(raw),
                     first_ms=[ms[0] for _, ms in out],
                     second_ms=[ms[1] for _, ms in out])

    async def ramp(self, node, sink) -> None:
        await asyncio.wait_for(self.all_ready.wait(), 240)
        await self.warm_pool(node)
        self.before = self._counters()
        await super().ramp(node, sink)

    def _owed(self, sent_blocks: int) -> dict:
        """txid -> the verdicts it is owed."""
        times = {}
        for i, txid in enumerate(self.txids):
            b = self.block_of_relay[i]
            n = (self.sent_at[i] is not None) + (b is not None and b < sent_blocks)
            if n:
                times[txid] = n
        for txid, b in self.block_of_other.items():
            g = self.g_of.get(txid)
            n = (b < sent_blocks) + (
                g is not None and self.unseen_served[g - self.n_txs] is not None)
            if n:
                times[txid] = n
        return times

    async def drain(self, node, sink) -> None:
        self.stop = True
        await harness.until(lambda: self.pumps_out >= 2, 30, "the pumps to stop")
        sent_blocks = len(self.block_sent)
        in_blocks = sum(self.block_size[:sent_blocks])

        def quiet() -> bool:
            # a child handed over whose parent was not is parked, asks for
            # it and is served: the peers answer to the end
            due = (self.sent_relay + self.served["unseen"] + in_blocks
                   - len(self.unsure))
            return node.mempool.orphan_count() == 0 and len(sink.t) >= due

        for _ in range(2):  # an orphan that resolves may park once more
            await harness.until(quiet, 90, "outstanding verdicts and orphans")
            await asyncio.sleep(0.3)
        got = {}
        for txid in sink.txids:
            got[txid] = got.get(txid, 0) + 1
        times = self._owed(sent_blocks)
        for txid in self.unsure & times.keys():
            # behind its block it is a duplicate: one verdict or two
            times[txid] = min(times[txid], max(times[txid] - 1, got.get(txid, 0)))
        self.offered.times = times
        self.utxo_behind = 0
        try:
            await harness.until(lambda: node.utxo.height >= sent_blocks, 60,
                                "the UTXO watermark")
        except SystemExit:
            pass
        self.utxo_behind = max(0, sent_blocks - node.utxo.height)
        self.dedup_hits = node.mempool.stats()["dedup_hits"]
        self.orphans_left = node.mempool.orphan_count()
        after = self._counters()
        self.moved = {k: int(after[k] - self.before[k]) for k in WATCHED}

    def extra_checks(self) -> list:
        m = self.moved
        return [
            # a tx reaches the node twice only where a peer served what had
            # been handed over already
            ("mempool.dedup_hits_beyond_txs_served_again",
             max(0, int(self.dedup_hits) - self.served["again"]
                 - len(self.unsure))),
            ("utxo_watermark_behind_last_verified", int(self.utxo_behind)),
            ("inputs_the_extractor_left_out", m["extract.unsupported_inputs"]),
            ("prevout_rows_no_source_answered", m["node.resolve_missing"]),
            ("orphans_that_left_the_pool_unresolved",
             m["mempool.orphan_evicted"] + m["mempool.orphan_expired"]),
            ("orphans_left_after_the_drain", int(self.orphans_left)),
            ("callback_answers_beyond_its_funding_outpoints",
             self.oracle.beyond),
            ("getdata_for_txs_the_traffic_does_not_hold",
             self.served["unknown"]),
        ]

    def end_to_end(self, sink, opened, closed) -> tuple:
        e2e, samples = super().end_to_end(sink, opened, closed)
        if "verdict_p50_ms" in e2e:
            # of the relay verdicts, from their due times, as ``relay-open``
            # reports it: the orphan pool and the mempool's answers act on
            # these, and a window holds some 59,000 of them where it holds
            # 20 blocks, whose median stays ``tip.block_verdict_p50_ms``
            e2e["verdict_p50_ms"] = harness.quantile(samples["verdict_ms"], 0.5)
        m = self.moved
        harness.line(
            "unconf", prevouts=self.plan, served=self.served,
            served_near_their_block=len(self.unsure),
            callback={"asked": self.oracle.asked,
                      "answered": self.oracle.answered,
                      "none": self.oracle.asked - self.oracle.answered},
            orphaned=m["mempool.orphaned"],
            resolved=m["mempool.orphan_resolved"],
            resolved_by={h: m[f'mempool.orphan_resolved_by{{how="{h}"}}']
                         for h in ("push", "fetch", "block")},
            fetched=m["mempool.fetched"],
            fetch_failures=m["mempool.fetch_failures"],
            admitted=m["mempool.admitted"])
        return e2e, samples
