"""A block's shards are independent chains (ISSUE 32): each extract job's
items go to the engine the moment THAT job is out of the pool, so a big
block's first verdicts are on the bus while its last shard is still being
extracted — and what must not change with that: exactly one ``TxVerdict``
a tx whatever fails or is cancelled on the way, the verdicts themselves,
the UTXO connect after the last of them, the region closed once, and
``mempool.confirmed`` ahead of every verdict of the engine's.

Through ``tests/test_verdict_reuse.py``'s node, peer and blocks; the pool's
jobs are gated with events, so nothing here waits on a clock to order
things.
"""

from __future__ import annotations

import asyncio
import threading
import types

import pytest

from chipbench import gen
from tests.fakenet import poll_until
from tests.test_verdict_reuse import MIX, a_node, block_of, tuples
from tpunode import node as node_mod
from tpunode.mempool import MempoolConfig
from tpunode.metrics import metrics
from tpunode.peer import PeerMessage
from tpunode.txextract import ParsedTxRegion
from tpunode.wire import MsgBlock

N_TXS = 90  # the block's txs beside its coinbase: 91 to verify


def a_block(seed: int = 32):
    job = gen.gen_job(gen.jobs_for(MIX, seed, N_TXS, N_TXS)[0])
    oracle = gen.Oracle()
    oracle.p2pk.update(job["p2pk"])
    blk = block_of(job["raw"])
    order = [tx.txid for tx in blk.txs]
    assert order[1:] == job["txids"]
    return blk, oracle, dict(zip(job["txids"], job["expect"])), order


def cut_into(monkeypatch, k: int) -> None:
    monkeypatch.setattr(node_mod.Node, "_n_extract_jobs", lambda self, n: k)


class Jobs:
    """The extract pool's jobs, gated: the job whose range starts at
    ``boom`` waits for ``fail`` and raises; any other that starts at or
    after ``hold_from`` waits for ``release``.  Every region's real closes
    are counted."""

    def __init__(self, monkeypatch, hold_from: int, boom: int | None = None):
        self.release, self.fail = threading.Event(), threading.Event()
        self.started: list = []
        self.closes = 0
        plain, close = ParsedTxRegion.extract_range, ParsedTxRegion.close
        jobs = self

        def gated(self, lo, hi, **kw):
            jobs.started.append(lo)
            if lo == boom:
                assert jobs.fail.wait(60)
                raise ValueError("boom")
            if lo >= hold_from:
                assert jobs.release.wait(60)
            return plain(self, lo, hi, **kw)

        def counted(self):
            jobs.closes += bool(self._h)
            close(self)

        monkeypatch.setattr(ParsedTxRegion, "extract_range", gated)
        monkeypatch.setattr(ParsedTxRegion, "close", counted)


@pytest.fixture
def gate(monkeypatch):
    made = []

    def make(hold_from: int, boom: int | None = None) -> Jobs:
        made.append(Jobs(monkeypatch, hold_from, boom))
        return made[-1]

    yield make
    for jobs in made:  # a failed assertion leaves no thread waiting
        jobs.release.set()
        jobs.fail.set()


def offer(d, blk) -> None:
    d.node._peer_pub.publish(PeerMessage(d.peer, MsgBlock(blk)))


async def settled(d, n: int) -> list:
    """``n`` verdicts on the bus, and no more a moment later."""
    await poll_until(lambda: len(d.verdicts) >= n, what=f"{n} verdicts")
    await asyncio.sleep(0.1)
    assert len(d.verdicts) == n
    return list(d.verdicts)


@pytest.mark.asyncio
@pytest.mark.parametrize("shards", [1, 2, 4, 9])
async def test_a_block_cut_into_shards_publishes_the_unsharded_verdicts(
        shards, monkeypatch):
    blk, oracle, expect, order = a_block()
    seen = {}
    async with asyncio.timeout(120):
        for port, k in ((17940, 1), (17941, shards)):
            cut_into(monkeypatch, k)
            s0 = metrics.get("node.stream_items")
            async with a_node(oracle=oracle, port=port) as d:
                got, subs, _ = await d.block(blk)
            assert len(subs) == k  # one engine submission a shard
            assert sum(len(txids) for _, _, txids in subs) == len(order)
            assert metrics.get("node.stream_items") - s0 == sum(
                s[1] for s in subs) == gen.totals(MIX, N_TXS)["items"]
            seen[k] = got
    assert tuples(seen[shards]) == tuples(seen[1])
    assert [v.txid for v in seen[shards]] == order  # one a tx, each once
    for v in seen[shards][1:]:
        assert v.error is None and tuple(v.verdicts) == expect[v.txid]
        assert v.valid == all(v.verdicts)


@pytest.mark.asyncio
async def test_the_first_shards_verdicts_are_out_while_the_last_job_is_held(
        monkeypatch, gate):
    blk, oracle, expect, order = a_block(33)
    cut_into(monkeypatch, 4)  # 91 txs: runs of 23, the last from 69
    early0 = metrics.get("node.stream_early_items")
    all0 = metrics.get("node.stream_items")
    jobs = gate(hold_from=69)
    async with asyncio.timeout(120):
        async with a_node(oracle=oracle, utxo=True, port=17942) as d:
            d.node.chain.headers(d.peer, [blk.header])
            await poll_until(
                lambda: d.node.chain.get_block(blk.header.hash) is not None,
                what="header import")
            offer(d, blk)
            early = await settled(d, 69)  # three shards of four
            assert 69 in jobs.started and not jobs.release.is_set()
            assert {v.txid for v in early} == set(order[:69])
            assert all(v.error is None for v in early)
            handed = metrics.get("node.stream_early_items") - early0
            assert handed > 0
            assert metrics.get("node.stream_items") - all0 == handed
            # the connect waits for the last verdict
            assert d.node.utxo.height == -1
            jobs.release.set()
            got = await settled(d, 91)
            await poll_until(lambda: d.node.utxo.height == 1,
                             what="utxo connect")
    assert sorted(v.txid for v in got) == sorted(order)
    for v in got:
        if v.txid != order[0]:
            assert v.error is None and tuple(v.verdicts) == expect[v.txid]
    # the last shard was handed on with no job behind it
    assert metrics.get("node.stream_early_items") - early0 == handed
    assert metrics.get("node.stream_items") - all0 == gen.totals(
        MIX, N_TXS)["items"]
    assert jobs.closes == 1


@pytest.mark.asyncio
@pytest.mark.parametrize("shard0", ["published", "with-the-engine"])
async def test_a_job_that_fails_late_leaves_one_verdict_a_tx(
        shard0, monkeypatch, gate):
    """Shard 0 has published, or is with the engine; shard 1 raises while
    shards 2.. are queued or running: error verdicts for 1.., shard 0's
    own verdicts whenever the engine answers, none twice, no connect."""
    blk, oracle, expect, order = a_block(34)
    cut_into(monkeypatch, 9)  # runs of 11: 0, 11, 22, .. 88
    jobs = gate(hold_from=11, boom=11)
    errors0 = metrics.get("node.verify_errors")
    async with asyncio.timeout(120):
        async with a_node(oracle=oracle, utxo=True, port=17943) as d:
            d.node.chain.headers(d.peer, [blk.header])
            await poll_until(
                lambda: d.node.chain.get_block(blk.header.hash) is not None,
                what="header import")
            engine = asyncio.Event()  # holds what the engine is handed
            if shard0 == "published":
                engine.set()
            recorded, handed = d.node.verify_engine.verify_raw, []

            async def held(items, **kw):
                handed.append(items.n_txs)
                await engine.wait()
                return await recorded(items, **kw)

            d.node.verify_engine.verify_raw = held
            offer(d, blk)
            if shard0 == "published":
                first = await settled(d, 11)
                assert {v.txid for v in first} == set(order[:11])
            else:
                await poll_until(lambda: handed == [11],
                                 what="shard 0 reaches the engine")
            jobs.fail.set()
            if shard0 == "with-the-engine":
                failed = await settled(d, 80)
                assert {v.txid for v in failed} == set(order[11:])
                engine.set()
            got = await settled(d, 91)
            assert jobs.closes == 0  # jobs still hold the region
            jobs.release.set()  # they finish: their items are dropped
            await poll_until(lambda: d.node._verify_pending == 0,
                             what="the block's task ends")
            await poll_until(lambda: jobs.closes == 1, what="region closed")
            await asyncio.sleep(0.1)
            assert d.node.utxo.height == -1  # not clean: no connect
            assert jobs.closes == 1
            assert len(d.submissions) == 1  # shard 0, and nothing after
    assert sorted(v.txid for v in got) == sorted(order)  # one a tx
    by = {v.txid: v for v in got}
    for txid in order[1:11]:
        assert by[txid].error is None
        assert tuple(by[txid].verdicts) == expect[txid]
    for txid in order[11:]:
        assert by[txid].error == "extract: boom" and not by[txid].valid
    assert metrics.get("node.verify_errors") - errors0 == 1


@pytest.mark.asyncio
async def test_cancelling_the_blocks_task_mid_extract(monkeypatch, gate):
    blk, oracle, expect, order = a_block(35)
    cut_into(monkeypatch, 4)
    jobs = gate(hold_from=23)  # only the first job gets out
    async with asyncio.timeout(120):
        async with a_node(oracle=oracle, utxo=True, port=17944) as d:
            offer(d, blk)
            await settled(d, 23)
            task, = [t for t in d.node._verify_tasks.children
                     if t.get_name() == "verify-txs"]
            task.cancel()
            await poll_until(task.done, what="the block's task ends")
            assert task.cancelled() and d.node._verify_pending == 0
            jobs.release.set()
            await poll_until(lambda: jobs.closes == 1, what="region closed")
            got = await settled(d, 23)  # nothing more, nothing twice
            assert d.node.utxo.height == -1
            assert jobs.closes == 1
            assert not [t for t in d.node._verify_tasks.children
                        if t.get_name() == "verify-shard-commit"]
    assert sorted(v.txid for v in got) == sorted(order[:23])


@pytest.mark.asyncio
async def test_confirmed_leaves_before_the_first_verdict(monkeypatch):
    blk, oracle, expect, order = a_block(36)
    cut_into(monkeypatch, 4)
    log: list = []
    async with asyncio.timeout(120):
        async with a_node(oracle=oracle, port=17945,
                          mempool=MempoolConfig(tick_interval=0.05)) as d:
            confirmed, publish = d.node.mempool.confirmed, d.node._publish_verdict

            def confirmed_logged(txids):
                log.append(("confirmed", list(txids)))
                confirmed(txids)

            def publish_logged(v, relay=True):
                log.append(("verdict", v.txid))
                publish(v, relay=relay)

            monkeypatch.setattr(d.node.mempool, "confirmed", confirmed_logged)
            monkeypatch.setattr(d.node, "_publish_verdict", publish_logged)
            await d.block(blk)
    assert log[0] == ("confirmed", order)
    assert [kind for kind, _ in log].count("confirmed") == 1
    assert len(log) == 1 + len(order)


@pytest.mark.parametrize("n,workers,jobs", [
    (64, 4, 1),        # ibd, ibd-spend: one job a block, as before
    (127, 4, 1),
    (155, 4, 2),       # tip: the txs no relay verdict answered
    (300, 4, 4),
    (3105, 4, 4),      # tip's whole block: a job a worker
    (12288, 4, 4),     # ... up to STREAM_SHARD_TXS a worker
    (12289, 4, 5),
    (66672, 4, 22),    # blocks, single: runs of 3,031 txs
    (66672, 1, 1),
    (66672, 2, 22),
])
def test_how_many_jobs_a_block_is_cut_into(n, workers, jobs):
    node = types.SimpleNamespace(
        _extract_workers=workers,
        MIN_SHARD_TXS=node_mod.Node.MIN_SHARD_TXS,
        STREAM_SHARD_TXS=node_mod.Node.STREAM_SHARD_TXS)
    assert node_mod.Node._n_extract_jobs(node, n) == jobs


# ---- the jobs leave from inside the prevout walk (ISSUE 46) ----------------

STREAM = ("node.stream_blocks", "node.stream_jobs", "node.stream_jobs_in_walk",
          "span.node.prefix.count", "span.node.resolve.count")


def counters() -> dict:
    return {k: metrics.get(k) for k in STREAM}


def moved(before: dict) -> dict:
    return {k: int(metrics.get(k) - v) for k, v in before.items()}


def watch_the_walk(monkeypatch, d) -> list:
    """-> a log of ``("walk",)`` where a prevout walk makes its first read
    — a callback is put on the loop's queue there — and
    ``("job", did that callback run, node.resolve entries closed so far)``
    for every extract job given to the pool."""
    log: list = []
    loop = asyncio.get_running_loop()
    ran: list = []
    sources, submit = d.node._prevout_sources, d.node._extract_pool.submit

    def first_read():
        ran.clear()
        loop.call_soon(ran.append, True)
        log.append(("walk",))
        return sources()

    def submitted(fn, *args, **kw):
        if fn is node_mod._extract_counted:
            log.append(("job", bool(ran),
                        metrics.get("span.node.resolve.count")))
        return submit(fn, *args, **kw)

    monkeypatch.setattr(d.node, "_prevout_sources", first_read)
    monkeypatch.setattr(d.node._extract_pool, "submit", submitted)
    return log


@pytest.mark.asyncio
@pytest.mark.parametrize("utxo", [False, True], ids=["plain", "gated"])
async def test_every_job_is_in_the_pool_before_the_walks_hold_ends(
        utxo, monkeypatch):
    """ONE hold: no callback of the loop runs between the walk's first read
    and the last job's submission, and ``node.resolve`` is still open at
    each; the verdicts are the unsharded ones."""
    blk, oracle, expect, order = a_block(46)
    cut_into(monkeypatch, 4)
    async with asyncio.timeout(120):
        async with a_node(oracle=oracle, utxo=utxo, port=17946) as d:
            log = watch_the_walk(monkeypatch, d)
            c0 = counters()
            got, subs, _ = await d.block(blk)
            assert moved(c0) == {
                "node.stream_blocks": 1, "node.stream_jobs": 4,
                "node.stream_jobs_in_walk": 3, "span.node.prefix.count": 1,
                "span.node.resolve.count": 1}
            assert metrics.get("span.node.prefix.seconds") > 0
    assert log[0] == ("walk",) and len(log) == 5
    open_at = c0["span.node.resolve.count"]
    assert log[1:] == [("job", False, open_at)] * 4
    assert len(subs) == 4 and [v.txid for v in got] == order
    for v in got[1:]:
        assert v.error is None and tuple(v.verdicts) == expect[v.txid]


@pytest.mark.asyncio
async def test_a_block_read_ahead_of_one_beneath_it_submits_after_the_read(
        monkeypatch):
    """The gate's ``resolve(final=False)`` hands nothing on; where it has
    every answer the jobs go right after it, all at once."""
    first, _, _, _ = a_block(47)
    job = gen.gen_job(gen.jobs_for(MIX, 48, N_TXS, N_TXS)[0])
    oracle = gen.Oracle()
    oracle.p2pk.update(job["p2pk"])
    blk = block_of(job["raw"], height=2, prev=first.header.hash)
    expect = dict(zip(job["txids"], job["expect"]))
    cut_into(monkeypatch, 4)
    finals: list = []
    walk = node_mod.Node._resolve_ext_rows

    def recorded(self, *a, final=True, **kw):
        finals.append(final)
        return walk(self, *a, final=final, **kw)

    monkeypatch.setattr(node_mod.Node, "_resolve_ext_rows", recorded)
    async with asyncio.timeout(120):
        async with a_node(oracle=oracle, utxo=True, port=17947) as d:
            d.node.chain.headers(d.peer, [first.header, blk.header])
            await poll_until(
                lambda: d.node.chain.get_block(blk.header.hash) is not None,
                what="header import")
            log = watch_the_walk(monkeypatch, d)
            c0 = counters()
            got, subs, _ = await d.block(blk)  # its parent has not come
            assert moved(c0) == {
                "node.stream_blocks": 1, "node.stream_jobs": 4,
                "node.stream_jobs_in_walk": 0, "span.node.prefix.count": 1,
                "span.node.resolve.count": 1}
    assert finals == [False]  # one read, ahead of its turn, and it sufficed
    # the four jobs went after node.resolve closed, and still in its hold
    assert log == [("walk",)] + [
        ("job", False, c0["span.node.resolve.count"] + 1)] * 4
    assert len(subs) == 4 and len(got) == N_TXS + 1
    for v in got[1:]:
        assert v.error is None and tuple(v.verdicts) == expect[v.txid]


@pytest.mark.asyncio
@pytest.mark.parametrize("jobs_out", [0, 2])
async def test_a_callback_that_raises_mid_walk_ends_the_message_with_no_verdict(
        jobs_out, monkeypatch, gate):
    """The embedder's callback raises with ``jobs_out`` of the block's four
    jobs in the pool: the block's task ends as it always did on such an
    error — crashed and counted, no verdict, no connect, nothing taken —
    and the jobs that were out are cancelled or run out, the last of them
    closing the region, once."""
    blk, oracle, expect, order = a_block(49)
    cut_into(monkeypatch, 4)  # runs of 23 txs
    with ParsedTxRegion(blk.raw_txs, blk.tx_count) as region:
        wants = region.scan_outpoints(True)[3]
        answered = int(wants[: int(region.input_offsets()[23 * jobs_out])].sum())
    calls = 0

    def failing(txid, vout):
        nonlocal calls
        calls += 1
        if calls > answered + 1:  # in the next shard's first rows
            raise LookupError("embedder down")
        return oracle(txid, vout)

    jobs = gate(hold_from=0)
    crashes0 = metrics.get("node.verify_task_crashes")
    async with asyncio.timeout(120):
        async with a_node(oracle=failing, utxo=True, port=17948) as d:
            d.node.chain.headers(d.peer, [blk.header])
            await poll_until(
                lambda: d.node.chain.get_block(blk.header.hash) is not None,
                what="header import")
            futures: list = []
            submit = d.node._extract_pool.submit

            def kept(fn, *args, **kw):
                futures.append((fn, submit(fn, *args, **kw)))
                return futures[-1][1]

            monkeypatch.setattr(d.node._extract_pool, "submit", kept)
            c0 = counters()
            offer(d, blk)
            await poll_until(
                lambda: metrics.get("node.verify_task_crashes") > crashes0,
                what="the block's task ends")
            assert d.node._verify_pending == 0
            assert not d.node._block_taken(blk.header.hash)
            assert len(d.node._inflight) == 0
            out = [f for fn, f in futures if fn is node_mod._extract_counted]
            assert len(out) == jobs_out
            assert moved(c0)["node.stream_jobs"] == jobs_out
            if jobs_out:
                await poll_until(lambda: 0 in jobs.started, what="job 0 runs")
                assert jobs.closes == 0  # under a live extract: not closed
            jobs.release.set()
            await poll_until(lambda: jobs.closes == 1, what="region closed")
            assert all(f.done() for f in out)  # run out, or cancelled queued
            assert await settled(d, 0) == []  # dropped: no verdict at all
            assert d.node.utxo.height == -1
            assert jobs.closes == 1
            assert not [t for t in d.node._verify_tasks.children
                        if t.get_name() in ("verify-txs", "verify-shard-commit")]
    assert metrics.get("node.verify_task_crashes") - crashes0 == 1


@pytest.mark.asyncio
async def test_a_cut_that_fails_leaves_one_error_verdict_a_tx(
        monkeypatch, gate):
    """The cut is made between the parse and the walk (the layout, the
    row offsets): where it raises, the block ends as where its parse
    raises — one error verdict a tx, the region closed once, no connect."""
    blk, oracle, _, order = a_block(51)
    cut_into(monkeypatch, 4)
    jobs = gate(hold_from=10 ** 6)

    def no_layout(self):
        raise MemoryError("no layout")

    monkeypatch.setattr(ParsedTxRegion, "input_offsets", no_layout)
    async with asyncio.timeout(120):
        async with a_node(oracle=oracle, utxo=True, port=17950) as d:
            d.node.chain.headers(d.peer, [blk.header])
            await poll_until(
                lambda: d.node.chain.get_block(blk.header.hash) is not None,
                what="header import")
            c0 = counters()
            offer(d, blk)
            got = await settled(d, 91)
            await poll_until(lambda: d.node._verify_pending == 0,
                             what="the block's task ends")
            assert jobs.closes == 1 and not jobs.started
            assert moved(c0)["node.stream_jobs"] == 0
            assert d.node.utxo.height == -1
            assert not d.node._block_taken(blk.header.hash)
    assert sorted(v.txid for v in got) == sorted(order)  # one a tx
    assert all(v.error == "extract: no layout" and not v.valid for v in got)


@pytest.mark.asyncio
async def test_a_64_tx_block_is_one_job_after_the_walk_and_counts_nothing(
        monkeypatch):
    job = gen.gen_job(gen.jobs_for(MIX, 50, 63, 63)[0])
    oracle = gen.Oracle()
    oracle.p2pk.update(job["p2pk"])
    blk = block_of(job["raw"])
    async with asyncio.timeout(120):
        async with a_node(oracle=oracle, utxo=True, port=17949) as d:
            assert d.node._n_extract_jobs(64) == 1
            log = watch_the_walk(monkeypatch, d)
            c0 = counters()
            got, subs, _ = await d.block(blk)
            assert moved(c0) == {
                "node.stream_blocks": 0, "node.stream_jobs": 0,
                "node.stream_jobs_in_walk": 0, "span.node.prefix.count": 0,
                "span.node.resolve.count": 1}
    # the walk, closed, and then the one job: in the same hold
    assert log == [("walk",), ("job", False, c0["span.node.resolve.count"] + 1)]
    assert len(subs) == 1 and len(got) == 64
    assert all(v.error is None for v in got)
