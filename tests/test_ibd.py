"""Block-fetch-driven IBD (ISSUE 11 / ROADMAP item 5): a bare Node syncs
a fakenet chain through the fetch planner (tpunode/ibd.py) with no
embedder pushes — exactly-once verdicts, watermark monotone to tip,
restart resuming from the watermark, peer stalls/death reassigning
batches, sharded block extraction bit-identical to serial, and reorg
unwind through the per-block undo log.

Tier-1 keeps the small smokes; the 10k-block acceptance variants are
slow-marked per the 870s budget discipline.
"""

from __future__ import annotations

import asyncio
import contextlib

import pytest

from benchmarks.txgen import gen_chain, synth_prevout
from tests.fakenet import dummy_peer_connect, poll_until
from tests.fixtures import all_blocks, reference_set, utxo_records
from tpunode import (
    BCH_REGTEST,
    IbdConfig,
    Node,
    NodeConfig,
    Publisher,
    TxVerdict,
)
from tpunode.metrics import metrics
from tpunode.peer import PeerConnected, PeerTimeout
from tpunode.store import LogKV, MemoryKV
from tpunode.verify.engine import VerifyConfig

NET = BCH_REGTEST

IBD_FAST = IbdConfig(batch_blocks=4, tick_interval=0.05)


@contextlib.asynccontextmanager
async def ibd_node(store, blocks, *, verify=False, connect=None, peers=None,
                   ibd=IBD_FAST, **kw):
    pub = Publisher(name="ibd-test", maxsize=None)
    cfg = NodeConfig(
        net=NET,
        store=store,
        pub=pub,
        peers=peers or ["[::1]:17486"],
        discover=False,
        connect=connect or (lambda sa: dummy_peer_connect(NET, blocks)),
        verify=(
            VerifyConfig(backend="cpu", max_wait=0.005) if verify else None
        ),
        prevout_lookup=synth_prevout if verify else None,
        utxo=True,
        ibd=ibd,
        **kw,
    )
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            yield node, events


def test_ibd_requires_utxo():
    with pytest.raises(ValueError):
        NodeConfig(
            net=NET, store=MemoryKV(), pub=Publisher(name="x"),
            ibd=IbdConfig(),
        )


@pytest.mark.asyncio
async def test_bare_node_syncs_via_fetch_planner():
    """The tier-1 ~15-block smoke: no verify engine, no embedder pushes —
    the planner fetches every block and the UTXO watermark reaches the
    header tip, fetching each block exactly once."""
    blocks = all_blocks()
    async with ibd_node(MemoryKV(), blocks) as (node, _):
        await poll_until(
            lambda: node.utxo.height == len(blocks), what="ibd watermark"
        )
        await poll_until(
            lambda: node.ibd.synced.is_set(), what="ibd synced event"
        )
        st = node.ibd.stats()
        assert st["fetched_blocks"] == len(blocks)  # exactly once
        assert st["watermark"] == st["target"] == len(blocks)
        assert node.ibd.backfilling is False
        # the coinbase outputs are served by the prevout oracle
        cb = blocks[3].txs[0]
        assert node.utxo.lookup(cb.txid, 0) == (
            cb.outputs[0].value, cb.outputs[0].script,
        )
        assert node.stats()["ibd"]["enabled"] is True


@pytest.mark.asyncio
async def test_ibd_verify_exactly_once_and_monotone():
    """With the verify engine on: every unique tx gets exactly ONE clean
    verdict (verdict conservation over the fetch path) and the watermark
    only ever moves up."""
    blocks = gen_chain(NET, 20, 2, seed=0x1BD1, cache="ibd_t_20x2.bin")
    verdicts: dict[bytes, int] = {}
    heights: list[int] = []
    async with ibd_node(MemoryKV(), blocks, verify=True) as (node, events):
        async def watch():
            while True:
                ev = await events.receive()
                if isinstance(ev, TxVerdict):
                    verdicts[ev.txid] = verdicts.get(ev.txid, 0) + 1
                    heights.append(node.utxo.height)

        task = asyncio.ensure_future(watch())  # asyncsan: disable=raw-spawn (test observer, cancelled below)
        try:
            await poll_until(
                lambda: node.utxo.height == 20, timeout=60, what="ibd"
            )
            await poll_until(
                lambda: len(verdicts) >= 20 * 3, timeout=30, what="verdicts"
            )
            await asyncio.sleep(0.2)  # absorb any (wrong) duplicates
        finally:
            task.cancel()
        assert len(verdicts) == 20 * 3  # 2 txs + coinbase per block
        assert all(n == 1 for n in verdicts.values())
        assert heights == sorted(heights)  # watermark monotone


@pytest.mark.asyncio
async def test_stalling_peer_batches_retry_from_another():
    """A peer that serves headers but never answers block getdata: its
    batches time out and retry from the healthy peer; killing it mid-
    fetch reassigns immediately (ibd.peer_gone)."""
    blocks = all_blocks()

    def connect(sa):
        # port 1 stalls on blocks, port 2 serves everything
        return dummy_peer_connect(NET, blocks, serve_blocks=(sa[1] == 2))

    f0 = metrics.get("ibd.batch_failures")
    ibd = IbdConfig(batch_blocks=4, tick_interval=0.05, fetch_timeout=0.4)
    async with ibd_node(
        MemoryKV(), blocks, connect=connect,
        peers=["[::1]:1", "[::1]:2"], ibd=ibd, max_peers=2,
    ) as (node, events):
        # kill the staller once it is online (exercises peer_gone
        # reassignment on top of the timeout path)
        async def kill_staller():
            while True:
                o = next(
                    (o for o in node.peer_mgr.get_peers()
                     if o.address[1] == 1),
                    None,
                )
                if o is not None:
                    await asyncio.sleep(0.3)
                    o.peer.kill(PeerTimeout("test: staller down"))
                    return
                await asyncio.sleep(0.02)

        task = asyncio.ensure_future(kill_staller())  # asyncsan: disable=raw-spawn (test helper, awaited/cancelled below)
        try:
            await poll_until(
                lambda: node.utxo.height == len(blocks), timeout=30,
                what="ibd past stalling peer",
            )
        finally:
            task.cancel()
    # at least one batch had to fail over (timeout or death)
    assert metrics.get("ibd.batch_failures") >= f0


@pytest.mark.asyncio
async def test_restart_resumes_from_watermark_zero_refetch(tmp_path):
    """Kill-restart contract over the fetch path: a node reopened over
    the same store starts at the persisted watermark and the planner
    fetches (and the engine re-verifies) NOTHING below it."""
    blocks = all_blocks()
    path = str(tmp_path / "node.log")
    store = LogKV(path)
    async with ibd_node(store, blocks) as (node, _):
        await poll_until(
            lambda: node.utxo.height == len(blocks), what="first sync"
        )
    store.close()

    store2 = LogKV(path)  # real cold replay of the segmented log
    v0 = metrics.get("node.verify_txs")
    async with ibd_node(store2, blocks) as (node2, _):
        assert node2.utxo.height == len(blocks)  # before any traffic
        await poll_until(
            lambda: node2.ibd.synced.is_set(), what="resume synced"
        )
        await asyncio.sleep(0.2)
        assert node2.ibd.stats()["fetched_blocks"] == 0  # zero re-fetch
        assert metrics.get("node.verify_txs") == v0  # zero re-verify
    store2.close()


@pytest.mark.asyncio
async def test_sharded_block_extraction_matches_serial():
    """BLOCK regions shard across the worker pool (ISSUE 11): big blocks
    through extract_workers=4 produce the same verdicts and a
    bit-identical UTXO store as the serial worker, whose store is
    cross-checked against the pure-Python connect's
    (``UtxoStore.apply_block``, ``tests/fixtures.py``)."""
    blocks = gen_chain(
        NET, 2, 150, seed=0x1BD2, cache="ibd_t_2x150.bin", mix=True
    )

    async def run(workers: int):
        verdicts = {}
        async with ibd_node(
            MemoryKV(), blocks, verify=True, extract_workers=workers,
        ) as (node, events):
            async def watch():
                while True:
                    ev = await events.receive()
                    if isinstance(ev, TxVerdict):
                        verdicts[ev.txid] = (ev.valid, ev.verdicts)

            task = asyncio.ensure_future(watch())  # asyncsan: disable=raw-spawn (test observer, cancelled below)
            try:
                await poll_until(
                    lambda: node.utxo.height == 2, timeout=60,
                    what=f"ibd workers={workers}",
                )
                await poll_until(
                    lambda: len(verdicts) >= 2 * 151, timeout=30,
                    what="verdicts",
                )
            finally:
                task.cancel()
            return verdicts, utxo_records(node)

    v_serial, s_serial = await run(1)
    v_shard, s_shard = await run(4)
    assert v_serial == v_shard  # bit-identical verdicts
    assert s_serial == s_shard  # sharded connect == serial connect
    assert s_serial == reference_set(blocks)  # == python connect


@pytest.mark.asyncio
async def test_reorg_unwinds_through_undo_log(tmp_path):
    """A reorg beneath the watermark disconnects tip blocks through the
    per-block UNDO records and re-syncs the new branch — the resulting
    store is bit-identical to a fresh sync of that branch."""
    a = gen_chain(NET, 3, 2, seed=0x1BDA, cache="ibd_t_a_3x2.bin")
    b = gen_chain(NET, 5, 2, seed=0x1BDB, cache="ibd_t_b_5x2.bin")
    path = str(tmp_path / "node.log")

    async def sync(p, blocks, target):
        store = LogKV(p)
        try:
            async with ibd_node(store, blocks) as (node, _):
                await poll_until(
                    lambda: node.utxo.height == target, timeout=30,
                    what=f"sync to {target}",
                )
                return node.utxo.block_hash, node.utxo.snapshot()
        finally:
            store.close()

    d0 = metrics.get("utxo.disconnected")
    s0 = metrics.get("utxo.reorg_stale")
    wm_a, _ = await sync(path, a, 3)
    assert wm_a == a[2].header.hash
    wm_b, snap_reorg = await sync(path, b, 5)  # same store: reorg
    assert wm_b == b[4].header.hash
    assert metrics.get("utxo.disconnected") == d0 + 3
    assert metrics.get("utxo.reorg_stale") == s0
    _, snap_fresh = await sync(str(tmp_path / "fresh.log"), b, 5)
    assert snap_reorg == snap_fresh  # bit-identical to a fresh sync


# ---------------------------------------------------------------------------
# 10k-block acceptance (slow: multi-minute — the tier-1 smoke above covers
# the same invariants at 15 blocks)

@pytest.mark.slow
@pytest.mark.asyncio
async def test_ibd_10k_blocks_acceptance():
    """ISSUE 11 acceptance: a bare Node syncs a 10k-block fakenet chain
    via the fetch planner — exactly-once verdicts per unique tx and the
    watermark monotone to tip."""
    n_blocks = 10_000
    blocks = gen_chain(
        NET, n_blocks, 1, seed=0x1BD6, cache=f"ibd_{n_blocks}x1.bin"
    )
    verdicts: dict[bytes, int] = {}
    ibd = IbdConfig(batch_blocks=32, tick_interval=0.05)
    async with ibd_node(MemoryKV(), blocks, verify=True, ibd=ibd) as (
        node, events,
    ):
        async def watch():
            while True:
                for ev in [await events.receive()]:
                    if isinstance(ev, TxVerdict):
                        verdicts[ev.txid] = verdicts.get(ev.txid, 0) + 1

        task = asyncio.ensure_future(watch())  # asyncsan: disable=raw-spawn (test observer, cancelled below)
        try:
            await poll_until(
                lambda: node.utxo.height == n_blocks, timeout=900,
                what="10k-block ibd",
            )
            await poll_until(
                lambda: len(verdicts) >= n_blocks * 2, timeout=120,
                what="all verdicts",
            )
            await asyncio.sleep(0.5)
        finally:
            task.cancel()
        st = node.ibd.stats()
        assert st["watermark"] == n_blocks
        assert st["refetches"] == 0  # healthy sync: no heal rounds
    assert len(verdicts) == n_blocks * 2  # 1 tx + coinbase per block
    assert all(n == 1 for n in verdicts.values())
