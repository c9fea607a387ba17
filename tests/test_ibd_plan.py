"""The fetch planner's unit of work is the batch (ISSUE 43): the plan grows
by whole batches, so every ``getdata`` of a steady sync asks
``batch_blocks`` blocks, and the pass that runs for every connected block
costs nothing that grows with the header chain.

All over the planner's stubs (``tests/test_ibd_faults.py``'s ``_World`` /
``_Peer``) or the fakenet: no engine, no jax, no native library.
"""

from __future__ import annotations

import random
import time

import pytest

from benchmarks.txgen import assemble_chain
from tests.fakenet import poll_until
from tests.test_ibd import NET, ibd_node
from tests.test_ibd_faults import (
    Counters,
    Remote,
    _asked,
    _deliver,
    _Node,
    _Peer,
    _World,
    all_online,
    close_head_waits,  # noqa: F401 — the autouse fixture, for _World's planners
    connect_to,
    peers_of,
)
from tpunode import IbdConfig
from tpunode import ibd as ibd_mod
from tpunode.store import MemoryKV
from tpunode.wire import MsgGetData, MsgPing

# (batch_blocks, max_lead): bch-node / bch-utxo, bch-wan, btc-node, and
# None for IbdConfig's own
SIZES = [(24, 48), (8, 128), (16, 48), None]


def _cfg(size, **kw) -> IbdConfig:
    if size is None:
        return IbdConfig(**kw)
    return IbdConfig(batch_blocks=size[0], max_lead=size[1], **kw)


def _fleet(cfg: IbdConfig) -> list:
    """Peers enough to hold the whole lead in flight."""
    n = -(-cfg.max_lead // (cfg.batch_blocks * cfg.max_inflight_per_peer))
    return [_Peer(str(i)) for i in range(n + 1)]


def _getdata(peers, *, ever=False) -> list[list[int]]:
    """The getdata not yet served (``ever``: all that were sent), as the
    heights each asks."""
    return [[int.from_bytes(iv.hash, "big") for iv in m.invs]
            for p in peers
            for m in (getattr(p, "history", []) if ever else []) + p.sent
            if isinstance(m, MsgGetData)]


def _serve(f, peers) -> None:
    """Every peer delivers what it was asked, in order."""
    for p in peers:
        _deliver(f, p, _asked(p))
        p.history = getattr(p, "history", []) + p.sent
        p.sent = []


def _top(f) -> int:
    return max((b.hi for b in f._batches.values()), default=0)


# -- the plan grows by whole batches ------------------------------------------


@pytest.mark.parametrize("stride", [1, 7], ids=["every block", "every 7"])
@pytest.mark.parametrize("size", SIZES, ids=str)
def test_in_steady_state_every_getdata_asks_a_whole_batch(size, stride):
    """Away from the tip no getdata is short, whether the planner runs
    once a connected block or once every several, and the scheduled lead
    steps between ``max_lead - batch_blocks + 1`` and ``max_lead``."""
    cfg = _cfg(size)
    n, lead = cfg.batch_blocks, cfg.max_lead
    peers = _fleet(cfg)
    w = _World(3000, peers)
    f = w.planner(cfg, cap=2 * lead)
    f._plan()
    _serve(f, peers)
    leads = []
    while w.height + stride <= 2000:
        w.height += stride
        f._plan()
        _serve(f, peers)
        assert _top(f) <= w.height + lead
        leads.append(_top(f) - w.height)
    asked = _getdata(peers, ever=True)
    assert {len(g) for g in asked} == {n}
    flat = sorted(h for g in asked for h in g)
    assert flat == list(range(1, flat[-1] + 1))  # each height once, no hole
    assert len(asked) == flat[-1] // n
    assert lead - n - stride + 1 < min(leads) and max(leads) <= lead
    if stride == 1:
        assert min(leads) == lead - n + 1 and max(leads) == lead


@pytest.mark.parametrize("headers", [100, 30, 49])
def test_the_batch_before_the_tip_is_short_and_is_asked_at_once(headers):
    """The open edge waits for a batch's room only while there are
    headers beyond the horizon: what is left before the tip goes out the
    moment the horizon reaches it, at whatever size it has."""
    peers = [_Peer("a"), _Peer("b"), _Peer("c")]
    w = _World(headers, peers)
    f = w.planner(IbdConfig(batch_blocks=24, max_lead=48), cap=128)
    while w.height < headers:
        f._plan()
        _serve(f, peers)
        horizon = min(headers, w.height + 48)
        # nothing is held back once the tip is inside the lead
        assert (_top(f) == headers) == (horizon == headers)
        w.height += 1
    f._plan()
    assert f.synced.is_set()
    asked = _getdata(peers, ever=True)
    whole, rest = divmod(headers, 24)
    assert sorted(map(len, asked)) == sorted([24] * whole + [rest] * bool(rest))
    assert sorted(h for g in asked for h in g) == list(range(1, headers + 1))


def test_a_live_block_at_the_tip_is_asked_alone_and_at_once():
    """A synced node never holds a fresh block back for company."""
    peer = _Peer("a")
    w = _World(60, [peer])
    w.best = w.nodes[(50).to_bytes(32, "big")]
    w.height = 50
    f = w.planner(IbdConfig(batch_blocks=24, max_lead=48))
    f._plan()
    assert f.synced.is_set() and _asked(peer) == []
    for tip in (51, 52):
        w.best = w.nodes[tip.to_bytes(32, "big")]
        f._plan()
        assert _getdata([peer]) == [[tip]] and not f.backfilling
        _serve(f, [peer])
        w.height = tip


def test_a_gap_between_two_batches_after_a_reorg_unwind_is_filled_at_once():
    """The watermark moved back under surviving batches: the heights in
    front of them are what connects next, so they are asked at once, as
    one short batch, and the view of the chain covers them again."""
    peers = [_Peer("a"), _Peer("b")]
    w = _World(400, peers)
    f = w.planner(IbdConfig(batch_blocks=8, max_lead=32))
    f._plan()
    _serve(f, peers)
    w.height = 16
    f._plan()
    _serve(f, peers)
    assert min(f._hashes) == 17 and min(f._batches) == 17
    w.height = 11  # five blocks disconnected; the headers stand
    f._plan()
    assert _getdata(peers) == [[12, 13, 14, 15, 16]]
    assert min(f._hashes) == 12
    assert all(f._hashes[h] == h.to_bytes(32, "big") for h in range(12, 49))
    # and the plan above it is what it was: whole batches, no overlap
    lows = sorted(f._batches)
    assert lows == [12, 17, 25, 33, 41]
    assert _top(f) <= 11 + 32 + 8  # the old horizon's batches survive


def test_a_hole_left_by_a_dropped_batch_is_filled_whole():
    """A batch the reorg check dropped leaves a hole with batches on both
    sides: it is rescheduled at once, on the new branch's hashes."""
    peers = [_Peer("a"), _Peer("b")]
    w = _World(400, peers)
    f = w.planner(IbdConfig(batch_blocks=8, max_lead=32), cap=128)
    f._plan()
    assert sorted(f._batches) == [1, 9, 17, 25]
    # heights 9..16 are rewritten by a fork that rejoins nothing: the new
    # branch runs 9..400 on other hashes
    prev = (8).to_bytes(32, "big")
    for h in range(9, 401):
        hh = (h + 10**6).to_bytes(32, "big")
        w.nodes[hh] = _Node(h, hh, prev)
        prev = hh
    w.best = w.nodes[prev]
    f._plan()
    assert sorted(f._batches) == [1, 9, 17, 25]
    assert f._batches[1].hashes[0] == (1).to_bytes(32, "big")  # untouched
    for lo in (9, 17, 25):
        assert f._batches[lo].hashes == [
            (h + 10**6).to_bytes(32, "big") for h in range(lo, lo + 8)]


def test_a_shorter_best_chain_prunes_the_view_over_its_tip():
    peer = _Peer("a")
    w = _World(300, [peer])
    f = w.planner(IbdConfig(batch_blocks=8, max_lead=32))
    f._plan()
    assert max(f._hashes) == 300
    w.best = w.nodes[(120).to_bytes(32, "big")]
    f._plan()
    assert max(f._hashes) == 120 and f.stats()["target"] == 120
    w.best = w.nodes[(200).to_bytes(32, "big")]
    f._plan()
    assert sorted(f._hashes) == list(range(1, 201))


@pytest.mark.parametrize("how", ["pong", "peer gone", "timeout", "stall"])
def test_a_rerequest_asks_only_the_missing(how, monkeypatch):
    """Whole batches go out; what comes back to the queue is what did not
    arrive, and only that is asked of the next peer."""
    clock = [100.0]
    monkeypatch.setattr(ibd_mod.time, "monotonic", lambda: clock[0])
    a, b = _Peer("a"), _Peer("b")
    w = _World(500, [a, b])
    f = w.planner(IbdConfig(batch_blocks=24, max_lead=48, fetch_timeout=30.0,
                            stall_timeout=2.0, max_inflight_per_peer=1))
    f._plan()
    assert _getdata([a]) == [list(range(1, 25))]
    assert _getdata([b]) == [list(range(25, 49))]
    _deliver(f, a, range(1, 10))
    _deliver(f, b, range(25, 49))
    if how == "pong":
        f.pong(a, next(m.nonce for m in a.sent if isinstance(m, MsgPing)))
    elif how == "peer gone":
        f.peer_gone(a)
        w.peers = w.peers[1:]
    elif how == "timeout":
        for _ in range(20):  # it is heard from, and the batch stays short
            clock[0] += 1.6
            f._progress[a] = clock[0]
            f._plan()
    else:
        clock[0] += 2.1
    f._plan()
    assert _getdata([b])[1:] == [list(range(10, 25))]
    assert f.stats()["fetched_blocks"] == 24  # b's batch; a's is not whole


@pytest.mark.parametrize("size", SIZES, ids=str)
def test_nothing_is_scheduled_past_the_lead(size):
    """``max_lead`` stays the ceiling under any order of arrival: blocks
    come in part, late, or not at all; the watermark moves in steps."""
    cfg = _cfg(size, max_inflight_per_peer=4)
    rng = random.Random(43)
    peers = [_Peer(str(i)) for i in range(6)]
    w = _World(5000, peers)
    f = w.planner(cfg, cap=4 * cfg.max_lead)
    here: set[int] = set()
    for _ in range(600):
        f._plan()
        assert _top(f) <= w.height + cfg.max_lead
        for p in peers:
            # a peer answers in order: each getdata, then its ping
            while p.sent and rng.random() < 0.8:
                ask, ping = p.sent[:2]
                del p.sent[:2]
                heights = [int.from_bytes(iv.hash, "big") for iv in ask.invs]
                assert max(heights) <= w.height + cfg.max_lead
                if rng.random() < 0.25:  # it has only some of them
                    heights = heights[:rng.randrange(len(heights))]
                _deliver(f, p, heights)
                here.update(heights)
                f.pong(p, ping.nonce)
        while w.height + 1 in here and rng.random() < 0.9:
            w.height += 1
    assert w.height > 10 * cfg.max_lead  # the sync went on through it all
    assert f.stats()["refetches"] == 0


def test_a_batch_waits_for_room_under_the_shed_bound_and_goes_whole():
    """``_assign`` sees whole batches now: one that would put more on the
    wire than the node takes unshed waits, and is not cut to fit."""
    peers = [_Peer("a"), _Peer("b")]
    w = _World(500, peers)
    f = w.planner(IbdConfig(batch_blocks=24, max_lead=48), cap=64)
    w.pending = 20
    f._plan()
    assert _getdata(peers) == [list(range(1, 25))]  # 20 + 24 <= 64 < 20 + 48
    w.pending = 30
    f._plan()
    assert len(_getdata(peers)) == 1
    _deliver(f, peers[0], range(1, 25))
    w.pending = 40
    f._plan()
    assert _getdata(peers)[1:] == [list(range(25, 49))]  # 40 + 24 <= 64


# -- a pass costs nothing that grows with the chain ----------------------------


class _NoWalk(dict):
    """The height -> hash view, for a planner that must not walk it."""

    def _no(self, *a, **kw):
        raise AssertionError("the planner walked its whole view of the chain")

    __iter__ = keys = values = items = __len__ = _no

    def __init__(self, d):
        dict.__init__(self, d)
        self.reads = self.writes = 0

    def get(self, k, default=None):
        self.reads += 1
        return dict.get(self, k, default)

    def pop(self, k, *default):
        self.writes += 1
        return dict.pop(self, k, *default)

    def __setitem__(self, k, v):
        self.writes += 1
        dict.__setitem__(self, k, v)


def _steady(headers: int, size=(24, 48)):
    cfg = _cfg(size)
    peers = _fleet(cfg)
    w = _World(headers, peers)
    f = w.planner(cfg, cap=2 * cfg.max_lead)
    f._plan()
    _serve(f, peers)
    f._hashes = _NoWalk(f._hashes)
    return w, f, peers


@pytest.mark.parametrize("what", ["a connected block", "several at once",
                                  "a new header", "a shorter chain"])
def test_a_pass_does_not_iterate_the_view(what):
    w, f, peers = _steady(2000)
    for i in range(200):
        if what == "a connected block":
            w.height += 1
        elif what == "several at once":
            w.height += 5
        elif what == "a new header":
            w.height += 1
            w.best = w.nodes[(1500 + i).to_bytes(32, "big")]
        else:
            w.height += 1
            w.best = w.nodes[(1990 - i).to_bytes(32, "big")]
        f._plan()
        _serve(f, peers)
    assert {len(g) for g in _getdata(peers, ever=True)[2:]} == {24}
    assert dict.__len__(f._hashes) == w.best.height - w.height
    assert min(dict.keys(f._hashes)) == w.height + 1


@pytest.mark.parametrize("size", [(24, 48), (8, 128)], ids=str)
def test_a_pass_touches_the_view_as_often_at_1e5_headers_as_at_1e3(size):
    """Counted, so exact: a pass after a connected block retires one
    height and reads none; the pass that schedules a batch reads
    ``batch_blocks``."""
    touched = {}
    for headers in (10**3, 10**5):
        w, f, peers = _steady(headers, size)
        per_pass = []
        for _ in range(4 * size[0]):
            before = f._hashes.reads, f._hashes.writes
            w.height += 1
            f._plan()
            _serve(f, peers)
            per_pass.append((f._hashes.reads - before[0],
                             f._hashes.writes - before[1]))
        touched[headers] = per_pass
    assert touched[10**3] == touched[10**5]
    assert sorted(set(touched[10**3])) == [(0, 1), (size[0], 1)]
    assert touched[10**3].count((size[0], 1)) == 4


def test_a_pass_costs_the_same_at_1e5_headers_as_at_1e3():
    """On the clock: 254 us a pass at 12.7k headers and 16 ms at 800k is
    what the walk cost (PERF.md, PR 40).  The best of five rounds a size,
    and room for a noisy machine: the walk was a factor of 100."""
    def per_pass(headers) -> float:
        w, f, peers = _steady(headers)
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(240):
                w.height += 1
                f._plan()
                _serve(f, peers)
            best = min(best, (time.perf_counter() - t0) / 240)
        return best

    small, big = per_pass(10**3), per_pass(10**5)
    assert big < 3 * small + 20e-6, (small, big)


@pytest.mark.parametrize("size", [(24, 48), (8, 128)], ids=str)
def test_a_pass_that_frees_less_than_a_batch_sorts_nothing_and_takes_no_lock(
        size, monkeypatch):
    """After a connected block that leaves the open edge under a batch the
    pass ends before anything that sorts, writes a gauge or counts: the
    metrics' lock is taken by six other threads (PERF.md, PR 42)."""
    clock = [100.0]
    monkeypatch.setattr(ibd_mod.time, "monotonic", lambda: clock[0])
    w, f, peers = _steady(4000, size)
    calls = []

    class Spy:
        def __getattr__(self, name):
            return lambda *a, **kw: calls.append((name, a))

    def no_sort(*a, **kw):
        calls.append(("sorted", a))
        return sorted(*a, **kw)

    quiet = 0
    for _ in range(6 * size[0]):
        w.height += 1
        calls.clear()
        with monkeypatch.context() as m:
            m.setattr(ibd_mod, "metrics", Spy())
            m.setattr(ibd_mod, "sorted", no_sort, raising=False)
            f._plan()
        if not _getdata(peers):
            assert calls == []
            quiet += 1
        else:
            assert ("inc", ("ibd.fetches",)) in calls
        _serve(f, peers)
    assert quiet == 6 * size[0] - 6


def test_the_inflight_gauge_is_a_sample(monkeypatch):
    """``ibd.inflight_blocks`` is written when the planner asks for blocks
    and, between, every ``GAUGE_INTERVAL`` and only if it moved."""
    clock = [100.0]
    monkeypatch.setattr(ibd_mod.time, "monotonic", lambda: clock[0])
    wrote = []
    monkeypatch.setattr(ibd_mod.metrics, "set_gauge",
                        lambda name, v, labels=None: wrote.append((name, v)))
    a = _Peer("a")
    w = _World(500, [a])
    f = w.planner(IbdConfig(batch_blocks=24, max_lead=48, tick_interval=0.02),
                  cap=128)
    assert ibd_mod.GAUGE_INTERVAL == 0.5
    f._plan()
    assert wrote == [("ibd.target", 500.0), ("ibd.inflight_blocks", 48.0)]
    _deliver(f, a, range(1, 31))
    w.height = 3
    for _ in range(3):  # passes inside the interval: no sample
        clock[0] += 0.1
        f._plan()
    assert len(wrote) == 2
    clock[0] += 0.3
    f._plan()
    assert wrote[2:] == [("ibd.inflight_blocks", 18.0)]
    clock[0] += 1.0
    f._plan()  # an interval on, the value has not moved: nothing is written
    assert len(wrote) == 3


# -- through Node ---------------------------------------------------------------


@pytest.mark.asyncio
@pytest.mark.parametrize("size", [(24, 48), (16, 48), (8, 128)], ids=str)
async def test_a_600_block_sync_asks_whole_batches_and_every_block_once(size):
    """554 getdata for 600 blocks at ``batch_blocks=24`` before ISSUE 43:
    the plan slid by the one height each connected block freed."""
    blocks = assemble_chain(NET, [], 0, n_blocks=600)  # coinbase-only
    remotes = {1: Remote(blocks)}
    c = Counters("ibd.fetches", "ibd.blocks", "ibd.blocks_rerequested",
                 "node.block_duplicate_skipped", "node.block_replay_skipped")
    ibd = _cfg(size, tick_interval=0.05)
    async with ibd_node(MemoryKV(), blocks, connect=connect_to(remotes),
                        peers=peers_of(remotes), ibd=ibd) as (node, _):
        await all_online(node, remotes)
        await poll_until(lambda: node.utxo.height == 600, timeout=60,
                         what="the sync")
        await poll_until(lambda: node.ibd.synced.is_set(), what="synced")
        st = node.ibd.stats()
    asked = [[iv.hash for iv in m.invs] for _, m in remotes[1].got
             if isinstance(m, MsgGetData)]
    assert len(asked) == c["ibd.fetches"] == -(-600 // size[0])  # 25 at 24
    assert {len(g) for g in asked[:-1]} == {size[0]}
    assert [h for g in asked for h in g] == [b.header.hash for b in blocks]
    assert st["fetched_blocks"] == c["ibd.blocks"] == 600
    assert st["refetches"] == c["ibd.blocks_rerequested"] == 0
    assert c["node.block_duplicate_skipped"] == 0
    assert c["node.block_replay_skipped"] == 0
