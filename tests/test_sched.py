"""Streaming verify scheduler (ISSUE 10).

Three tiers:

* packer units — priority ordering (block > mempool > bulk), slicing
  across submission boundaries, verdict-conservation bookkeeping on
  Submission, the telemetry surface.
* engine pipeline — verdict conservation through sliced/packed lanes at
  ``pipeline_depth`` 1 and 2, priority ordering at dispatch, lane-failure
  isolation, and the oldest-inflight watchdog contract
  (``dispatch_inflight_seconds`` reports the OLDEST in-flight dispatch;
  the watchdog stall signal keeps firing on it).
* acceptance — the fakenet scenario: peers pushing interleaved blocks +
  mempool txs through parallel extraction and the packed pipelined
  dispatch, asserting verdict conservation, per-lane priority ordering,
  a monotone UTXO watermark, and zero task leaks; plus the chaos
  variant (device_loss mid-pipeline → ladder failover drains every
  in-flight lane, breaker recovers).
"""

import asyncio
import collections
import itertools
import threading
import time

import pytest

from tpunode.actors import Publisher, task_registry
from tpunode.metrics import metrics
from tpunode.verify.engine import VerifyConfig, VerifyEngine
from tpunode.verify.sched import (
    AffinityMap,
    FleetDispatcher,
    LINGER,
    LanePacker,
    PRIORITIES,
    Submission,
    affinity_key,
    host_names,
    slice_payload,
)
from tpunode.watchdog import Watchdog, WatchdogConfig

from tests.test_engine import _cut_counts, make_items


def _sub(n: int, priority: str = "bulk", payload=None, **kw) -> Submission:
    fut: asyncio.Future = asyncio.get_running_loop().create_future()
    return Submission(
        payload if payload is not None else list(range(n)), fut, None,
        priority, **kw,
    )


# --- packer units ------------------------------------------------------------


@pytest.mark.asyncio
async def test_packer_priority_ordering():
    """Under saturation, block items claim lane space before mempool
    before bulk, regardless of arrival order."""
    p = LanePacker()
    bulk = _sub(3, "bulk")
    mem = _sub(2, "mempool")
    blk = _sub(4, "block")
    for s in (bulk, mem, blk):  # arrival order is worst-case
        p.push(s)
    lane = p.pop_lane(16)
    assert [s.priority for s, _, _ in lane.slices] == [
        "block", "mempool", "bulk"
    ]
    assert lane.total == 9 and p.pending() == 0


@pytest.mark.asyncio
async def test_packer_slices_across_submission_boundaries():
    """Lanes are cut at exactly ``target`` items: one submission spans
    lanes, several submissions share one."""
    p = LanePacker()
    a = _sub(3)
    b = _sub(5)
    p.push(a)
    p.push(b)
    assert p.pending() == 8
    lane1 = p.pop_lane(4)
    assert [(s is a, lo, hi) for s, lo, hi in lane1.slices] == [
        (True, 0, 3), (False, 0, 1)
    ]
    assert lane1.total == 4 and lane1.occupancy == 1.0
    assert p.pending() == 4
    lane2 = p.pop_lane(4)
    assert [(s is b, lo, hi) for s, lo, hi in lane2.slices] == [
        (True, 1, 5)
    ]
    assert p.pop_lane(4) is None


@pytest.mark.asyncio
async def test_packer_depths_metrics_and_drain():
    metrics.reset()
    p = LanePacker()
    p.push(_sub(5, "mempool"))
    p.push(_sub(2, "block"))
    assert p.depths() == {"block": 2, "mempool": 5, "ibd": 0, "bulk": 0}
    assert p.batches() == 2
    assert metrics.get(
        "sched.queue_depth", labels={"priority": "mempool"}
    ) == 5.0
    lane = p.pop_lane(4)  # block(2) + mempool(2)
    assert lane.total == 4
    assert metrics.get("sched.lanes") == 1
    assert metrics.get("sched.packed_submissions") == 2
    h = metrics.histogram("sched.pack_efficiency")
    assert h is not None and h.count == 1 and h.max == 1.0
    drained = p.drain()
    assert len(drained) == 1 and p.pending() == 0  # the residual mempool sub
    assert metrics.get(
        "sched.queue_depth", labels={"priority": "mempool"}
    ) == 0.0


@pytest.mark.asyncio
async def test_submission_delivery_out_of_order_and_failure():
    """Verdict conservation bookkeeping: slices land in any order, the
    future resolves exactly once with per-item results; a lane failure
    fails the whole submission and later deliveries are ignored."""
    s = _sub(5)
    s.deliver(3, [True, False])  # tail lane first
    assert not s.fut.done()
    s.deliver(0, [False, True, True])
    assert await s.fut == [False, True, True, True, False]

    f = _sub(4)
    f.deliver(0, [True, True])
    f.fail(RuntimeError("all rungs down"))
    with pytest.raises(RuntimeError, match="all rungs down"):
        await f.fut
    f.deliver(2, [True, True])  # late slice of a failed submission: no-op
    assert f.failed

    with pytest.raises(ValueError, match="unknown priority"):
        _sub(1, "urgent")


@pytest.mark.asyncio
async def test_packer_skips_failed_submission_remainder():
    """Review pin: once a lane failure fails a submission's waiter, its
    still-queued remainder is dropped at the next pop — whole device
    lanes must not be burned on verdicts nobody can observe."""
    p = LanePacker()
    big = _sub(10)
    tail = _sub(2)
    p.push(big)
    p.push(tail)
    lane1 = p.pop_lane(4)  # claims big[0:4]
    assert lane1.total == 4
    big.fail(RuntimeError("lane down"))
    with pytest.raises(RuntimeError):
        await big.fut
    lane2 = p.pop_lane(4)  # big's remaining 6 dropped, tail survives
    assert [(s is tail, lo, hi) for s, lo, hi in lane2.slices] == [
        (True, 0, 2)
    ]
    assert p.pending() == 0 and p.depths() == {
        "block": 0, "mempool": 0, "ibd": 0, "bulk": 0
    }


def test_slice_payload_list_and_raw():
    from tpunode.verify.raw import pack_items

    items, _ = make_items(6)
    assert slice_payload(items, 1, 4) == items[1:4]
    assert slice_payload(items, 0, 6) is items  # whole payload: no copy
    raw = pack_items(items)
    part = slice_payload(raw, 2, 5)
    assert len(part) == 3
    assert part.to_tuples() == raw.to_tuples()[2:5]


# --- the lane rule by class (ISSUE 37) ---------------------------------------
# Times are the tests' own: submissions carry explicit enqueue stamps and
# decide() / cut() are asked at a chosen ``now``.

SMALL, BIG, WAIT = 4096, 32768, 0.025
IBD = LINGER["ibd"] * WAIT  # how long a lone `ibd` submission lingers


@pytest.mark.asyncio
async def test_oldest_by_class_survives_partial_claims():
    p = LanePacker(small=SMALL, max_wait=WAIT)
    assert p.oldest_by_class() == {} and p.oldest_enqueued() is None
    ibd = _sub(10, "ibd", enqueued=10.0)
    p.push(ibd)
    p.push(_sub(6, "ibd", enqueued=11.0))
    p.push(_sub(2, "mempool", enqueued=12.0))
    assert p.oldest_by_class() == {"ibd": 10.0, "mempool": 12.0}
    assert p.oldest_enqueued() == 10.0
    lane = p.pop_lane(6)  # mempool's 2, then 4 of the oldest ibd's 10
    assert [(s.priority, lo, hi) for s, lo, hi in lane.slices] == [
        ("mempool", 0, 2), ("ibd", 0, 4)
    ]
    # the part-claimed submission is still its class's oldest, at the
    # time it was enqueued
    assert p.oldest_by_class() == {"ibd": 10.0} and ibd.enqueued == 10.0
    p.pop_lane(6)  # the rest of it
    assert p.oldest_by_class() == {"ibd": 11.0}


@pytest.mark.asyncio
async def test_oldest_by_class_survives_fleet_reroutes():
    f = FleetDispatcher(
        ["h0", "h1"], packer=LanePacker(small=SMALL, max_wait=WAIT)
    )
    on = {h: [k for k in range(64) if f.affinity.prefer(k) == h][:2]
          for h in f.hosts}
    old = _sub(5, "ibd", enqueued=5.0, affinity=on["h0"][0])
    part = _sub(9, "ibd", enqueued=6.0, affinity=on["h0"][1])
    new = _sub(3, "ibd", enqueued=7.0, affinity=on["h1"][0])
    blk = _sub(1, "block", enqueued=7.01)  # keyless: the central packer
    for s in (old, part, new, blk):
        f.push(s)
    assert f._packers["h0"].pop_lane(7).total == 7  # old whole, 2 of part
    assert f.oldest_by_class() == {"ibd": 6.0, "block": 7.01}
    f.deactivate("h0")  # part's remainder is pushed into h1's packer
    assert f._packers["h0"].pending() == 0
    # ... BEHIND nothing: it is older than what h1 held, so it is that
    # packer's oldest, under the stamp it was given at its enqueue
    assert f._packers["h1"].oldest_by_class() == {"ibd": 6.0}
    assert f.oldest_by_class() == {"ibd": 6.0, "block": 7.01}
    assert part.enqueued == 6.0 and f.uncut_pending() == 7 + 3 + 1
    lane = f._packers["h1"].pop_lane(8)
    assert [(s, lo, hi) for s, lo, hi in lane.slices] == [
        (part, 2, 9), (new, 0, 1)
    ]
    # the fleet's linger reads the same table over all its packers:
    # block's 1x (7.035) comes before the ibd left's own (7.0 + IBD)
    d = f.decide(BIG, 7.02)
    assert (d.cut, d.priority, d.size) == (None, "block", BIG)
    assert d.wait == pytest.approx(0.015)


@pytest.mark.parametrize("cls", PRIORITIES)
@pytest.mark.asyncio
async def test_lone_submission_is_cut_at_its_classes_linger(cls):
    """`ibd` lingers LINGER["ibd"] x max_wait, every class with a waiter
    1 x."""
    assert LINGER == {"block": 1, "mempool": 1, "ibd": 2, "bulk": 1}
    linger = IBD if cls == "ibd" else WAIT
    metrics.reset()
    p = LanePacker(small=SMALL, max_wait=WAIT)
    p.push(_sub(3, cls, enqueued=100.0))
    d = p.decide(BIG, 100.0)
    assert d.cut is None and d.priority == cls
    assert d.wait == pytest.approx(linger)
    d = p.decide(BIG, 100.0 + linger - 1e-4)
    assert d.cut is None and d.wait == pytest.approx(1e-4)
    d = p.decide(BIG, 100.0 + linger)
    assert (d.cut, d.priority, d.wait) == ("deadline", cls, 0.0)
    assert d.size == (SMALL if cls == "ibd" else BIG)
    assert p.cut(BIG, now=100.0 + linger).total == 3
    assert _cut_counts() == {
        'sched.lanes_cut_deadline{priority="%s"}' % cls: 1
    }


@pytest.mark.asyncio
async def test_mempool_deadline_cuts_and_takes_ibd_along():
    metrics.reset()
    p = LanePacker(small=SMALL, max_wait=WAIT)
    p.push(_sub(5, "ibd", enqueued=100.0))  # its own deadline: 100 + IBD
    due = 100.0 + (IBD + WAIT) / 2  # mempool's: sooner, enqueued later
    p.push(_sub(2, "mempool", enqueued=due - WAIT))
    d = p.decide(BIG, due - 0.005)
    assert d.cut is None and d.priority == "mempool"
    assert d.wait == pytest.approx(0.005)
    d = p.decide(BIG, due)
    # mempool's deadline, mempool's goal (the big shape), as before
    assert (d.cut, d.priority, d.size) == ("deadline", "mempool", BIG)
    lane = p.cut(BIG, now=due)
    assert [(s.priority, hi - lo) for s, lo, hi in lane.slices] == [
        ("mempool", 2), ("ibd", 5)
    ]
    assert lane.target == BIG and p.pending() == 0
    # an ibd submission old enough decides itself, though mempool is there
    p.push(_sub(5, "ibd", enqueued=200.0))
    p.push(_sub(2, "mempool", enqueued=200.0 + IBD - 0.01))
    d = p.decide(BIG, 200.0 + IBD)
    assert (d.cut, d.priority, d.size) == ("deadline", "ibd", BIG)
    assert p.cut(BIG, now=200.0 + IBD).total == 7
    assert _cut_counts() == {
        'sched.lanes_cut_deadline{priority="mempool"}': 1,
        'sched.lanes_cut_deadline{priority="ibd"}': 1,
    }


@pytest.mark.parametrize("pending", [SMALL + 1, 2 * SMALL + 5, BIG - 1])
@pytest.mark.asyncio
async def test_ibd_between_the_shapes_is_cut_at_the_small_one(pending):
    """No lane falls over the pad cliff: a chunk over ``batch_size`` is
    padded to ``device_batch``, so `ibd`-only work between the two is
    cut at exactly ``batch_size`` and the rest lingers on."""
    metrics.reset()
    p = LanePacker(small=SMALL, max_wait=WAIT)
    first = _sub(SMALL - 10, "ibd", enqueued=1.0)
    rest = _sub(pending - first.n, "ibd", enqueued=2.0)
    p.push(first)
    p.push(rest)
    d = p.decide(BIG, 2.0)  # no deadline is due: the goal is queued
    assert (d.cut, d.priority, d.size, d.wait) == ("full", "ibd", SMALL, 0.0)
    lane = p.cut(BIG, now=2.0)
    assert lane.total == lane.target == SMALL and lane.occupancy == 1.0
    assert p.pending() == pending - SMALL
    # the remainder is not re-stamped
    assert p.oldest_by_class() == {"ibd": 2.0} and rest.enqueued == 2.0
    assert rest.taken == 10
    d = p.decide(BIG, 2.0)
    if p.pending() >= SMALL:
        assert (d.cut, d.size) == ("full", SMALL)
    else:
        assert d.cut is None and d.wait == pytest.approx(IBD)
    assert _cut_counts() == {'sched.lanes_cut_full{priority="ibd"}': 1}


@pytest.mark.asyncio
async def test_an_ibd_batch_is_a_full_lane_and_a_remainder_that_lingers():
    """The IBD cells' turn (PERF.md §5, PR 45): 24 blocks of 176 items
    are one lane of 4,096 and one of 128 that waits its class's whole
    linger after the LAST block's enqueue, whatever comes or does not."""
    metrics.reset()
    p = LanePacker(small=SMALL, max_wait=WAIT)
    for i in range(24):  # the burst leaves the extract pool inside 24 ms
        p.push(_sub(176, "ibd", enqueued=10.0 + i * 0.001))
        d = p.decide(BIG, 10.0 + i * 0.001)
        assert d.cut == ("full" if i == 23 else None)
    assert p.cut(BIG, now=10.023).total == SMALL
    assert p.pending() == 24 * 176 - SMALL == 128
    d = p.decide(BIG, 10.023)
    assert d.cut is None and d.wait == pytest.approx(IBD)
    assert p.decide(BIG, 10.023 + IBD - 1e-4).cut is None
    assert p.decide(BIG, 10.023 + IBD)[:3] == ("deadline", "ibd", SMALL)
    lane = p.cut(BIG, now=10.023 + IBD)
    assert lane.total == 128 and lane.target == SMALL and p.pending() == 0
    assert _cut_counts() == {
        'sched.lanes_cut_full{priority="ibd"}': 1,
        'sched.lanes_cut_deadline{priority="ibd"}': 1,
    }


@pytest.mark.parametrize("cls,pending,want", [
    ("ibd", BIG, ("full", BIG)),
    ("ibd", BIG + 10, ("full", BIG)),
    ("block", BIG + 10, ("full", BIG)),
    ("block", SMALL + 1, (None, BIG)),  # lingers, then one padded lane
    ("bulk", BIG - 1, (None, BIG)),
])
@pytest.mark.asyncio
async def test_other_lanes_are_cut_as_before(cls, pending, want):
    metrics.reset()
    p = LanePacker(small=SMALL, max_wait=WAIT)
    p.push(_sub(pending, cls, enqueued=1.0))
    d = p.decide(BIG, 1.0)
    assert (d.cut, d.size) == want and d.priority == cls
    lane = p.cut(BIG, now=1.0 + IBD)
    assert lane.total == min(pending, BIG) and lane.target == BIG
    reason = "full" if pending >= BIG else "deadline"
    assert _cut_counts() == {
        'sched.lanes_cut_%s{priority="%s"}' % (reason, cls): 1
    }


@pytest.mark.asyncio
async def test_a_class_with_a_waiter_sets_the_big_goal_for_ibd_too():
    p = LanePacker(small=SMALL, max_wait=WAIT)
    p.push(_sub(SMALL + 100, "ibd", enqueued=1.0))
    p.push(_sub(1, "bulk", enqueued=1.0))
    d = p.decide(BIG, 1.0)
    assert (d.cut, d.priority, d.size) == (None, "bulk", BIG)
    # before the device is up there is one shape, whatever is queued
    assert p.decide(SMALL, 1.0)[:3] == ("full", "bulk", SMALL)
    # a packer built bare has one shape and no linger
    bare = LanePacker()
    bare.push(_sub(3, "ibd", enqueued=1.0))
    assert bare.decide(64, 1.0)[:3] == ("deadline", "ibd", 64)
    assert bare.cut(64).total == 3 and bare.cut(64) is None


@pytest.mark.asyncio
async def test_fleet_cuts_by_the_same_rule():
    """cut_next / pop_any cut through the chosen packer's rule."""
    metrics.reset()
    f = FleetDispatcher(
        ["h0", "h1"], packer=LanePacker(small=8, max_wait=WAIT)
    )
    key = next(k for k in range(64) if f.affinity.prefer(k) == "h1")
    f.push(_sub(11, "ibd", enqueued=1.0, affinity=key))
    lane, host = f.cut_next(64, now=1.0)
    assert (lane.total, lane.target, host) == (8, 8, "h1")
    f.push(_sub(2, "block", enqueued=1.0))
    lane, host = f.cut_next(64, now=1.0)  # the central packer's block first
    assert (lane.total, lane.target, host) == (2, 64, "h0")
    f.deactivate("h0")
    f.deactivate("h1")
    assert f.pop_any(64, now=1.0).total == 3  # the ibd left, dark fleet
    assert _cut_counts() == {
        'sched.lanes_cut_full{priority="ibd"}': 1,
        'sched.lanes_cut_deadline{priority="block"}': 1,
        'sched.lanes_cut_deadline{priority="ibd"}': 1,
    }


# --- fleet dispatcher units (ISSUE 13) ---------------------------------------


def _pop_assign(fleet, target=4):
    lane = fleet.packer.pop_lane(target)
    assert lane is not None
    return lane, fleet.assign(lane)


@pytest.mark.asyncio
async def test_fleet_assign_shallowest_with_room():
    """Lanes land on the shallowest ACTIVE host queue; a full fleet
    reports no room (the scheduler's backpressure signal) and assign
    refuses rather than piling deeper."""
    f = FleetDispatcher(["h0", "h1"], max_queue=1)
    f.push(_sub(12))
    lane1, host1 = _pop_assign(f)
    lane2, host2 = _pop_assign(f)
    assert {host1, host2} == {"h0", "h1"}  # spread, not piled
    assert not f.has_room()
    lane3 = f.packer.pop_lane(4)
    assert f.assign(lane3) is None  # both queues at max_queue
    assert f.host_depths() == {"h0": 4, "h1": 4}
    assert metrics.get("sched.host_depth", labels={"host": host1}) == 4.0
    # consuming makes room again
    assert f.take(host1) is lane1 if host1 == "h0" else lane2
    assert f.has_room()

    with pytest.raises(ValueError):
        FleetDispatcher([])
    with pytest.raises(ValueError):
        FleetDispatcher(["a", "a"])


@pytest.mark.asyncio
async def test_fleet_steal_oldest_from_deepest():
    """An idle host steals the OLDEST lane (queue head) of the DEEPEST
    peer — lanes were cut in global priority order, so the head is the
    fleet's most urgent queued work; sched.steals counts it."""
    metrics.reset()
    f = FleetDispatcher(["h0", "h1", "h2"], max_queue=4)
    f.push(_sub(4, "block"))
    f.push(_sub(4, "mempool"))
    f.push(_sub(4, "bulk"))
    lanes = []
    for _ in range(3):
        lane = f.packer.pop_lane(4)
        f._queues["h0"].append(lane)  # pile everything on h0
        lanes.append(lane)
    f.push(_sub(2, "bulk"))
    tail = f.packer.pop_lane(4)
    f._queues["h1"].append(tail)  # h1 shallower than h0
    # h2 is idle: steals h0's HEAD (the block lane), not h1's or a tail
    got = f.take("h2")
    assert got is lanes[0]
    assert [s.priority for s, _, _ in got.slices] == ["block"]
    assert f.steals == 1 and metrics.get("sched.steals") == 1
    # next steal still prefers the deepest (h0 has 8 items vs h1's 2)
    assert f.take("h2") is lanes[1]
    # own queue outranks stealing
    assert f.take("h1") is tail
    # nothing anywhere -> None
    f.take("h0"), f.take("h0")
    assert f.take("h2") is None


@pytest.mark.asyncio
async def test_fleet_requeue_and_deactivate_redistribute():
    """A lost host's queued lanes move (order-preserved) to active
    peers; a re-queued in-flight lane goes to the FRONT of the
    shallowest active peer; with no active peers lanes stay put for
    steals / the local fallback."""
    metrics.reset()
    f = FleetDispatcher(["h0", "h1", "h2"], max_queue=8)
    f.push(_sub(12))
    l0 = f.packer.pop_lane(4)
    l1 = f.packer.pop_lane(4)
    l2 = f.packer.pop_lane(4)
    f._queues["h0"].extend([l0, l1])
    f._queues["h1"].append(l2)
    moved = f.deactivate("h0")
    assert moved == 2 and not f.is_active("h0")
    assert f.active_hosts() == ["h1", "h2"]
    assert f.host_lanes("h0") == 0
    # the orphans spread to the shallowest peers, each at the FRONT
    # (they are older than anything queued): l1 -> the empty h2, then
    # l0 -> h1, AHEAD of the younger l2
    assert list(f._queues["h2"]) == [l1]
    assert list(f._queues["h1"]) == [l0, l2]
    # review r13: redistribution counts in telemetry but does NOT
    # consume the lanes' in-flight orbit budget
    assert l0.requeues == 0 and l1.requeues == 0
    assert f.requeued == 2 and metrics.get("sched.requeued") == 2
    # an in-flight lane re-queued by a dying host jumps the peer's queue
    f.deactivate("h2")  # moves l1 onto h1 too
    assert list(f._queues["h1"])[0] is l1
    back = f.requeue("h2", l0)
    assert back == "h1" and list(f._queues["h1"])[0] is l0
    assert l0.requeues == 1  # a real in-flight bounce DOES consume it
    f._queues["h1"].popleft()  # undo the double-queue for the dark case
    # every host dark: requeue REFUSES (returns None without queueing
    # or counting) — ownership stays with the caller, which resolves
    # the lane itself; queueing here too would leave two live copies
    f.deactivate("h1")
    before = list(f._queues["h1"])
    requeued_before = f.requeued
    assert f.requeue("h1", l0) is None
    assert list(f._queues["h1"]) == before
    assert f.requeued == requeued_before and l0.requeues == 1
    # reactivation restores assignment
    f.activate("h0")
    assert f.active_hosts() == ["h0"]
    # drain_lanes empties every queue (teardown contract)
    drained = f.drain_lanes()
    assert set(map(id, drained)) == {id(l0), id(l1), id(l2)}
    assert f.queued_lanes() == 0


@pytest.mark.asyncio
async def test_fleet_priority_preserved_through_pack_order():
    """block > mempool > ibd > bulk holds GLOBALLY through the fleet:
    lanes are cut in priority order and per-host queues are FIFO, so
    consuming any host's queue (or stealing) never serves a bulk lane
    while a block lane cut earlier still waits."""
    f = FleetDispatcher(["h0", "h1"], max_queue=4)
    for prio in ("bulk", "ibd", "mempool", "block"):  # worst-case arrival
        f.push(_sub(4, prio))
    order = []
    while True:
        lane = f.packer.pop_lane(4)
        if lane is None:
            break
        host = f.assign(lane)
        assert host is not None
        order.append([s.priority for s, _, _ in lane.slices])
    assert order == [["block"], ["mempool"], ["ibd"], ["bulk"]]
    # FIFO consumption per host preserves the cut order per queue
    rank = {p: i for i, p in enumerate(PRIORITIES)}
    for h in ("h0", "h1"):
        served = []
        while True:
            lane = f.take(h, steal=False)
            if lane is None:
                break
            served.extend(s.priority for s, _, _ in lane.slices)
        assert [rank[p] for p in served] == sorted(rank[p] for p in served)


@pytest.mark.asyncio
async def test_fleet_stolen_lane_resolves_exactly_once():
    """ISSUE 13 lane-requeue hardening (unit half): once host B steals a
    lane, the lane lives ONLY with B — B's delivery resolves the
    submission exactly once, and a late cancel/teardown on A has no lane
    to double-resolve; a delivery into an already-cancelled future is a
    no-op."""
    f = FleetDispatcher(["hA", "hB"], max_queue=4)
    sub = _sub(4)
    f.push(sub)
    lane = f.packer.pop_lane(4)
    assert f.assign(lane) == "hA"
    stolen = f.take("hB")  # B steals A's only lane
    assert stolen is lane
    assert f.take("hA", steal=False) is None  # A has nothing left
    stolen and sub.deliver(0, [True, False, True, True])
    assert await sub.fut == [True, False, True, True]
    # teardown-after-delivery: cancel is a no-op on a resolved future
    assert not sub.fut.cancel()

    # the reverse race: teardown cancels the future while the stolen
    # lane is still in flight — the late delivery must not blow up or
    # resurrect it
    sub2 = _sub(2)
    f.push(sub2)
    lane2 = f.packer.pop_lane(4)
    sub2.fut.cancel()
    lane2.slices[0][0].deliver(0, [True, True])  # no InvalidStateError
    assert sub2.fut.cancelled()


# --- engine pipeline ---------------------------------------------------------


@pytest.mark.asyncio
async def test_pipeline_verdict_conservation_across_lanes():
    """Odd-sized submissions slice across batch_size-8 lanes with two in
    flight: every waiter gets exactly its own items' verdicts."""
    metrics.reset()
    sizes = [3, 9, 1, 7, 5, 2]
    batches = [make_items(n, tamper_every=3) for n in sizes]
    async with VerifyEngine(
        VerifyConfig(
            backend="cpu", batch_size=8, max_wait=0.02, pipeline_depth=2,
        )
    ) as eng:
        futs = [
            asyncio.ensure_future(eng.verify(items))
            for items, _ in batches
        ]
        got = await asyncio.gather(*futs)
    for (items, expected), out in zip(batches, got):
        assert out == expected
    assert metrics.get("sched.lanes") >= 2  # really packed into lanes
    assert metrics.get("verify.items") == sum(sizes)


@pytest.mark.asyncio
async def test_pipeline_depth_one_is_serial_and_identical():
    """The A/B baseline: pipeline_depth=1 dispatches one lane at a time
    and produces the same verdicts."""
    items, expected = make_items(20, tamper_every=4)
    async with VerifyEngine(
        VerifyConfig(
            backend="cpu", batch_size=8, max_wait=0.0, pipeline_depth=1,
        )
    ) as eng:
        seen_conc = []
        orig = eng._dispatch_multi

        def spy(payloads, target=None):
            seen_conc.append(eng.dispatch_inflight())
            return orig(payloads, target)

        eng._dispatch_multi = spy
        assert await eng.verify(items) == expected
    assert seen_conc and max(seen_conc) == 1

    with pytest.raises(ValueError, match="pipeline_depth"):
        VerifyConfig(backend="cpu", warmup=False, pipeline_depth=0)


@pytest.mark.asyncio
async def test_block_priority_dispatches_before_bulk():
    """A block submission enqueued AFTER a bulk one still leads the next
    packed lane (the saturation ordering the acceptance test observes
    end-to-end)."""
    bulk_items, bulk_exp = make_items(2)
    blk_items, blk_exp = make_items(3, tamper_every=2)
    lanes: list = []
    async with VerifyEngine(
        VerifyConfig(
            backend="cpu", batch_size=1024, max_wait=0.1, pipeline_depth=1,
        )
    ) as eng:
        orig = eng._dispatch_multi

        def spy(payloads, target=None):
            lanes.append([len(p) for p in payloads])
            return orig(payloads, target)

        eng._dispatch_multi = spy
        f1 = asyncio.ensure_future(eng.verify(bulk_items))  # bulk first
        await asyncio.sleep(0)
        f2 = asyncio.ensure_future(eng.verify(blk_items, priority="block"))
        assert await f1 == bulk_exp
        assert await f2 == blk_exp
    # both lingered into ONE lane, block slice leading
    assert lanes == [[3, 2]]


@pytest.mark.asyncio
async def test_lane_failure_fails_only_carried_submissions():
    """A lane that fails on every rung fails exactly the submissions
    holding slices in it; the pipeline keeps serving."""
    a_items, _ = make_items(6)
    b_items, b_exp = make_items(2, tamper_every=1)
    async with VerifyEngine(
        VerifyConfig(
            backend="oracle", batch_size=4, max_wait=0.01, pipeline_depth=1,
        )
    ) as eng:
        calls = {"n": 0}
        orig = eng._dispatch_multi

        def flaky(payloads, target=None):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("all rungs down")
            return orig(payloads, target)

        eng._dispatch_multi = flaky
        # A spans two lanes (4 + 2); the first fails -> A's waiter fails,
        # the second delivers into the dead buffer without resurrecting it
        with pytest.raises(RuntimeError, match="all rungs down"):
            await eng.verify(a_items)
        assert await eng.verify(b_items) == b_exp
    assert calls["n"] >= 2


@pytest.mark.asyncio
async def test_oldest_inflight_drives_watchdog_stall(monkeypatch):
    """ISSUE 10 watchdog satellite: with two lanes in flight the engine
    reports the OLDEST dispatch age (a single scalar would have lost it
    when the younger lane started), and the watchdog's dispatch-stall
    signal fires on that age and clears when the pipeline drains."""
    gate = threading.Event()
    async with VerifyEngine(
        VerifyConfig(
            backend="cpu", batch_size=2, max_wait=0.0, pipeline_depth=2,
        )
    ) as eng:
        orig = eng._dispatch_multi

        def blocked(payloads, target=None):
            gate.wait(10)
            return orig(payloads, target)

        eng._dispatch_multi = blocked
        items1, exp1 = make_items(2)
        items2, exp2 = make_items(2, tamper_every=1)
        f1 = asyncio.ensure_future(eng.verify(items1))
        t0 = time.monotonic()
        while eng.dispatch_inflight() < 1:
            await asyncio.sleep(0.005)
        await asyncio.sleep(0.2)  # age the first dispatch
        f2 = asyncio.ensure_future(eng.verify(items2))
        while eng.dispatch_inflight() < 2:
            await asyncio.sleep(0.005)
        oldest = eng.dispatch_inflight_seconds()
        assert oldest >= 0.2  # the FIRST dispatch's age, not the second's
        assert eng.dispatch_inflight() == 2
        wd = Watchdog(
            WatchdogConfig(dispatch_stall_threshold=0.05), engine=eng
        )
        emitted = wd.check()
        assert [e["kind"] for e in emitted] == ["verify_dispatch"]
        assert emitted[0]["age_seconds"] >= 0.2
        assert emitted[0]["inflight"] == 2
        gate.set()
        assert await f1 == exp1
        assert await f2 == exp2
        while eng.dispatch_inflight():
            await asyncio.sleep(0.005)
        assert eng.dispatch_inflight_seconds() == 0.0
        assert wd.check() == []  # episode cleared
        assert time.monotonic() - t0 < 10


@pytest.mark.asyncio
async def test_campaign_pool_clean_through_packed_path():
    """ISSUE 10 acceptance: the adversarial campaign pool (valid +
    mutated + degenerate ECDSA/Schnorr/BIP340 shapes) driven through the
    packed pipelined dispatch as many odd-sized concurrent submissions
    — every shape keeps its required verdict across the lane slicing."""
    import random

    from benchmarks.campaign import build_pool

    items, shapes, expects = build_pool(24, random.Random(0xCA4))
    async with VerifyEngine(
        VerifyConfig(
            backend="cpu", batch_size=64, max_wait=0.01, pipeline_depth=2,
        )
    ) as eng:
        futs, k, i = [], 0, 0
        sizes = [37, 53, 11, 97, 5]
        while k < len(items):
            n = sizes[i % len(sizes)]
            i += 1
            futs.append(asyncio.ensure_future(eng.verify(items[k : k + n])))
            k += n
        got = [v for f in futs for v in await f]
    mism = [
        (j, shapes[j])
        for j, (g, e) in enumerate(zip(got, expects))
        if g != e
    ]
    assert not mism, mism[:5]
    assert metrics.get("sched.lanes") >= 2


def test_engine_mesh_gating(monkeypatch):
    """VerifyConfig.mesh_devices: off by default; a usable mesh is built
    lazily (and only once); a mesh that was asked for and cannot be built
    RAISES — it never degrades to one chip, and fleet hosts never
    silently share the default device (the compile-parity pin for the
    sharded program itself lives in test_multichip's heavy tier)."""
    jax = pytest.importorskip("jax")

    eng = VerifyEngine(VerifyConfig(backend="cpu", warmup=False))
    assert eng._mesh() is None  # default: mesh dispatch off

    eng2 = VerifyEngine(
        VerifyConfig(backend="cpu", warmup=False, mesh_devices=2)
    )
    mesh = eng2._mesh()
    assert mesh is not None and mesh.devices.size == 2
    assert eng2._mesh() is mesh  # cached, not rebuilt

    eng3 = VerifyEngine(
        VerifyConfig(backend="cpu", warmup=False, mesh_devices=4)
    )
    fleet = VerifyEngine(
        VerifyConfig(backend="cpu", warmup=False, mesh_hosts=4,
                     mesh_devices=4)
    )
    devs = jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a: devs[:1])
    with pytest.raises(RuntimeError, match="only 1 device"):
        eng3._mesh()
    with pytest.raises(RuntimeError, match="only 1 device"):
        eng3._mesh()  # asked again, raises again: never "off for good"
    with pytest.raises(RuntimeError, match="only 1 device"):
        fleet._host_mesh(fleet._hosts["h0"])
    # without mesh_devices the grid is carved from what is visible: four
    # hosts cannot be carved out of one device either
    fleet2 = VerifyEngine(
        VerifyConfig(backend="cpu", warmup=False, mesh_hosts=4)
    )
    with pytest.raises(ValueError, match="needs 4 devices"):
        fleet2._host_mesh(fleet2._hosts["h0"])
    monkeypatch.setattr(jax, "devices", lambda *a: devs)
    assert eng3._mesh().devices.size == 4  # the chips came back
    subs = [fleet._host_mesh(hs) for hs in fleet._hosts.values()]
    assert len({m.devices.flat[0].id for m in subs}) == 4


# --- fleet engine integration (ISSUE 13) -------------------------------------


def _fake_fleet_device(monkeypatch):
    """The chaos-sim device extended to the fleet's sharded rung: host
    sub-meshes build for real (cheap — 1-D meshes over the virtual CPU
    devices, no compile) but both device dispatch entry points compute
    verdicts on the host, so fleet tests run the genuine tpu rung with
    per-host breakers engaged and zero XLA compiles."""
    import tpunode.verify.multichip as MC
    from tests.test_chaos import _fake_device
    from tpunode.verify.ecdsa_cpu import verify_batch_cpu

    _fake_device(monkeypatch)
    monkeypatch.setattr(
        MC, "dispatch_raw_sharded",
        lambda raw, mesh, pad_to=None, kernel="auto": (
            verify_batch_cpu(raw.to_tuples()), len(raw)
        ),
    )


@pytest.mark.asyncio
async def test_fleet_engine_verdict_conservation():
    """mesh_hosts=4 on the cpu rung: odd-sized concurrent submissions
    slice across lanes dispatched by four host workers — every waiter
    gets exactly its own items' verdicts and the fleet stats surface."""
    metrics.reset()
    sizes = [3, 9, 1, 7, 5, 2, 11, 4]
    batches = [make_items(n, tamper_every=3) for n in sizes]
    async with VerifyEngine(
        VerifyConfig(
            backend="cpu", batch_size=8, max_wait=0.02, pipeline_depth=1,
            mesh_hosts=4, warmup=False,
        )
    ) as eng:
        futs = [
            asyncio.ensure_future(eng.verify(items))
            for items, _ in batches
        ]
        got = await asyncio.gather(*futs)
        st = eng.stats()["fleet"]
    for (items, expected), out in zip(batches, got):
        assert out == expected
    assert st["hosts"] == 4 and len(st["active"]) == 4
    assert metrics.get("sched.lanes") >= 2
    assert metrics.get("verify.items") == sum(sizes)
    assert task_registry.report_leaks() == []

    with pytest.raises(ValueError, match="mesh_hosts"):
        VerifyConfig(backend="cpu", warmup=False, mesh_hosts=1)
    with pytest.raises(ValueError, match="fleet_queue"):
        VerifyConfig(backend="cpu", warmup=False, mesh_hosts=2,
                     fleet_queue=0)


@pytest.mark.asyncio
async def test_fleet_engine_steals_from_blocked_host():
    """Work stealing end to end: with h0's dispatch wedged, its queued
    lanes are stolen and served by h1 — throughput degrades to the
    healthy host instead of queueing behind the sick one."""
    metrics.reset()
    gate = threading.Event()
    async with VerifyEngine(
        VerifyConfig(
            backend="cpu", batch_size=4, max_wait=0.0, pipeline_depth=1,
            mesh_hosts=2, fleet_queue=2, warmup=False,
        )
    ) as eng:
        orig = eng._dispatch_multi

        def gated(payloads, target=None, host=None, backend=None):
            if host is not None and host.name == "h0":
                gate.wait(10)
            return orig(payloads, target, host=host, backend=backend)

        eng._dispatch_multi = gated
        batches = [make_items(4, tamper_every=3) for _ in range(8)]
        futs = [
            asyncio.ensure_future(eng.verify(items))
            for items, _ in batches
        ]
        # h1 drains everything stealable while h0 wedges on (at most)
        # its one in-flight lane
        deadline = time.monotonic() + 10
        while sum(f.done() for f in futs) < len(futs) - 1:
            assert time.monotonic() < deadline, "h1 failed to steal"
            await asyncio.sleep(0.01)
        assert eng._fleet.steals >= 1
        gate.set()
        got = await asyncio.gather(*futs)
    for (items, expected), out in zip(batches, got):
        assert out == expected
    assert metrics.get("sched.steals") >= 1


@pytest.mark.asyncio
async def test_fleet_partition_requeues_exactly_once_and_rejoins():
    """ISSUE 13 degradation: an injected host partition deactivates the
    host and re-queues its in-flight lane onto the peer — the lane
    resolves exactly once (correct verdicts, no double delivery) — and
    the cooldown-paced canary rejoins the host once the fault clears."""
    from tpunode.chaos import ChaosPlan, chaos

    metrics.reset()
    chaos.install(ChaosPlan.parse(
        "seed=3;mesh.dispatch:partition:match=h1,n=2"
    ))
    try:
        async with VerifyEngine(
            VerifyConfig(
                backend="cpu", batch_size=8, max_wait=0.005,
                pipeline_depth=1, mesh_hosts=2, warmup=False,
                breaker_cooldown=0.1,
            )
        ) as eng:
            downs = []
            for _ in range(10):
                batches = [make_items(6, tamper_every=3) for _ in range(6)]
                got = await asyncio.gather(
                    *(eng.verify(i) for i, _ in batches)
                )
                for (items, expected), out in zip(batches, got):
                    assert out == expected  # requeued lanes: verdicts once
                downs.append(len(eng._fleet.active_hosts()))
                await asyncio.sleep(0.01)
            assert min(downs) == 1, "partition never deactivated h1"
            assert eng._fleet.requeued >= 1
            assert metrics.get("mesh.host_losses") >= 1
            # the plan is exhausted: the canary rejoin restores the fleet
            deadline = time.monotonic() + 5
            while (
                len(eng._fleet.active_hosts()) < 2
                and time.monotonic() < deadline
            ):
                await asyncio.sleep(0.02)
            assert len(eng._fleet.active_hosts()) == 2
        assert task_registry.report_leaks() == []
    finally:
        chaos.uninstall()


@pytest.mark.asyncio
async def test_fleet_dark_requeue_bound_serves_locally():
    """Every host partitioned: new lanes take the scheduler's local
    fallback, and a lane bouncing between dying hosts exhausts its
    requeue bound and is served through the local cpu ladder — waiters
    always resolve, nothing double-resolves, nothing strands."""
    from tpunode.chaos import ChaosPlan, chaos

    chaos.install(ChaosPlan.parse(
        "seed=9;mesh.dispatch:partition:p=1"  # every fleet dispatch dies
    ))
    try:
        async with VerifyEngine(
            VerifyConfig(
                backend="cpu", batch_size=8, max_wait=0.005,
                pipeline_depth=1, mesh_hosts=2, warmup=False,
                breaker_cooldown=0.05,
            )
        ) as eng:
            batches = [make_items(5, tamper_every=2) for _ in range(8)]
            async with asyncio.timeout(30):
                got = await asyncio.gather(
                    *(eng.verify(i) for i, _ in batches)
                )
            for (items, expected), out in zip(batches, got):
                assert out == expected
            assert eng.dispatch_inflight() == 0
    finally:
        chaos.uninstall()
    assert task_registry.report_leaks() == []


@pytest.mark.asyncio
async def test_fleet_shutdown_cancels_queued_and_inflight():
    """ISSUE 13 requeue hardening (teardown half): engine exit with a
    wedged host cancels in-flight lanes' futures AND the futures of
    lanes still sitting in host queues — no waiter hangs, no task
    leaks, and late deliveries into cancelled futures are no-ops."""
    gate = threading.Event()
    eng = VerifyEngine(
        VerifyConfig(
            backend="cpu", batch_size=4, max_wait=0.0, pipeline_depth=1,
            mesh_hosts=2, fleet_queue=2, warmup=False,
        )
    )
    futs = []
    async with eng:
        orig = eng._dispatch_multi

        def wedged(payloads, target=None, host=None, backend=None):
            gate.wait(10)
            return orig(payloads, target, host=host, backend=backend)

        eng._dispatch_multi = wedged
        for _ in range(8):
            items, _ = make_items(4)
            futs.append(asyncio.ensure_future(eng.verify(items)))
        while eng.dispatch_inflight() < 2:
            await asyncio.sleep(0.005)
        await asyncio.sleep(0.05)  # let the scheduler queue the rest
    gate.set()  # unblock the abandoned dispatch threads
    for f in futs:
        with pytest.raises(asyncio.CancelledError):
            await f
    assert task_registry.report_leaks() == []


@pytest.mark.asyncio
async def test_fleet_chip_loss_shrinks_then_canary_regrows(monkeypatch, threadsan_armed):
    """Chip-by-chip degradation: a device loss on one multi-chip host
    halves that host's sub-mesh (largest still-healthy half) while the
    OTHER host keeps its full row; the failed lane still resolves via
    the ladder; the breaker's canary close re-grows the sub-mesh."""
    from tpunode.chaos import ChaosPlan, chaos

    jax = pytest.importorskip("jax")
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device conftest mesh")
    _fake_fleet_device(monkeypatch)
    chaos.install(ChaosPlan.parse(
        "seed=5;mesh.dispatch:device_loss:match=h0:tpu,n=1"
    ))
    try:
        async with VerifyEngine(
            VerifyConfig(
                backend="auto", batch_size=8, device_batch=8,
                min_tpu_batch=1, max_wait=0.0, pipeline_depth=1,
                mesh_hosts=2, warmup=True, breaker_threshold=1,
                breaker_cooldown=0.05,
            )
        ) as eng:
            assert eng._warmup_done.wait(5)
            assert eng.device_state == "ready"
            h0 = eng._hosts["h0"]
            shrunk = False
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                items, expected = make_items(8, tamper_every=3)
                assert await eng.verify(items) == expected
                if h0.chips == 2:
                    shrunk = True  # 4-chip row halved by the device loss
                if shrunk and h0.chips == 4:
                    break
                await asyncio.sleep(0.01)
            assert shrunk, "device loss never shrank h0's sub-mesh"
            assert h0.chips == 4, "canary close never re-grew the mesh"
            # the sick host degraded ALONE: h1's row was never shrunk
            # (0 = not yet built, 4 = built at full width)
            assert eng._hosts["h1"].chips in (0, 4)
            assert metrics.get("mesh.shrinks") >= 1
            assert metrics.get("mesh.regrows") >= 1
    finally:
        chaos.uninstall()
    # threadsan (ISSUE 18): shrink + canary regrow is deadlock-free
    assert threadsan_armed.lock_cycles == 0, threadsan_armed.findings
    assert threadsan_armed.lock_reentries == 0, threadsan_armed.findings


@pytest.mark.asyncio
async def test_fleet_chip_loss_regrows_without_breaker_open(monkeypatch, threadsan_armed):
    """Review r13: at the DEFAULT breaker threshold a single device
    loss only reaches 'degraded' — the shrink must still re-grow (via
    the cooldown-paced success probe), not pin the host at half width
    forever behind a breaker that reads 'ready'."""
    from tpunode.chaos import ChaosPlan, chaos

    jax = pytest.importorskip("jax")
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device conftest mesh")
    _fake_fleet_device(monkeypatch)
    chaos.install(ChaosPlan.parse(
        "seed=6;mesh.dispatch:device_loss:match=h0:tpu,n=1"
    ))
    try:
        async with VerifyEngine(
            VerifyConfig(
                backend="auto", batch_size=8, device_batch=8,
                min_tpu_batch=1, max_wait=0.0, pipeline_depth=1,
                mesh_hosts=2, warmup=True,
                breaker_threshold=3,  # the default shape: loss => degraded
                breaker_cooldown=0.05,
            )
        ) as eng:
            assert eng._warmup_done.wait(5)
            h0 = eng._hosts["h0"]
            shrunk = False
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                items, expected = make_items(8, tamper_every=3)
                assert await eng.verify(items) == expected
                if h0.chips == 2:
                    shrunk = True
                    assert h0.breaker.state in ("degraded", "ready")
                    assert eng.breaker.opens == 0  # global untouched
                if shrunk and h0.chips == 4:
                    break
                await asyncio.sleep(0.01)
            assert shrunk, "device loss never shrank h0's sub-mesh"
            assert h0.chips == 4, (
                "shrink without a breaker open never re-grew"
            )
            assert h0.breaker.opens == 0  # the gap scenario: no open ever
    finally:
        chaos.uninstall()
    # threadsan (ISSUE 18): probe-paced regrow is deadlock-free
    assert threadsan_armed.lock_cycles == 0, threadsan_armed.findings
    assert threadsan_armed.lock_reentries == 0, threadsan_armed.findings


@pytest.mark.asyncio
async def test_fleet_mesh_shrink_soak(monkeypatch, threadsan_armed):
    """ISSUE 13 acceptance SOAK: 8 fleet hosts under staged partitions —
    the active set shrinks 8 -> ... -> 1 (h0 is never partitioned) while
    traffic flows, then re-grows to 8 as the canaries clear.  Every
    unique item gets exactly one clean verdict across the whole
    degradation cycle, and zero tasks leak."""
    from tpunode.chaos import ChaosPlan, chaos

    _fake_fleet_device(monkeypatch)
    # Staged losses: four hosts die on their first dispatch, two more
    # after a couple of rounds, one last — each stays dead for n fires
    # of its canary probes, then recovers.  h0 survives throughout.
    plan = ";".join(
        ["seed=1337"]
        + [f"mesh.dispatch:partition:match=h{i},n=14" for i in (4, 5, 6, 7)]
        + [f"mesh.dispatch:partition:match=h{i},after=2,n=12" for i in (2, 3)]
        + ["mesh.dispatch:partition:match=h1,after=4,n=10"]
    )
    chaos.install(ChaosPlan.parse(plan))
    # The shrink trajectory is read from the mesh.host_down/host_up
    # events (each carries the post-transition active_hosts count), NOT
    # by sampling active_hosts() on a timer — under suite load a whole
    # loss cascade can complete between two wall-clock samples (review
    # r13: the sampled variant flaked with observed={1, 8}).
    from tpunode.events import events as _events

    sizes: list[int] = []
    unsub = _events.subscribe(
        lambda ev: sizes.append(ev["active_hosts"])
        if ev.get("type") in ("mesh.host_down", "mesh.host_up")
        else None
    )
    try:
        async with VerifyEngine(
            VerifyConfig(
                backend="auto", batch_size=8, device_batch=8,
                min_tpu_batch=1, max_wait=0.002, pipeline_depth=1,
                mesh_hosts=8, warmup=True, breaker_threshold=2,
                breaker_cooldown=0.05, fleet_queue=1,
            )
        ) as eng:
            assert eng._warmup_done.wait(5)
            deadline = time.monotonic() + 40
            rounds = 0
            while time.monotonic() < deadline:
                batches = [
                    make_items(6, tamper_every=3) for _ in range(10)
                ]
                got = await asyncio.gather(
                    *(eng.verify(i) for i, _ in batches)
                )
                for (items, expected), out in zip(batches, got):
                    # exactly-once, clean: gather returning the right
                    # verdict lists IS verdict conservation — a dropped
                    # slice hangs the future, a doubled one corrupts it
                    assert out == expected
                rounds += 1
                if (
                    sizes
                    and min(sizes) == 1
                    and len(eng._fleet.active_hosts()) == 8
                ):
                    break
            assert sizes and min(sizes) == 1, (
                f"fleet never shrank to 1: {sorted(set(sizes))}"
            )
            # staged: the transition log passes through several distinct
            # fleet sizes on the way down (7 hosts die one by one)
            assert len(set(sizes)) >= 3, f"expected staged shrink: {sizes}"
            assert len(eng._fleet.active_hosts()) == 8, "never re-grew"
            assert eng._fleet.requeued >= 1
            assert metrics.get("mesh.host_losses") >= 7
            assert eng.dispatch_inflight() == 0
            # NOTE: no minimum-round assert — under full-suite load two
            # slow rounds can span the whole 8→1→8 cycle, and the
            # conservation proof is per-submission regardless (a round
            # count is traffic volume, not an invariant; it flaked at
            # rounds==2 on a loaded box)
            assert rounds >= 1
    finally:
        unsub()
        chaos.uninstall()
    assert task_registry.report_leaks() == []
    # threadsan (ISSUE 18): the whole 8->1->8 cycle — per-host breakers,
    # fleet dispatcher, canary probes, ledger charges — orders cleanly
    assert threadsan_armed.lock_cycles == 0, threadsan_armed.findings
    assert threadsan_armed.lock_reentries == 0, threadsan_armed.findings


# --- host-affine feeds (ISSUE 19) --------------------------------------------


def test_affinity_map_stable_placement():
    """Rendezvous placement invariants: keys spread across the fleet,
    removing a host remaps ONLY that host's keys, and a rejoin restores
    the original placement exactly (shrink never re-shuffles the
    steady state)."""
    hosts = host_names(4)
    assert hosts == ["h0", "h1", "h2", "h3"]
    amap = AffinityMap(hosts)
    keys = list(range(20000))
    home = {k: amap.prefer(k) for k in keys}
    # balance: a uniform mix lands every host within a loose band
    counts = collections.Counter(home.values())
    for h in hosts:
        assert 0.15 < counts[h] / len(keys) < 0.35, counts
    # shrink: only h2's keys move, everyone else's argmax is unchanged
    active = [h for h in hosts if h != "h2"]
    for k in keys:
        routed = amap.route(k, active)
        if home[k] == "h2":
            assert routed != "h2"
        else:
            assert routed == home[k]
    # rejoin: routing over the full set IS the original placement
    assert all(amap.route(k, hosts) == home[k] for k in keys)
    # dark fleet: no active host -> None (caller falls back to central)
    assert amap.route(1, []) is None
    # the txid key is the first 8 digest bytes, little-endian
    assert affinity_key(bytes(range(1, 33))) == int.from_bytes(
        bytes(range(1, 9)), "little"
    )


@pytest.mark.asyncio
async def test_fleet_affine_routing_and_teardown_drops_series():
    """Keyed submissions land on their rendezvous home host (routed
    counters up, zero spills with the fleet healthy), verdicts conserve
    through the affine path, and engine teardown retires every
    host-labeled series from the registry (satellite a)."""
    metrics.reset()
    amap = AffinityMap(host_names(4))
    batches = [make_items(5, tamper_every=3) for _ in range(12)]
    async with VerifyEngine(
        VerifyConfig(
            backend="cpu", batch_size=8, max_wait=0.02, pipeline_depth=1,
            mesh_hosts=4, warmup=False,
        )
    ) as eng:
        futs = [
            asyncio.ensure_future(eng.verify(items, affinity=k))
            for k, (items, _) in enumerate(batches)
        ]
        got = await asyncio.gather(*futs)
        st = eng.stats()["fleet"]
        assert eng._fleet.affinity.prefer(0) == amap.prefer(0)  # same map
        # while the engine is live, the affine feed surface is populated
        assert set(st["feed_depths"]) == set(host_names(4))
        assert set(st["feed_idle"]) == set(host_names(4))
        routed = metrics.series("sched.affinity_routed")
    for (items, expected), out in zip(batches, got):
        assert out == expected
    assert st["affinity"]["routed"] == len(batches)
    assert st["affinity"]["spilled"] == 0
    assert sum(routed.values()) == len(batches)
    for lk, _ in routed.items():
        assert dict(lk)["host"] in host_names(4)
    # teardown dropped every host= series (registry half; the Timeline
    # half is pinned in test_timeseries)
    assert metrics.series("sched.host_depth") == {}
    assert metrics.series("sched.feed_idle") == {}
    assert metrics.series("sched.affinity_routed") == {}
    assert task_registry.report_leaks() == []


@pytest.mark.asyncio
async def test_idle_host_steals_misaffined_lane():
    """Affinity is a placement hint, not a fence (satellite c): with h1
    wedged, lanes homed to h1 by their keys are stolen and served by
    idle h0 — verdicts still conserve and the steal counters move."""
    metrics.reset()
    gate = threading.Event()
    amap = AffinityMap(host_names(2))
    h1_keys = [k for k in range(200) if amap.prefer(k) == "h1"]
    assert len(h1_keys) >= 8
    async with VerifyEngine(
        VerifyConfig(
            backend="cpu", batch_size=4, max_wait=0.0, pipeline_depth=1,
            mesh_hosts=2, fleet_queue=2, warmup=False,
        )
    ) as eng:
        orig = eng._dispatch_multi

        def gated(payloads, target=None, host=None, backend=None):
            if host is not None and host.name == "h1":
                gate.wait(10)
            return orig(payloads, target, host=host, backend=backend)

        eng._dispatch_multi = gated
        batches = [make_items(4, tamper_every=3) for _ in range(8)]
        futs = [
            asyncio.ensure_future(eng.verify(items, affinity=k))
            for k, (items, _) in zip(h1_keys, batches)
        ]
        # every lane was homed to the wedged host; h0 must steal through
        # the backlog while h1 wedges on (at most) its one in-flight lane
        deadline = time.monotonic() + 10
        while sum(f.done() for f in futs) < len(futs) - 1:
            assert time.monotonic() < deadline, "h0 never stole"
            await asyncio.sleep(0.01)
        assert eng._fleet.steals >= 1
        assert eng._fleet.host_steals["h0"] >= 1
        # the keys ROUTED home (h1 stayed active); stealing isn't a spill
        assert eng._fleet.affinity_routed == len(batches)
        assert eng._fleet.affinity_spilled == 0
        gate.set()
        got = await asyncio.gather(*futs)
    for (items, expected), out in zip(batches, got):
        assert out == expected
    assert task_registry.report_leaks() == []


@pytest.mark.asyncio
async def test_fleet_affine_partition_soak(threadsan_armed):
    """Satellite c SOAK: partition -> requeue -> rejoin re-run with
    affinity ON.  Every submission carries a key; h1's partition
    deactivates it and its keyed work re-routes (spill or requeue)
    while h0 serves; the rejoin restores home placement — and every
    waiter still sees exactly one clean verdict throughout, with zero
    threadsan findings."""
    from tpunode.chaos import ChaosPlan, chaos

    metrics.reset()
    amap = AffinityMap(host_names(2))
    keys = itertools.cycle(
        [k for k in range(64) if amap.prefer(k) == "h1"][:4]
        + [k for k in range(64) if amap.prefer(k) == "h0"][:2]
    )
    chaos.install(ChaosPlan.parse(
        "seed=7;mesh.dispatch:partition:match=h1,n=2"
    ))
    try:
        async with VerifyEngine(
            VerifyConfig(
                backend="cpu", batch_size=8, max_wait=0.005,
                pipeline_depth=1, mesh_hosts=2, warmup=False,
                breaker_cooldown=0.1,
            )
        ) as eng:
            downs = []
            for _ in range(10):
                batches = [make_items(6, tamper_every=3) for _ in range(6)]
                got = await asyncio.gather(
                    *(
                        eng.verify(i, affinity=next(keys))
                        for i, _ in batches
                    )
                )
                for (items, expected), out in zip(batches, got):
                    assert out == expected  # exactly-once through spills
                downs.append(len(eng._fleet.active_hosts()))
                await asyncio.sleep(0.01)
            assert min(downs) == 1, "partition never deactivated h1"
            assert eng._fleet.requeued >= 1
            # h1-homed keys kept flowing while it was down: routed to the
            # runner-up (spill) — the affine path never strands work
            assert eng._fleet.affinity_spilled >= 1
            assert eng._fleet.affinity_routed >= 1
            deadline = time.monotonic() + 5
            while (
                len(eng._fleet.active_hosts()) < 2
                and time.monotonic() < deadline
            ):
                await asyncio.sleep(0.02)
            assert len(eng._fleet.active_hosts()) == 2
        assert task_registry.report_leaks() == []
    finally:
        chaos.uninstall()
    # threadsan (ISSUE 18): the affine feed path — per-host packers,
    # spills, deactivation re-routes — introduces no lock disorder
    assert threadsan_armed.lock_cycles == 0, threadsan_armed.findings
    assert threadsan_armed.lock_reentries == 0, threadsan_armed.findings


# --- acceptance: fakenet node through the full pipeline ----------------------


def _lane_recorder(eng):
    """Wrap the engine's packer to record each dispatched lane's slice
    priorities (in lane order)."""
    recorded: list[list[str]] = []
    orig = eng._packer.pop_lane

    def spy(target):
        lane = orig(target)
        if lane is not None:
            recorded.append([s.priority for s, _, _ in lane.slices])
        return lane

    eng._packer.pop_lane = spy
    return recorded


@pytest.mark.asyncio
async def test_streaming_pipeline_fakenet_acceptance():
    """ISSUE 10 acceptance: peers pushing interleaved blocks + mempool
    txs through parallel extraction and packed pipelined dispatch —
    every unique tx exactly one clean verdict, per-lane priority
    ordering holds, the UTXO watermark only ever advances, zero task
    leaks."""
    from benchmarks.txgen import gen_signed_txs
    from tests.fakenet import TxRelay, dummy_peer_connect, poll_until
    from tests.fixtures import all_blocks
    from tpunode import (
        BCH_REGTEST, ChainSynced, Node, NodeConfig, TxVerdict, txextract,
    )
    from tpunode.mempool import MempoolConfig
    from tpunode.peer import PeerConnected, PeerMessage
    from tpunode.store import MemoryKV
    from tpunode.util import Reader
    from tpunode.wire import Block, BlockHeader, MsgBlock

    if not txextract.have_native_extract():
        pytest.skip("native extractor unavailable")
    net = BCH_REGTEST
    txs = gen_signed_txs(48, inputs_per_tx=1, seed=0x10AC)
    blocks = all_blocks()
    # a SIGNED block (wire-round-tripped, as a peer's: it carries raw
    # bytes): its sig items ride block-priority lanes; the coinbase-only
    # chain blocks drive the UTXO watermark
    blk_txs = gen_signed_txs(24, inputs_per_tx=1, seed=0xB10C)
    hdr = BlockHeader(1, b"\x00" * 32, b"\x00" * 32, 0, 0x207FFFFF, 0)
    signed_block = Block.deserialize(
        Reader(Block(hdr, tuple(blk_txs)).serialize())
    )
    assert signed_block.raw_txs is not None
    unique = (
        {t.txid for t in txs}
        | {t.txid for t in blk_txs}
        | {t.txid for b in blocks for t in b.txs}
    )
    relays = {
        18801: TxRelay(txs, announce=True, mode="serve"),
        18802: TxRelay(announce=False, push=txs),
        18803: TxRelay(announce=False, push=txs),
    }
    pub = Publisher(name="pipeline-acceptance", maxsize=None)
    cfg = NodeConfig(
        net=net,
        store=MemoryKV(),
        pub=pub,
        peers=[f"[::1]:{port}" for port in relays],
        discover=False,
        max_peers=len(relays),
        connect=lambda sa: dummy_peer_connect(
            net, blocks, relay=relays.get(sa[1])
        ),
        verify=VerifyConfig(
            backend="cpu", max_wait=0.01, batch_size=64, pipeline_depth=2,
        ),
        mempool=MempoolConfig(tick_interval=0.05),
        extract_workers=2,
        utxo=True,
    )
    verdict_counts: dict = {}
    watermarks: list[int] = []
    async with pub.subscription() as sub:
        async with Node(cfg) as node:
            lanes = _lane_recorder(node.verify_engine)
            async with asyncio.timeout(60):
                peer = None
                while True:
                    ev = await sub.receive()
                    if isinstance(ev, PeerConnected) and peer is None:
                        peer = ev.peer
                    if isinstance(ev, ChainSynced):
                        break
                assert peer is not None
                # interleave block delivery with the ongoing tx firehose
                for b in blocks:
                    node._peer_pub.publish(PeerMessage(peer, MsgBlock(b)))
                node._peer_pub.publish(
                    PeerMessage(peer, MsgBlock(signed_block))
                )
                while unique - set(verdict_counts):
                    ev = await sub.receive()
                    watermarks.append(node.utxo.height)
                    if isinstance(ev, TxVerdict):
                        assert ev.error is None, f"faulted verdict: {ev}"
                        verdict_counts[ev.txid] = (
                            verdict_counts.get(ev.txid, 0) + 1
                        )
            # -- verdict conservation: exactly one verdict per unique tx
            dupes = {k: v for k, v in verdict_counts.items() if v != 1}
            assert not dupes, f"non-singular verdicts: {len(dupes)}"
            # -- UTXO watermark monotone, and it caught up
            assert watermarks == sorted(watermarks)
            await poll_until(
                lambda: node.utxo.height == len(blocks),
                what="utxo watermark catch-up",
            )
            # -- parallel extraction actually engaged
            assert node._extract_pool is not None
            st = node.stats()["verify"]
            assert st["extract_workers"] == 2
            assert st["pipeline_depth"] == 2
    # -- per-lane priority ordering: within every packed lane, block
    # slices lead mempool slices lead bulk slices
    assert lanes, "no lanes dispatched?"
    rank = {p: i for i, p in enumerate(PRIORITIES)}
    for lane in lanes:
        order = [rank[p] for p in lane]
        assert order == sorted(order), f"priority inversion in lane: {lane}"
    assert any("block" in lane for lane in lanes)
    assert any("mempool" in lane for lane in lanes)
    # -- zero task leaks
    assert task_registry.report_leaks() == []


@pytest.mark.asyncio
async def test_pipeline_chaos_device_loss_drains_inflight(monkeypatch):
    """Chaos variant: device_loss faults landing mid-pipeline (two lanes
    in flight) fail over down the ladder — every waiter gets verdicts,
    the breaker opens on the repeated loss and recovers to ready once
    the fault plan is exhausted."""
    from tests.test_chaos import _fake_device
    from tpunode.chaos import ChaosPlan, chaos

    _fake_device(monkeypatch)
    chaos.install(ChaosPlan.parse(
        "seed=77;engine.dispatch:device_loss:match=tpu,after=1,n=3"
    ))
    try:
        cfg = VerifyConfig(
            backend="auto", max_wait=0.005, batch_size=16, device_batch=16,
            min_tpu_batch=1, pipeline_depth=2, breaker_threshold=2,
            breaker_cooldown=0.2,
        )
        async with VerifyEngine(cfg) as eng:
            eng._warmup_done.wait(5)
            assert eng.device_state == "ready"
            batches = [make_items(6, tamper_every=3) for _ in range(10)]
            # concurrent submissions keep both pipeline slots busy while
            # the injected losses fire (60 items over 16-wide lanes = 4
            # lanes through a depth-2 pipeline)
            results = await asyncio.gather(
                *(eng.verify(items) for items, _ in batches)
            )
            for (items, expected), got in zip(batches, results):
                assert got == expected  # failover: verdicts, never faults
            # keep concurrent traffic flowing until every injected loss
            # fired and the breaker opened
            deadline = time.monotonic() + 20.0
            while eng.breaker.opens < 1 and time.monotonic() < deadline:
                more = [make_items(6, tamper_every=2) for _ in range(4)]
                got = await asyncio.gather(
                    *(eng.verify(items) for items, _ in more)
                )
                for (items, expected), out in zip(more, got):
                    assert out == expected
            assert eng.breaker.opens >= 1, chaos.stats()
            # keep traffic flowing until the canary closes the breaker
            items, expected = make_items(4, tamper_every=2)
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline:
                assert await eng.verify(items) == expected
                if eng.breaker.state == "ready":
                    break
                await asyncio.sleep(0.02)
            assert eng.breaker.state == "ready"
            assert eng.dispatch_inflight() == 0  # nothing stranded
    finally:
        chaos.uninstall()
