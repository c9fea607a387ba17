"""Lane-packing verify scheduler (ISSUE 10).

The engine used to dispatch FIFO-coalesced submissions: whole payloads
were popped until the fill target was crossed, and a sub-``min_tpu_batch``
remainder was shunted to the CPU rung.  Under many-tenant traffic (Flow's
consensus/compute separation, arXiv:1909.05832: one verify service fed by
many light ingest sources) that wastes device occupancy twice — lanes
dispatch part-empty, and small tails pay a CPU step that the *next*
submission's items could have filled.

This module owns the queue instead:

* **Priority classes** — ``block`` > ``mempool`` > ``ibd`` > ``bulk``.
  Live block-ingest items always pack (and therefore dispatch) ahead of
  mempool relay, which packs ahead of IBD backfill (ISSUE 11: the fetch
  planner's historical blocks must not starve fresh traffic), which packs
  ahead of bulk/re-index traffic.  Within a class, FIFO.
* **Cross-submission packing** — :meth:`LanePacker.pop_lane` slices
  queued payloads so every lane is exactly ``target`` items (the
  compiled device shape) regardless of how the work arrived.  One
  submission may span several lanes; several submissions may share one.
  Per-item futures still resolve exactly once with exactly their items'
  verdicts (verdict conservation — the chaos SOAK invariant).
* **Max-linger deadline, by class** — a lone small submission is
  dispatched as a partial lane once its linger expires;
  ``min_tpu_batch`` degrades from a routing rule to a shed-only floor
  applied at dispatch time.  The linger is a latency budget, so it
  belongs to the class of what is queued (ISSUE 37): each class's oldest
  unclaimed submission may wait ``LINGER[class] × max_wait``, and the
  earliest such deadline over the queued classes cuts the lane, taking
  lower classes along in the room left.  ``block``, ``mempool`` and
  ``bulk`` have a caller waiting on a verdict (multiple 1); nobody waits
  on one block of ``ibd`` backfill — the planner's window does — so it
  lingers longer, toward a full lane.
* **Fill goal, by class** — queued work is a full lane at the big
  compiled shape, except while only ``SMALL_LANE`` classes (``ibd``) are
  queued: the planner's window never queues a big lane, the slot cost is
  flat across the two shapes, so such work is full at the small shape and
  is cut at exactly that — never a little over it, which the device pads
  to the big shape (:func:`decide_lane`).

The packer is plain data + arithmetic on the event loop; the engine's
pipeline (``VerifyConfig.pipeline_depth``) pulls lanes from it.

Pod scale (ISSUE 13): :class:`FleetDispatcher` promotes the packer into
a cross-host work-stealing dispatcher — one lane queue per mesh host,
fed from the shared packer in global priority order (block > mempool >
ibd > bulk is preserved because lanes are CUT in priority order and
every per-host queue is FIFO), with idle hosts stealing whole packed
lanes from the deepest peer queue.  Steals move the OLDEST lane (queue
head): verification lanes have no cache locality worth protecting, so
unlike classic tail-stealing the head steal strictly improves the
highest-priority lane's latency.  Lane granularity keeps verdict
conservation intact — a stolen or re-queued lane still resolves its
carried submissions exactly once, because a lane lives in exactly one
queue (or exactly one host's in-flight set) at a time and
:class:`Submission` bookkeeping is slice-indexed, not host-indexed.

Host-affine feeds (ISSUE 19): at pod scale the single shared packer is
the feed bottleneck — every tx funnels through one queue before a lane
ships to the host that verifies it.  :class:`AffinityMap` gives every
submission key a stable home host via rendezvous (highest-random-weight)
hashing: removing a host remaps ONLY that host's keys, and a rejoin
restores exactly the old placement, so a rebalance never re-shuffles
the steady state.  :class:`FleetDispatcher` grows one
:class:`LanePacker` PER HOST fed by :meth:`FleetDispatcher.push`;
lanes are cut per-host but in GLOBAL priority order (the feed loop
compares per-packer head classes before cutting), and head-steal stays
as the anti-starvation fallback — affinity is a placement hint, never
a starvation source.

Telemetry: ``sched.queue_depth{priority=}`` gauges, the
``sched.pack_efficiency`` histogram (lane occupancy at dispatch),
``sched.lanes`` / ``sched.packed_submissions`` counters,
``sched.lanes_cut_full{priority=}`` / ``sched.lanes_cut_deadline{priority=}``
(why each lane was cut, by the class that decided), and the fleet
surface — ``sched.host_depth{host=}`` gauges, ``sched.steals`` /
``sched.requeued`` counters, ``sched.steal`` events, plus the affine
feed surface: ``sched.affinity_routed{host=}`` / ``sched.affinity_spilled``
counters and ``sched.feed_idle{host=}`` gauges (queue-idle fraction —
the per-host feed-starvation metric; OBSERVABILITY.md).  The scheduler
loop that drives a packer lives in ``engine.VerifyEngine._run``; its
three waits are the ``sched.starved`` / ``sched.linger`` /
``sched.slot_wait`` spans, entered there.
"""

from __future__ import annotations

import asyncio
import collections
import hashlib
import time
from typing import NamedTuple, Optional, Sequence

from ..events import events
from ..metrics import metrics

__all__ = [
    "OCCUPANCY_BUCKETS",
    "PRIORITIES",
    "LINGER",
    "SMALL_LANE",
    "LaneDecision",
    "decide_lane",
    "affinity_key",
    "host_names",
    "AffinityMap",
    "Submission",
    "PackedLane",
    "LanePacker",
    "FleetDispatcher",
]

# Dispatch order under saturation: live block ingest outranks mempool
# relay, which outranks IBD backfill (planner-fetched historical blocks,
# ISSUE 11 — a syncing node keeps serving fresh verdicts first), which
# outranks bulk (API default / re-index) traffic.
PRIORITIES = ("block", "mempool", "ibd", "bulk")

# How long a class's oldest unclaimed submission may linger for a fuller
# lane, in multiples of ``VerifyConfig.max_wait`` (ISSUE 37).  That wait
# is a latency budget: ``block`` and ``mempool`` verdicts have a user
# behind them, ``bulk`` is ``verify_raw``'s default and the serve path's
# tenants, who wait too.  ``ibd`` is planner-era backfill: no caller
# waits on one block of it, the planner's window of ``max_lead`` blocks
# does, and a lane costs the host the same whatever it holds.  2, 4 and 6
# were read on the chip (PERF.md §6, PR 37): each step costs less CPU a
# signature and verifies more (at 6 IBD's lanes leave full, cut by the
# goal and not the clock); 2 is kept until the benchmark's IBD chain is
# long enough to measure the others' rate (ROADMAP B1).
LINGER = {"block": 1, "mempool": 1, "ibd": 2, "bulk": 1}

# Classes that fill toward the SMALL compiled shape: while nothing else
# is queued, ``batch_size`` items are a full lane.  The planner keeps
# ``max_lead`` blocks beyond the watermark, far under ``device_batch``
# items, and a device slot costs the same in either shape.
SMALL_LANE = frozenset({"ibd"})

# Linear occupancy buckets (0.05 steps): lane occupancy lives in [0, 1],
# which the duration-shaped default bounds would quantize uselessly.
OCCUPANCY_BUCKETS = tuple(i / 20 for i in range(1, 21))

metrics.describe(
    "node.verdict_latency",
    "submit->verdict-publish latency per priority class (seconds)",
)


class LaneDecision(NamedTuple):
    """What the linger loop does with the queued work at one instant."""

    cut: Optional[str]  # "full" | "deadline"; None: keep lingering
    priority: str  # the class whose fill goal or deadline decides
    size: int  # items to cut the lane at
    wait: float  # seconds until the deciding deadline (0.0 on a cut)


def decide_lane(
    pending: int,
    oldest: dict[str, float],
    small: Optional[int],
    big: int,
    max_wait: float,
    now: float,
) -> LaneDecision:
    """The lane rule (ISSUE 37), as arithmetic on what is queued.

    ``pending``: unclaimed items; ``oldest``: the oldest unclaimed
    submission's enqueue time for each class that has one (at least one
    has).  ``small`` / ``big``: the two compiled shapes (``small`` None
    or over ``big``: there is one, ``big``).

    *Fill goal*: ``big``, unless only :data:`SMALL_LANE` classes are
    queued — then ``small``.  *Cut size*: the goal; ``big`` once that
    much is queued.  So ``small``-goal work between the shapes is cut at
    exactly ``small`` and its remainder lingers on under its own enqueue
    times: a lane a little over ``small`` would be padded to ``big``.
    *Deadline*: the earliest ``oldest[class] + LINGER[class] × max_wait``
    (ties to the higher class)."""
    small = big if small is None else min(small, big)
    queued = [p for p in PRIORITIES if p in oldest]
    big_cls = next((p for p in queued if p not in SMALL_LANE), None)
    goal, goal_cls = (small, queued[0]) if big_cls is None else (big, big_cls)
    deadline, _, deadline_cls = min(
        (oldest[p] + LINGER[p] * max_wait, i, p) for i, p in enumerate(queued)
    )
    size = big if pending >= big else goal
    if pending >= goal:
        return LaneDecision("full", goal_cls, size, 0.0)
    if deadline <= now:
        return LaneDecision("deadline", deadline_cls, size, 0.0)
    return LaneDecision(None, deadline_cls, size, deadline - now)


def slice_payload(payload, lo: int, hi: int):
    """A view/copy of ``payload[lo:hi]`` in dispatchable form: list
    payloads slice natively, raw-batch payloads through
    :func:`raw.as_raw_batch` (numpy views, no copies)."""
    if lo == 0 and hi >= len(payload):
        return payload
    if isinstance(payload, list):
        return payload[lo:hi]
    from .raw import as_raw_batch

    return as_raw_batch(payload).slice(lo, hi)


_MASK64 = (1 << 64) - 1


def _mix64(x: int) -> int:
    """splitmix64 finalizer: a cheap, well-distributed 64-bit mixer —
    rendezvous hashing only needs per-(key, host) scores that are
    independent across hosts, not cryptographic strength."""
    x &= _MASK64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK64
    return x ^ (x >> 31)


def affinity_key(txid: bytes) -> int:
    """The affinity key for a txid / block hash: its first 8 bytes as a
    little-endian integer.  Hash digests are already uniform, so no
    extra mixing is needed here — :class:`AffinityMap` mixes the key
    against each host's seed anyway."""
    return int.from_bytes(txid[:8], "little")


def host_names(n: int) -> list:
    """Canonical fleet host names (``h0`` .. ``h{n-1}``).  Owned HERE —
    next to :class:`AffinityMap`, which seeds per-host rendezvous
    scores from these strings: a renamed host is a re-shuffled steady
    state, so the engine fleet, the topology module, the bench proxy,
    and the timeline's host-series parsing must agree on one naming
    scheme.  Jax-free on purpose (multichip re-exports it): the
    analyzer's label-cardinality rule allowlists this as the bounded
    source for ``host=`` label values, so jax-free workers must be able
    to import it too."""
    return [f"h{i}" for i in range(n)]


class AffinityMap:
    """Stable key→host placement via rendezvous (HRW) hashing.

    Every ``(key, host)`` pair gets an independent score
    ``_mix64(key ^ seed(host))``; a key's home is the highest-scoring
    host.  The property ISSUE 19 needs falls out directly: removing a
    host remaps ONLY the keys that host owned (every other key's argmax
    is unchanged), and re-adding it restores exactly the old placement —
    a shrink/rejoin cycle never re-shuffles the steady state, unlike
    modulo placement where every key moves.

    Pure arithmetic, no mutable state beyond the fixed seed table:
    safe to call from any thread.
    """

    def __init__(self, hosts: Sequence[str]):
        hosts = list(hosts)
        if not hosts:
            raise ValueError("AffinityMap needs at least one host")
        self.hosts = hosts
        self._seed = {
            h: _mix64(
                int.from_bytes(
                    hashlib.blake2b(h.encode(), digest_size=8).digest(),
                    "big",
                )
            )
            for h in hosts
        }

    def prefer(self, key: int) -> str:
        """The key's home host over the FULL host set (ignores health —
        the steady-state placement a rejoin restores)."""
        return self._argmax(key, self.hosts)

    def route(self, key: int, active: Sequence[str]) -> Optional[str]:
        """The key's home host over ``active`` — the live routing
        decision.  None when no host is active (dark fleet: the caller
        falls back to the central path)."""
        if not active:
            return None
        return self._argmax(key, active)

    def _argmax(self, key: int, hosts: Sequence[str]) -> str:
        key &= _MASK64
        best = None
        best_score = -1
        for h in hosts:
            score = _mix64(key ^ self._seed[h])
            if score > best_score:
                best, best_score = h, score
        return best


class Submission:
    """One queued verify request: a payload plus the future its caller
    awaits.  ``results`` fills in slices as the lanes carrying this
    submission complete (in any order); the future resolves when the
    last slice lands, or fails on the FIRST lane failure (later slices
    of a failed submission are delivered into a dead buffer)."""

    __slots__ = (
        "payload", "n", "fut", "act", "priority", "enqueued",
        "taken", "results", "remaining", "failed", "affinity", "tenant",
    )

    def __init__(
        self,
        payload,
        fut: asyncio.Future,
        act: Optional[tuple],
        priority: str,
        enqueued: Optional[float] = None,
        affinity: Optional[int] = None,
        tenant: Optional[str] = None,
    ):
        if priority not in PRIORITIES:
            raise ValueError(
                f"unknown priority {priority!r}: one of {PRIORITIES}"
            )
        self.payload = payload
        self.n = len(payload)
        self.fut = fut
        self.act = act
        self.priority = priority
        self.affinity = affinity
        # serve-layer attribution (ISSUE 20): the registered tenant this
        # submission bills to, None for the node's own traffic
        self.tenant = tenant
        self.enqueued = time.monotonic() if enqueued is None else enqueued
        self.taken = 0  # items already claimed into lanes
        self.results: list = [None] * self.n
        self.remaining = self.n
        self.failed = False

    def deliver(self, lo: int, verdicts: Sequence[bool]) -> None:
        """Fill ``results[lo:lo+len(verdicts)]``; resolve the future when
        the submission is complete.  Idempotent against a prior failure."""
        self.results[lo : lo + len(verdicts)] = verdicts
        self.remaining -= len(verdicts)
        if self.remaining <= 0 and not self.failed and not self.fut.done():
            # Per-class e2e latency (ISSUE 17): admission stamp -> last
            # slice delivered.  Observed HERE — submission-side, not
            # lane-side — so packed/sliced/stolen/requeued lanes still
            # attribute the latency to the originating priority class.
            metrics.observe(
                "node.verdict_latency",
                time.monotonic() - self.enqueued,
                labels={"priority": self.priority},
            )
            self.fut.set_result(self.results)

    def fail(self, exc: BaseException) -> None:
        """A lane carrying part of this submission failed on every rung:
        the whole submission's waiter learns it (partial verdict lists
        are never surfaced — all-or-nothing per submission)."""
        self.failed = True
        if not self.fut.done():
            self.fut.set_exception(exc)


class PackedLane:
    """One dispatchable lane: ``(submission, lo, hi)`` slices summing to
    ``total`` items (≤ the pack target).  ``requeues`` counts fleet
    re-queues after a host loss (ISSUE 13) — the engine bounds it so a
    lane bouncing between dying hosts eventually falls through the
    local ladder instead of orbiting forever."""

    __slots__ = ("slices", "total", "target", "requeues")

    def __init__(
        self, slices: list[tuple[Submission, int, int]], target: int
    ):
        self.slices = slices
        self.total = sum(hi - lo for _, lo, hi in slices)
        self.target = target
        self.requeues = 0

    @property
    def occupancy(self) -> float:
        return self.total / self.target if self.target else 1.0

    @property
    def act0(self) -> Optional[tuple]:
        """First traced submitter's trace position — the tree the
        dispatch-phase spans are recorded into (exact for the
        one-block-per-lane common case)."""
        for sub, _, _ in self.slices:
            if sub.act is not None:
                return sub.act
        return None

    def payloads(self) -> list:
        """Sliced payloads in lane order (what the dispatch rungs run)."""
        return [
            slice_payload(sub.payload, lo, hi) for sub, lo, hi in self.slices
        ]

    def class_counts(self) -> dict[str, int]:
        """Items per priority class carried by this lane — the cost
        ledger's attribution input (ISSUE 17): the engine pro-rates the
        lane's wall-clock rung time across these counts."""
        out: dict[str, int] = {}
        for sub, lo, hi in self.slices:
            out[sub.priority] = out.get(sub.priority, 0) + (hi - lo)
        return out

    def tenant_counts(self) -> dict[str, int]:
        """Items per serve-layer tenant carried by this lane (ISSUE 20)
        — empty for pure node traffic, so the ledger's tenant table only
        exists when the serve subsystem is live."""
        out: dict[str, int] = {}
        for sub, lo, hi in self.slices:
            if sub.tenant is not None:
                out[sub.tenant] = out.get(sub.tenant, 0) + (hi - lo)
        return out


class LanePacker:
    """Priority-binned submission queue with cross-boundary lane packing.

    Not thread-safe by design: every method runs on the event loop (the
    engine's queue loop and ``_enqueue``).

    ``gauge=False`` silences the ``sched.queue_depth{priority=}``
    gauges: the fleet's per-host packers (ISSUE 19) would otherwise
    last-writer-win the same gauge keys as the central packer.  The
    counters/histogram stay on — they are process totals and sum
    correctly across packers.

    ``small`` and ``max_wait`` are the lane rule's two constants of the
    deployment (``VerifyConfig.batch_size`` / ``.max_wait``) for
    :meth:`decide` and :meth:`cut`; a packer built without them has one
    shape and no linger, and :meth:`pop_lane` needs neither.
    """

    def __init__(
        self,
        gauge: bool = True,
        small: Optional[int] = None,
        max_wait: float = 0.0,
    ):
        self._gauge_on = gauge
        self.small = small
        self.max_wait = max_wait
        self._q: dict[str, collections.deque[Submission]] = {
            p: collections.deque() for p in PRIORITIES
        }
        # Running unclaimed-item counts (global + per priority): push and
        # pop_lane maintain them in O(1) — recomputing by summing the
        # deque would make a burst of n enqueues O(n^2) on the event
        # loop (review finding).
        self._pending_items = 0
        self._depth: dict[str, int] = {p: 0 for p in PRIORITIES}

    # -- intake ---------------------------------------------------------------

    def push(self, sub: Submission) -> None:
        # Unclaimed remainder, not sub.n: a host deactivation re-routes
        # its packer's queue through push(), and a partially-claimed
        # submission must not inflate the depth by items already cut
        # into lanes (ISSUE 19).
        rem = sub.n - sub.taken
        q = self._q[sub.priority]
        i = len(q)
        # A re-routed submission is older than what its new packer
        # holds: it goes in by enqueue time, so q[0] stays the class's
        # oldest (the linger deadline anchors on it) and the class FIFO.
        while i and q[i - 1].enqueued > sub.enqueued:
            i -= 1
        q.insert(i, sub)
        self._pending_items += rem
        self._depth[sub.priority] += rem
        if self._gauge_on:
            metrics.set_gauge(
                "sched.queue_depth",
                float(self._depth[sub.priority]),
                labels={"priority": sub.priority},
            )

    # -- introspection --------------------------------------------------------

    def pending(self) -> int:
        """Unclaimed items across every priority class."""
        return self._pending_items

    def batches(self) -> int:
        return sum(len(q) for q in self._q.values())

    def depths(self) -> dict[str, int]:
        """Unclaimed items per priority (stats/debug endpoints)."""
        return dict(self._depth)

    def oldest_by_class(self) -> dict[str, float]:
        """Enqueue time of the oldest unclaimed submission of each class
        that has one — the linger deadlines anchor on these.  A
        submission keeps its time while lanes claim parts of it and when
        a fleet re-route pushes it into another packer."""
        return {p: q[0].enqueued for p, q in self._q.items() if q}

    def oldest_enqueued(self) -> Optional[float]:
        """Enqueue time of the oldest queued submission (any class)."""
        return min(self.oldest_by_class().values(), default=None)

    def head_class(self) -> Optional[int]:
        """Index into PRIORITIES of the highest class with unclaimed
        items (None when empty) — the fleet feed loop compares per-host
        packers by this before cutting, so per-host packing preserves
        GLOBAL priority order (ISSUE 19)."""
        for i, p in enumerate(PRIORITIES):
            if self._depth[p] > 0:
                return i
        return None

    # -- packing --------------------------------------------------------------

    def pop_lane(self, target: int) -> Optional[PackedLane]:
        """Claim up to ``target`` items into one lane, draining priority
        classes in order and slicing across submission boundaries.
        Returns None when the queue is empty."""
        slices: list[tuple[Submission, int, int]] = []
        room = target
        for p in PRIORITIES:
            q = self._q[p]
            while q and room > 0:
                sub = q[0]
                if sub.failed:
                    # an earlier lane already failed this submission's
                    # waiter: dispatching its remainder would burn whole
                    # device lanes on verdicts nobody can observe
                    rem = sub.n - sub.taken
                    sub.taken = sub.n
                    self._pending_items -= rem
                    self._depth[p] -= rem
                    metrics.inc("sched.failed_skipped", rem)
                    q.popleft()
                    continue
                take = min(room, sub.n - sub.taken)
                slices.append((sub, sub.taken, sub.taken + take))
                sub.taken += take
                room -= take
                self._pending_items -= take
                self._depth[p] -= take
                if sub.taken >= sub.n:
                    q.popleft()
            if self._gauge_on:
                metrics.set_gauge(
                    "sched.queue_depth",
                    float(self._depth[p]),
                    labels={"priority": p},
                )
            if room <= 0:
                break
        if not slices:
            return None
        lane = PackedLane(slices, target)
        metrics.inc("sched.lanes")
        metrics.inc("sched.packed_submissions", len(slices))
        metrics.observe(
            "sched.pack_efficiency", lane.occupancy, buckets=OCCUPANCY_BUCKETS
        )
        return lane

    def decide(self, target: int, now: float) -> LaneDecision:
        """:func:`decide_lane` over this packer's queue (which must not
        be empty), ``target`` being the big shape."""
        return decide_lane(
            self._pending_items, self.oldest_by_class(), self.small, target,
            self.max_wait, now,
        )

    def cut(self, target: int, now: Optional[float] = None
            ) -> Optional[PackedLane]:
        """Cut the lane the rule gives at ``now``, whether or not it
        says to linger on (the caller has decided to cut), and count it
        under the reason and the class that decided."""
        if not self._pending_items:
            return None
        d = self.decide(target, time.monotonic() if now is None else now)
        lane = self.pop_lane(d.size)
        if lane is not None:
            metrics.inc(
                "sched.lanes_cut_full" if d.cut == "full"
                else "sched.lanes_cut_deadline",
                labels={"priority": d.priority},
            )
        return lane

    # -- shutdown -------------------------------------------------------------

    def drain(self) -> list[Submission]:
        """Remove and return every queued submission (engine teardown:
        their futures are cancelled by the caller).  Partially-claimed
        submissions are included — their in-flight slices resolve or
        fail through the lane that claimed them."""
        out: list[Submission] = []
        for p, q in self._q.items():
            out.extend(q)
            q.clear()
            self._depth[p] = 0
            if self._gauge_on:
                metrics.set_gauge(
                    "sched.queue_depth", 0.0, labels={"priority": p}
                )
        self._pending_items = 0
        return out


class FleetDispatcher:
    """Cross-host work-stealing lane dispatcher (ISSUE 13).

    One FIFO lane queue per mesh host, fed from a shared
    :class:`LanePacker` in global priority order; idle hosts steal the
    OLDEST lane from the deepest peer queue.  Lane granularity preserves
    verdict conservation: a lane lives in exactly one queue at a time,
    so a steal or a host-loss re-queue moves the whole resolution
    responsibility with it — its carried submissions still resolve
    exactly once.

    Host health is the ENGINE's business (per-host circuit breakers,
    canary re-probes); this class only tracks the active set so
    assignment and re-queueing skip lost hosts.  Not thread-safe by
    design: every method runs on the event loop, like the packer.
    """

    def __init__(
        self,
        hosts,
        packer: Optional[LanePacker] = None,
        max_queue: int = 2,
    ):
        hosts = list(hosts)
        if not hosts:
            raise ValueError("FleetDispatcher needs at least one host")
        if len(set(hosts)) != len(hosts):
            raise ValueError(f"duplicate host names: {hosts}")
        self.hosts = hosts
        self.packer = packer if packer is not None else LanePacker()
        self.max_queue = max(1, max_queue)
        self._queues: dict = {h: collections.deque() for h in hosts}
        self._active: dict = {h: True for h in hosts}
        self.steals = 0
        self.requeued = 0
        # per-thief steal totals: the fleet timeline's per-host steal
        # series (tpunode/timeseries.py) — bounded by the fixed host set
        self.host_steals: dict = {h: 0 for h in hosts}
        # Host-affine feeds (ISSUE 19): one packer per host, routed by
        # rendezvous hashing.  The shared self.packer stays as the
        # central path for affinity-less submissions and the dark-fleet
        # fallback; per-host packers run gauge-silenced so they don't
        # stomp the central sched.queue_depth series.
        self.affinity = AffinityMap(hosts)
        self._packers: dict = {
            h: LanePacker(
                gauge=False, small=self.packer.small,
                max_wait=self.packer.max_wait,
            )
            for h in hosts
        }
        self.affinity_routed = 0
        self.affinity_spilled = 0
        # feed starvation: take attempts that found the host's own
        # queue dry, over all take attempts — the queue-idle fraction
        self._takes: dict = {h: 0 for h in hosts}
        self._idle_takes: dict = {h: 0 for h in hosts}

    # -- intake ---------------------------------------------------------------

    def push(self, sub: Submission) -> None:
        """Route a submission to its packer.  Affinity-keyed work goes
        to its home host's packer over the ACTIVE set — a lost host's
        keys spill to their rendezvous runner-up (counted as a spill),
        and a rejoin restores the steady-state placement for new work.
        Affinity-less submissions and dark-fleet traffic take the
        central packer."""
        if sub.affinity is None:
            self.packer.push(sub)
            return
        host = self.affinity.route(sub.affinity, self.active_hosts())
        if host is None:
            self.packer.push(sub)
            return
        self._packers[host].push(sub)
        if host == self.affinity.prefer(sub.affinity):
            self.affinity_routed += 1
            metrics.inc("sched.affinity_routed", labels={"host": host})
        else:
            self.affinity_spilled += 1
            metrics.inc("sched.affinity_spilled")

    # -- introspection --------------------------------------------------------

    def is_active(self, host: str) -> bool:
        return self._active[host]

    def active_hosts(self) -> list:
        return [h for h in self.hosts if self._active[h]]

    def host_depth(self, host: str) -> int:
        """Queued ITEMS on one host (the steal victim metric)."""
        return sum(lane.total for lane in self._queues[host])

    def host_lanes(self, host: str) -> int:
        return len(self._queues[host])

    def host_depths(self) -> dict:
        return {h: self.host_depth(h) for h in self.hosts}

    def queued_lanes(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def uncut_pending(self) -> int:
        """Unclaimed items across the central AND every per-host packer
        (what the engine's linger loop measures)."""
        return self.packer.pending() + sum(
            p.pending() for p in self._packers.values()
        )

    def pending(self) -> int:
        """Unclaimed packer items + items already cut into host lanes."""
        return self.uncut_pending() + sum(
            lane.total for q in self._queues.values() for lane in q
        )

    def batches(self) -> int:
        return self.packer.batches() + sum(
            p.batches() for p in self._packers.values()
        )

    def depths(self) -> dict[str, int]:
        """Unclaimed items per priority, summed over every packer."""
        out = self.packer.depths()
        for p in self._packers.values():
            for k, v in p.depths().items():
                out[k] += v
        return out

    def oldest_by_class(self) -> dict[str, float]:
        """Per class, the oldest unclaimed submission over the central
        and every per-host packer."""
        out = self.packer.oldest_by_class()
        for p in self._packers.values():
            for k, t in p.oldest_by_class().items():
                out[k] = min(t, out.get(k, t))
        return out

    def decide(self, target: int, now: float) -> LaneDecision:
        """The linger loop's question over everything uncut (which must
        not be nothing): the same rule as one packer's, on the sums."""
        return decide_lane(
            self.uncut_pending(), self.oldest_by_class(), self.packer.small,
            target, self.packer.max_wait, now,
        )

    def feed_depth(self, host: str) -> int:
        """Uncut items homed to ``host`` plus items already cut into
        its queue — the per-host backpressure signal (ISSUE 19):
        node/mempool intake gates on the TARGET host's feed depth, not
        a global counter, so one slow host can't stall fleet intake."""
        return self._packers[host].pending() + self.host_depth(host)

    def feed_depths(self) -> dict:
        return {h: self.feed_depth(h) for h in self.hosts}

    def feed_idle(self) -> dict:
        """Per-host queue-idle fraction of take attempts (the feed
        starvation metric: 0.0 = always fed, → 1.0 = starved)."""
        return {
            h: (self._idle_takes[h] / self._takes[h])
            if self._takes[h]
            else 0.0
            for h in self.hosts
        }

    def has_room(self) -> bool:
        """May the scheduler cut + assign another lane?  (Backpressure:
        keeping assignment shallow lets late high-priority submissions
        pack ahead of work that hasn't been cut into lanes yet.)"""
        return any(
            self._active[h] and len(self._queues[h]) < self.max_queue
            for h in self.hosts
        )

    def feedable(self) -> bool:
        """Is there a lane the feed loop could cut + place right now?
        True when an active host with queue room has a nonempty home
        packer, or the central packer has work and any active queue has
        room."""
        central = self.packer.pending() > 0
        for h in self.hosts:
            if not self._active[h]:
                continue
            if len(self._queues[h]) >= self.max_queue:
                continue
            if central or self._packers[h].pending() > 0:
                return True
        return False

    def _gauge(self, host: str) -> None:
        metrics.set_gauge(
            "sched.host_depth",
            float(self.host_depth(host)),
            labels={"host": host},
        )

    # -- assignment / consumption ---------------------------------------------

    def _shallowest(
        self, exclude: Optional[str] = None, respect_cap: bool = False
    ) -> Optional[str]:
        """The shallowest-by-items ACTIVE host (ties -> first in host
        order), optionally excluding one host and/or skipping queues at
        ``max_queue`` — the one selection policy behind assignment AND
        re-queueing (review r13: two hand-rolled copies would fork)."""
        best = None
        for h in self.hosts:
            if h == exclude or not self._active[h]:
                continue
            if respect_cap and len(self._queues[h]) >= self.max_queue:
                continue
            if best is None or self.host_depth(h) < self.host_depth(best):
                best = h
        return best

    def assign(self, lane: PackedLane) -> Optional[str]:
        """Queue ``lane`` on the shallowest active host with room; None
        when every active queue is full (caller waits) or no host is
        active (caller must dispatch locally — traffic never stops)."""
        best = self._shallowest(respect_cap=True)
        if best is None:
            return None
        self._queues[best].append(lane)
        self._gauge(best)
        return best

    def cut_next(
        self, target: int, now: Optional[float] = None
    ) -> tuple[Optional[PackedLane], Optional[str]]:
        """Cut the globally most-urgent feedable lane and place it.

        Candidate sources: each active host's home packer (the lane
        lands on that host's OWN queue — host-local feed, no cross-host
        placement decision) and the central packer (the lane lands on
        the shallowest active queue).  The winner is the source whose
        head is highest-class, ties broken by oldest enqueue — per-host
        packing thus preserves the GLOBAL block > mempool > ibd > bulk
        order (ISSUE 19).  Returns ``(lane, host)``; ``(None, None)``
        when nothing was cut; ``(lane, None)`` when a central lane was
        cut but no queue had room (caller dispatches it locally —
        traffic never stops)."""
        best_key = None
        best_host: Optional[str] = None
        for h in self.hosts:
            if not self._active[h]:
                continue
            if len(self._queues[h]) >= self.max_queue:
                continue
            cls = self._packers[h].head_class()
            if cls is None:
                continue
            key = (cls, self._packers[h].oldest_enqueued() or 0.0)
            if best_key is None or key < best_key:
                best_key, best_host = key, h
        central_cls = self.packer.head_class()
        if central_cls is not None and self.has_room():
            key = (central_cls, self.packer.oldest_enqueued() or 0.0)
            if best_key is None or key < best_key:
                best_key, best_host = key, None
        if best_key is None:
            return None, None
        if best_host is not None:
            lane = self._packers[best_host].cut(target, now)
            if lane is None:  # only failed-submission residue queued
                return None, None
            self._queues[best_host].append(lane)
            self._gauge(best_host)
            return lane, best_host
        lane = self.packer.cut(target, now)
        if lane is None:
            return None, None
        return lane, self.assign(lane)

    def pop_any(
        self, target: int, now: Optional[float] = None
    ) -> Optional[PackedLane]:
        """Cut a lane from ANY packer, priority-first (dark fleet: the
        engine's local-CPU fallback drains the affine packers too, so
        affinity never strands work when every host is down)."""
        best_key = None
        best_packer = None
        for p in (self.packer, *self._packers.values()):
            cls = p.head_class()
            if cls is None:
                continue
            key = (cls, p.oldest_enqueued() or 0.0)
            if best_key is None or key < best_key:
                best_key, best_packer = key, p
        if best_packer is None:
            return None
        return best_packer.cut(target, now)

    def take(self, host: str, steal: bool = True) -> Optional[PackedLane]:
        """Next lane for ``host``: its own queue head, else (``steal``)
        the OLDEST lane of the deepest peer queue.  The deque pop is the
        atomic hand-off — once taken, no other host can reach this lane."""
        q = self._queues[host]
        # Feed starvation accounting (ISSUE 19): a take that finds the
        # host's own queue dry is a feed miss, counted BEFORE stealing —
        # a steal hides compute starvation but not feed starvation.
        self._takes[host] += 1
        if not q:
            self._idle_takes[host] += 1
        metrics.set_gauge(
            "sched.feed_idle",
            self._idle_takes[host] / self._takes[host],
            labels={"host": host},
        )
        if q:
            lane = q.popleft()
            self._gauge(host)
            return lane
        if not steal:
            return None
        return self._steal_for(host)

    def _steal_for(self, thief: str) -> Optional[PackedLane]:
        # Deepest queue by ITEMS, scanned over every host (a lost host's
        # orphaned lanes are legitimate loot too).  Head steal: lanes
        # were cut in global priority order, so the victim's oldest lane
        # is the whole fleet's most urgent queued work.
        victim = None
        depth = 0
        for h in self.hosts:
            if h == thief or not self._queues[h]:
                continue
            d = self.host_depth(h)
            if d > depth:
                victim, depth = h, d
        if victim is None:
            return None
        lane = self._queues[victim].popleft()
        self.steals += 1
        self.host_steals[thief] += 1
        metrics.inc("sched.steals")
        metrics.inc("sched.host_steals", labels={"host": thief})
        events.emit(
            "sched.steal", thief=thief, victim=victim, items=lane.total,
        )
        self._gauge(victim)
        return lane

    # -- degradation (ISSUE 13: one sick host degrades alone) -----------------

    def requeue(self, host: str, lane: PackedLane) -> Optional[str]:
        """Give a lost host's IN-FLIGHT lane to a peer (FRONT of the
        shallowest active queue — it is older than anything queued).
        Returns the host it landed on, or None WITHOUT queueing (and
        without counting — review r13: a refused requeue placed
        nothing) when no peer is active: ownership stays with the
        caller, which must resolve the lane itself (queueing it here
        too would leave two live copies — the double-resolution hazard
        the ISSUE 13 requeue audit exists to rule out).  Only THESE
        in-flight bounces consume ``lane.requeues`` (the engine's orbit
        bound); queued-lane redistribution at deactivation does not."""
        best = self._shallowest(exclude=host)
        if best is None:
            return None
        lane.requeues += 1
        self.requeued += 1
        metrics.inc("sched.requeued")
        self._queues[best].appendleft(lane)
        self._gauge(best)
        return best

    def deactivate(self, host: str) -> int:
        """Mark ``host`` lost and redistribute its queued lanes to the
        active peers (order preserved, each to the FRONT of the
        shallowest peer — they are older than anything queued; with no
        active peer they stay put for steals / the engine's local
        fallback).  A redistribution is NOT an in-flight bounce: it
        counts in ``sched.requeued`` telemetry but never consumes
        ``lane.requeues`` — a lane that merely sat queued on dying
        hosts must arrive at its first real dispatch with its full
        orbit budget (review r13).  Returns how many lanes moved.
        Idempotent."""
        if not self._active[host]:
            return 0
        self._active[host] = False
        moved = 0
        lanes = list(self._queues[host])
        self._queues[host].clear()
        self._gauge(host)
        for lane in reversed(lanes):
            target = self._shallowest(exclude=host)
            if target is None:
                self._queues[host].appendleft(lane)
                continue
            self._queues[target].appendleft(lane)
            self._gauge(target)
            self.requeued += 1
            metrics.inc("sched.requeued")
            moved += 1
        self._gauge(host)
        # Re-route the lost host's UNCUT feed through push(): rendezvous
        # re-homes each key over the remaining active set (counted as
        # spills), affinity-less work falls back to the central packer.
        # Runs after the active flag flipped so route() skips this host;
        # push()'s remainder accounting keeps partially-claimed
        # submissions' depths truthful.
        for sub in self._packers[host].drain():
            self.push(sub)
        return moved

    def activate(self, host: str) -> None:
        self._active[host] = True

    # -- shutdown -------------------------------------------------------------

    def drain_lanes(self) -> list[PackedLane]:
        """Remove and return every queued lane (engine teardown: the
        caller cancels their carried futures)."""
        out: list[PackedLane] = []
        for h, q in self._queues.items():
            out.extend(q)
            q.clear()
            self._gauge(h)
        return out

    def drain_submissions(self) -> list[Submission]:
        """Remove and return every queued submission across the central
        and per-host packers (engine teardown: the caller cancels their
        futures)."""
        out = self.packer.drain()
        for p in self._packers.values():
            out.extend(p.drain())
        return out
