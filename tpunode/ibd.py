"""Block-fetch-driven IBD: the fetch planner behind ``NodeConfig.ibd``
(ISSUE 11 / ROADMAP item 5; the network's faults: ISSUE 36).

The node's block ingest used to be embedder-driven: headers synced through
the chain actor, but block BODIES only arrived when the embedding process
pushed them or drove ``peer.get_blocks`` windows itself (benchmarks/run.py
config3 was the canonical driver).  :class:`BlockFetcher` closes that gap:
a bare ``Node`` now syncs the whole chain by itself, the way the mempool's
inv-driven fetch pipeline already self-drives tx relay.

Shape (deliberately the mempool fetcher's, tpunode/mempool.py):

* the planner walks the **persisted chain from the UTXO watermark** —
  restart resumes exactly where the store says verification stopped, so a
  kill -9 mid-sync re-fetches (and re-verifies) nothing below the
  watermark (the ISSUE 9 crash contract, now end-to-end);
* block hashes come from an incrementally-maintained height->hash view of
  the best chain (one O(1) step per new header, one bounded walk per
  reorg) — never an O(n) ancestor walk per batch;
* ``getdata`` batches (``batch_blocks`` hashes each, a ``ping`` behind
  them) are spread across the online peer fleet, the peer that has served
  its batches fastest first, with a per-peer in-flight cap;
* the planner's unit of work is the batch (ISSUE 43): the plan grows by
  whole batches — its open edge waits until ``batch_blocks`` heights are
  free under the lead's horizon; only the last batch before the header
  tip and a gap with batches on both sides are scheduled short — and the
  pass that runs for every connected block ends, when that block freed
  less than a batch and nothing is queued, before anything that sorts,
  takes the metrics' lock or walks the view of the chain: a pass costs
  the same at 10^3 headers as at 10^6;
* delivery is kept **per block**: the node tells the planner of every
  block as it arrives (:meth:`BlockFetcher.block_arrived`), from whichever
  peer, so a batch that fails half way asks another peer for its missing
  blocks only;
* a batch fails when its peer answers the trailing ``ping`` with blocks
  still missing (it has finished answering: the reference's sentinel,
  Peer.hs:349-387), when the peer dies (immediate reassignment), or when
  it **stalls**: no block of any batch has come from the peer for
  ``stall_timeout`` seconds (Bitcoin Core's ``BLOCK_STALLING_TIMEOUT``, 2
  s).  A staller is disconnected (``PeerStalling``: the fleet bans the
  address for a while, tpunode/peermgr.py) and never handed another
  batch; the timeout doubles with every stall, up to 64 s, and falls
  back as batches complete — a network that is slow as a whole does not
  lose every peer in turn.  Time the event loop itself was held counts
  against no peer;
* delivered blocks arrive through the NORMAL peer-message path (the wire
  loop publishes them; ``node._peer_events`` routes them into verify
  ingest + UTXO connect) — the planner never touches block bytes, so
  admission stays single-path exactly like mempool fetch;
* scheduling is watermark-gated: at most ``max_lead`` blocks beyond the
  watermark are ever scheduled (bounded by the node's out-of-order
  parking; the lead steps between ``max_lead - batch_blocks + 1`` and
  ``max_lead``), the blocks on the wire plus those in verification never
  exceed the node's shed bound (``pending_cap``), and planning defers
  while verify-ingest pressure is high — the planner can saturate the
  pipeline but never outrun it into the shed path;
* a delivered-but-stuck head batch (its blocks shed, or lost to an engine
  failure) is re-fetched after ``refetch_after`` seconds — the watermark
  can stall but never wedge;
* the lead is the in-flight output view's bound (ISSUE 44): a block's
  outputs are a prevout source from its parse to its connect, and a block
  that spends an output of a block beneath it that has not come waits for
  it (``node.resolve_gate``).  Such a block is no ingest pressure and no
  work in hand: the plan is not deferred for it, and ``ibd.head_wait`` is
  open when every block the node holds waits so.

Telemetry: ``ibd.*`` metrics/events and the ``ibd.head_wait`` /
``ibd.stall`` spans (OBSERVABILITY.md).  Engine-side, the node submits
planner-era block batches at the ``ibd`` priority — beneath live
``block``/``mempool`` traffic in the lane packer — so a backfilling node
still serves fresh verdicts first (tpunode/verify/sched.py).
"""

from __future__ import annotations

import asyncio
import logging
import random
import time
from dataclasses import dataclass
from typing import Callable, Optional

from .actors import LinkedTasks
from .events import events
from .metrics import metrics
from .peer import PeerStalling
from .trace import record_span, span
from .wire import InvType, InvVector, MsgGetData, MsgPing

__all__ = ["IbdConfig", "BlockFetcher"]

log = logging.getLogger("tpunode.ibd")

# the stall timeout doubles up to this (Bitcoin Core's
# BLOCK_STALLING_TIMEOUT_MAX) and falls back by a sixth a completed batch
STALL_TIMEOUT_MAX = 64.0
STALL_TIMEOUT_DECAY = 0.85
# a tick this much later than asked for means the loop was held: blocks
# may be waiting unread in the sockets, and no peer is charged for it
LOOP_HELD = 0.05
# ``ibd.inflight_blocks`` is sampled this often between the planner's
# requests: writing a gauge takes the metrics registry's lock on the loop
GAUGE_INTERVAL = 0.5


@dataclass
class IbdConfig:
    """Fetch-planner knobs (``NodeConfig.ibd``).  The defaults keep the
    total in-flight block count under the node's verify-pending and
    out-of-order-parking bounds, so a healthy sync never sheds."""

    # blocks per getdata batch (one peer round-trip).  The plan grows by
    # whole batches: the open edge at the lead's horizon waits until this
    # many heights are uncovered, so every getdata asks this many blocks
    # but the last before the header tip, a gap between two batches (a
    # reorg unwind, a dropped batch) and a re-request of what is missing
    batch_blocks: int = 16
    # concurrent batches per peer
    max_inflight_per_peer: int = 2
    # a batch that is still incomplete this long after it was asked for
    # goes to another peer, however steadily it trickles in
    fetch_timeout: float = 45.0
    # max blocks scheduled beyond the UTXO watermark: bounds in-flight
    # memory AND stays inside Node.MAX_UTXO_PENDING (128 parked); what is
    # on the wire or in verification is held under
    # Node.MAX_VERIFY_PENDING (64 messages) besides, so healthy syncs
    # never shed.  A ceiling: the scheduled lead runs between
    # max_lead - batch_blocks + 1 and max_lead, stepping by a batch.
    # It is also the bound of the node's in-flight output view (ISSUE
    # 44): the outputs of every block parsed and not yet connected are
    # held in memory as a prevout source for the blocks above them
    max_lead: int = 48
    # a delivered head batch whose blocks still have not connected after
    # this long is re-fetched (heals shed/failed ingest; in a healthy sync
    # this never fires).  A block that spends an output of a block
    # beneath it that has not come waits for it (``Node._resolve_gate``)
    # for twice this long: a head that never comes is asked for again
    # before the wait ends
    refetch_after: float = 30.0
    # planner cadence (stalls and timeouts are detected on ticks;
    # deliveries and chain events wake it immediately)
    tick_interval: float = 0.5
    # a peer that owes blocks and has sent none for this long is a
    # staller: disconnected, its missing blocks asked of another peer
    # (Bitcoin Core's BLOCK_STALLING_TIMEOUT_DEFAULT)
    stall_timeout: float = 2.0


class _Batch:
    """One scheduled getdata window: heights ``[lo, hi]`` on the best
    chain.  States: queued -> fetching -> delivered (-> dropped once the
    watermark passes ``hi``); failures return it to queued, with the
    blocks that did arrive struck from ``missing``."""

    __slots__ = (
        "lo", "hi", "hashes", "missing", "state", "peer", "nonce", "tried",
        "attempts", "sent_at", "delivered_at", "stalled_at",
    )

    def __init__(self, lo: int, hi: int, hashes: list[bytes]):
        self.lo = lo
        self.hi = hi
        self.hashes = hashes
        self.missing: set[bytes] = set(hashes)
        self.state = "queued"
        self.peer = None
        self.nonce = 0  # of the ping behind the getdata
        self.tried: set = set()
        self.attempts = 0
        self.sent_at = 0.0
        self.delivered_at = 0.0
        # set on the lowest batch a staller held: its last sign of
        # progress, until the missing blocks are asked of another peer
        self.stalled_at = 0.0


class BlockFetcher:
    """The IBD fetch planner.  Constructed by ``Node`` (never directly);
    lives inside the node bracket like the other subsystems."""

    def __init__(
        self,
        cfg: IbdConfig,
        net,
        chain,
        peer_mgr,
        utxo,
        pressure: Callable[[], bool],
        pressure_key: Optional[Callable[[bytes], bool]] = None,
        on_failure=None,
        *,
        pending: Callable[[], int],
        pending_cap: int,
    ):
        self.cfg = cfg
        self._net = net
        self._chain = chain
        self._peer_mgr = peer_mgr
        self._utxo = utxo
        self._pressure = pressure
        # host-affine gate (ISSUE 19): true for a BLOCK HASH whose
        # target verify host is over its feed ceiling — _assign skips
        # just that batch instead of deferring the whole plan
        self._pressure_key = pressure_key
        # messages the node has in verification, and how many it takes
        # before it sheds: blocks on the wire count against the same bound
        self._pending = pending
        self._pending_cap = pending_cap
        self._tasks = LinkedTasks(name="ibd", on_failure=on_failure)
        self._wake = asyncio.Event()
        self._batches: dict[int, _Batch] = {}  # keyed by lo height
        self._want: dict[bytes, _Batch] = {}  # scheduled, not yet arrived
        self._inflight: dict[object, int] = {}
        self._progress: dict[object, float] = {}  # peer -> its last block
        self._pace: dict[object, float] = {}  # peer -> seconds a block
        self._stallers: set = set()  # killed, not yet reported gone
        self._stall_timeout = cfg.stall_timeout
        self._head_wait: Optional[span] = None
        self._hashes: dict[int, bytes] = {}  # best-chain height -> hash
        self._cache_best: Optional[bytes] = None
        self._cache_floor = 1 << 62  # lowest height the view covers
        self._cache_top = 0  # highest height the view covers
        # the plan's open edge: every height between the watermark and it
        # is covered by a batch and none above it is; 0 = not known (the
        # watermark moved back, a batch was dropped, a header was missing)
        self._edge = 0
        self._gauges: dict[str, float] = {}  # as last written
        self._gauged_at = 0.0
        self._target = 0
        self._announced = False
        self.synced = asyncio.Event()  # wm reached the header tip once
        self._fetched_blocks = 0
        self._refetches = 0
        self._retries = 0
        self._stalls = 0

    # -- lifecycle -----------------------------------------------------------

    async def __aenter__(self) -> "BlockFetcher":
        self._tasks.link(self._main_loop(), name="ibd-planner")
        return self

    async def __aexit__(self, *exc) -> None:
        await self._tasks.__aexit__(*exc)
        self._set_head_wait(False)

    # -- wiring from the node's routers (event-loop only) ---------------------

    def nudge(self) -> None:
        """Chain activity (new best header) or a connected block: plan."""
        self._wake.set()

    def block_arrived(self, peer, block_hash: bytes) -> None:
        """A ``block`` message came in.  Whoever sent it, a block that was
        scheduled is delivered, and its sender has shown progress."""
        b = self._want.pop(block_hash, None)
        if b is None:
            return
        now = time.monotonic()
        b.missing.discard(block_hash)
        self._progress[peer] = now
        self._set_head_wait(False)  # the engine is about to have it
        if not b.missing:
            self._delivered(b, now)

    def pong(self, peer, nonce: int) -> None:
        """The ping behind a getdata came back: the peer has finished
        answering (it answers in order), so what is still missing of that
        batch it does not have — ask elsewhere, without waiting."""
        for b in self._batches.values():
            if b.state == "fetching" and b.peer is peer and b.nonce == nonce:
                self._failed(b, "incomplete")
                return

    def peer_gone(self, peer) -> None:
        """A peer died: its in-flight batches reassign immediately instead
        of waiting out a timeout."""
        for b in list(self._batches.values()):
            if b.state == "fetching" and b.peer is peer:
                self._failed(b, "peer gone")
        self._inflight.pop(peer, None)
        self._progress.pop(peer, None)
        self._pace.pop(peer, None)
        self._stallers.discard(peer)
        self._wake.set()

    # -- introspection --------------------------------------------------------

    @property
    def backfilling(self) -> bool:
        """True while the watermark trails the header tip by more than
        the planner's lead window: the node tags block verify submissions
        ``ibd`` (beneath live traffic) during a genuine backfill and
        ``block`` otherwise.  The margin matters: on a SYNCED node a live
        block's headers land (bumping the target) before its UTXO connect
        advances the watermark, so a trail of a few blocks is the normal
        live-tip state — classifying it ``ibd`` would put fresh blocks
        beneath mempool relay, inverting the block > mempool ordering
        (review finding)."""
        return self._target - self._utxo.height > self.cfg.max_lead

    def stats(self) -> dict:
        return {
            "enabled": True,
            "target": self._target,
            "watermark": self._utxo.height,
            "batches": len(self._batches),
            "inflight": sum(self._inflight.values()),
            "fetched_blocks": self._fetched_blocks,
            "retries": self._retries,
            "refetches": self._refetches,
            "stalls": self._stalls,
            "stall_timeout": self._stall_timeout,
        }

    # -- planner --------------------------------------------------------------

    async def _main_loop(self) -> None:
        tick = self.cfg.tick_interval
        while True:
            t0 = time.monotonic()
            try:
                await asyncio.wait_for(self._wake.wait(), tick)
            except (asyncio.TimeoutError, TimeoutError):
                pass
            held = time.monotonic() - t0 - tick
            if held > LOOP_HELD:
                self._forgive(held)
            self._wake.clear()
            self._plan()

    def _forgive(self, seconds: float) -> None:
        """The loop did not run for ``seconds``: nobody's silence."""
        for b in self._batches.values():
            if b.state == "fetching":
                b.sent_at += seconds

    def _best(self):
        try:
            return self._chain.get_best()
        except Exception:
            return None  # chain DB not initialized yet

    def _plan(self) -> None:
        best = self._best()
        if best is None:
            return
        self._target = best.height
        wm = self._utxo.height
        self._gauge("ibd.target", float(self._target))
        if not self._announced and self._target > wm:
            self._announced = True
            events.emit(
                "ibd.start", watermark=wm, target=self._target,
            )
        # connected batches retire; so do the view's heights at or under
        # the watermark, which are contiguous from its floor
        for lo in [lo for lo, b in self._batches.items() if b.hi <= wm]:
            self._drop(self._batches.pop(lo))
        for h in range(self._cache_floor, wm + 1):
            self._hashes.pop(h, None)
        self._cache_floor = max(self._cache_floor, wm + 1)
        if wm >= self._target:
            if self._target > 0 and not self.synced.is_set():
                self.synced.set()
                events.emit("ibd.synced", height=wm)
                log.info("[IBD] watermark reached header tip %d", wm)
            self._gauge("ibd.inflight_blocks", 0.0)
            self._set_head_wait(False)
            return
        self.synced.clear()
        now = time.monotonic()
        self._check_peers(now)
        # the engine has nothing and the network owes blocks
        self._set_head_wait(bool(self._want) and self._pending() == 0)
        # head-of-line healing: the batch holding wm+1 was delivered but
        # never connected (shed under pressure, or its ingest failed) —
        # after the grace window, fetch it again
        head = next(
            (b for b in self._batches.values() if b.lo <= wm + 1 <= b.hi),
            None,
        )
        if (
            head is not None
            and head.state == "delivered"
            and now - head.delivered_at > self.cfg.refetch_after
        ):
            head.state = "queued"
            head.tried.clear()
            head.missing = {
                hh for h, hh in zip(range(head.lo, head.hi + 1), head.hashes)
                if h > wm
            }
            for hh in head.missing:
                self._want[hh] = head
            self._refetches += 1
            metrics.inc("ibd.refetches")
            events.emit("ibd.refetch", lo=head.lo, hi=head.hi)
        horizon = min(self._target, wm + self.cfg.max_lead)
        if (
            best.hash == self._cache_best
            and self._cache_floor == wm + 1  # the view stands as it is
            and self._edge
            and not self._room(self._edge, horizon)
            and not any(b.state == "queued" for b in self._batches.values())
        ):
            # a connected block that frees less than a batch: nothing to
            # schedule and nobody to ask.  This is the pass that runs for
            # every block of a sync, so it ends here, before anything
            # that sorts, takes the metrics' lock or reads the view
            if now - self._gauged_at >= GAUGE_INTERVAL:
                self._gauge_on_wire(now)
            return
        if self._pressure():
            metrics.inc("ibd.deferred")
            return  # the tick retries once ingest drains
        if self._refresh_hashes(best):
            # a reorg may have rewritten heights under planned batches: a
            # batch whose hashes no longer match the best-chain view
            # fetches orphaned blocks nobody can connect — drop it and
            # replan.  (A view that did not move rewrote nothing.)
            for lo in [
                lo for lo, b in self._batches.items()
                if any(
                    self._hashes.get(h) != hh
                    for h, hh in zip(range(b.lo, b.hi + 1), b.hashes)
                    if h > wm  # connected heights are pruned from the view
                )
            ]:
                self._drop(self._batches.pop(lo))
                metrics.inc("ibd.reorg_dropped")
        # extend the plan up to the lead horizon.  Not just past the
        # highest batch: after a reorg unwind the watermark sits BELOW
        # surviving batches, and the gap in front of them is exactly what
        # must be fetched next — a gap with a batch above it is filled at
        # once, at whatever size it has
        holes, edge = self._uncovered(max(wm + 1, 1))
        known = True
        for lo, hi in holes:
            known &= lo <= horizon and self._schedule(
                lo, min(hi, horizon)) > hi
        # the open edge grows by whole batches: it waits for a batch's
        # room under the horizon, or for the header tip
        n = self._room(edge, horizon)
        known &= self._schedule(edge, edge + n - 1) == edge + n
        self._edge = edge + n if known else 0  # 0: a header was missing
        self._assign()
        self._gauge_on_wire(now)

    def _room(self, edge: int, horizon: int) -> int:
        """Heights the plan's open ``edge`` can grow by under ``horizon``:
        whole batches, and before the header tip whatever is left."""
        n = horizon - edge + 1
        if horizon < self._target:
            n -= n % self.cfg.batch_blocks
        return max(n, 0)

    def _schedule(self, lo: int, hi: int) -> int:
        """Batches of ``batch_blocks`` over ``[lo, hi]``, the last as
        short as it comes.  -> the first height left uncovered: ``hi + 1``
        unless the view lacks a header (mid-reorg: the next pass goes
        on)."""
        while lo <= hi:
            b_hi = min(lo + self.cfg.batch_blocks - 1, hi)
            hashes = [self._hashes.get(h) for h in range(lo, b_hi + 1)]
            if any(h is None for h in hashes):
                break
            b = self._batches[lo] = _Batch(lo, b_hi, hashes)
            for hh in hashes:
                self._want[hh] = b
            lo = b_hi + 1
        return lo

    def _gauge(self, name: str, value: float) -> None:
        """``metrics.set_gauge`` takes the registry's lock, on the loop:
        only a value that moved is written."""
        if self._gauges.get(name) != value:
            self._gauges[name] = value
            metrics.set_gauge(name, value)

    def _gauge_on_wire(self, now: float) -> None:
        """``ibd.inflight_blocks`` is a sample: taken when the planner has
        asked for blocks, and otherwise every ``GAUGE_INTERVAL``."""
        self._gauged_at = now
        self._gauge("ibd.inflight_blocks", float(self._on_wire()))

    def _on_wire(self) -> int:
        """Blocks asked for and not yet here."""
        return sum(
            len(b.missing) for b in self._batches.values()
            if b.state == "fetching"
        )

    def _drop(self, b: _Batch) -> None:
        """The batch leaves the plan (connected, or reorged away)."""
        if b.state == "fetching":
            self._release(b.peer)
        b.state = "dropped"
        for hh in b.missing:
            if self._want.get(hh) is b:
                del self._want[hh]

    def _set_head_wait(self, waiting: bool) -> None:
        """``ibd.head_wait`` is open while the engine has nothing in
        verification and the planner has scheduled blocks that are not
        here: on the wire, or with no peer to ask."""
        if waiting and self._head_wait is None:
            self._head_wait = span("ibd.head_wait")
            self._head_wait.__enter__()
        elif not waiting and self._head_wait is not None:
            self._head_wait.__exit__(None, None, None)
            self._head_wait = None

    def _check_peers(self, now: float) -> None:
        """Stalls and overdue batches, on every plan pass."""
        stalled: dict[object, float] = {}
        for b in list(self._batches.values()):
            if b.state != "fetching":
                continue
            # a peer works through its batches in order: a block of any
            # of them is progress on all
            last = max(b.sent_at, self._progress.get(b.peer, 0.0))
            if now - last > self._stall_timeout:
                stalled[b.peer] = max(last, stalled.get(b.peer, 0.0))
            elif now - b.sent_at > self.cfg.fetch_timeout:
                self._failed(b, "timeout")
        for peer, last in stalled.items():
            held = sorted(
                (b for b in self._batches.values()
                 if b.state == "fetching" and b.peer is peer),
                key=lambda b: b.lo,
            )
            self._stalls += 1
            metrics.inc("ibd.stalls")
            events.emit(
                "ibd.stall", peer=getattr(peer, "label", "?"),
                lo=held[0].lo, hi=held[-1].hi,
                idle=round(now - last, 3),
                timeout=round(self._stall_timeout, 3),
            )
            log.warning(
                "[IBD] peer %s stalls the download (%.1fs without a "
                "block): disconnecting", getattr(peer, "label", "?"),
                now - last,
            )
            held[0].stalled_at = last
            for b in held:
                self._failed(b, "stall")
            self._stallers.add(peer)
            peer.kill(PeerStalling(f"no block for {now - last:.1f}s"))
            self._stall_timeout = min(
                STALL_TIMEOUT_MAX, 2.0 * self._stall_timeout
            )

    def _uncovered(self, lo: int) -> tuple[list[tuple[int, int]], int]:
        """Where no batch covers the heights from ``lo`` up: the holes
        that have a batch above them, and the open edge, the first height
        above every batch."""
        holes: list[tuple[int, int]] = []
        edge = lo
        for b_lo, b_hi in sorted(
            (b.lo, b.hi) for b in self._batches.values()
        ):
            if b_lo > edge:
                holes.append((edge, b_lo - 1))
            edge = max(edge, b_hi + 1)
        return holes, edge

    def _refresh_hashes(self, best) -> bool:
        """Maintain the height->hash view of the best chain: O(1) per tip
        extension, one bounded walk down to the first already-agreeing
        entry after a reorg.  The view covers ``[watermark+1, best]`` —
        ``_cache_floor`` tracks its lower edge so a reorg unwind that
        moves the watermark BACKWARD re-fills the newly-needed heights
        (early-stopping on an agreeing entry is only sound when the
        cached range already reaches the floor).  -> whether the view
        moved."""
        floor = max(self._utxo.height, 0)
        covered = self._cache_floor <= floor + 1
        if best.hash == self._cache_best and covered:
            return False
        node = best
        while node is not None and node.height > floor:
            if covered and self._hashes.get(node.height) == node.hash:
                break  # below here the cached view already agrees
            self._hashes[node.height] = node.hash
            node = self._chain.get_block(node.header.prev)
        self._cache_floor = min(self._cache_floor, floor + 1)
        self._cache_best = best.hash
        # a reorg may have shortened the chain: drop orphaned heights,
        # which are contiguous down from the view's old top
        for h in range(best.height + 1, self._cache_top + 1):
            self._hashes.pop(h, None)
        self._cache_top = best.height
        return True

    def _assign(self) -> None:
        """Hand queued batches to online peers with capacity, lowest
        heights first (the watermark only advances contiguously)."""
        # online, best median RTT first; then by what the planner itself
        # has seen: the fastest server of blocks first, a peer that has
        # not served a batch yet (pace 0) before all, to find out
        peers = sorted(
            (o for o in self._peer_mgr.get_peers()
             if o.peer not in self._stallers),
            key=lambda o: self._pace.get(o.peer, 0.0),
        )
        if not peers:
            return
        cap = self.cfg.max_inflight_per_peer
        on_wire = self._on_wire()
        room = self._pending_cap - self._pending()
        for lo in sorted(self._batches):
            b = self._batches[lo]
            if b.state != "queued":
                continue
            if on_wire and on_wire + len(b.missing) > room:
                break  # more would arrive than the node takes unshed
            if (
                self._pressure_key is not None
                and b.hashes
                and b.hashes[0] is not None
                and self._pressure_key(b.hashes[0])
            ):
                # this batch's verify host is saturated: defer IT, keep
                # assigning batches bound for other hosts (ISSUE 19)
                metrics.inc("ibd.deferred_batches")
                continue
            pick = next(
                (o.peer for o in peers
                 if self._inflight.get(o.peer, 0) < cap
                 and o.peer not in b.tried),
                None,
            )
            if pick is None:
                # every capable peer already failed this batch: rotate the
                # fleet and let the next pass retry from anyone
                if b.tried and all(
                    o.peer in b.tried for o in peers
                ):
                    b.tried.clear()
                    self._retries += 1
                    metrics.inc("ibd.rotations")
                continue
            self._request(b, pick)
            on_wire += len(b.missing)

    def _request(self, b: _Batch, peer) -> None:
        """One getdata for what the batch still misses, and the ping that
        says when the peer is through with it.  The blocks themselves
        arrive through the peer-message path (``block_arrived``)."""
        now = time.monotonic()
        if b.attempts:
            metrics.inc("ibd.blocks_rerequested", len(b.missing))
        if b.stalled_at:
            record_span("ibd.stall", now - b.stalled_at)
            b.stalled_at = 0.0
        b.state = "fetching"
        b.peer = peer
        b.sent_at = now
        b.nonce = random.getrandbits(64)
        self._inflight[peer] = self._inflight.get(peer, 0) + 1
        metrics.inc("ibd.fetches")
        t = InvType.WITNESS_BLOCK if self._net.segwit else InvType.BLOCK
        peer.send_message(MsgGetData(tuple(
            InvVector(t, hh) for hh in b.hashes if hh in b.missing
        )))
        peer.send_message(MsgPing(b.nonce))

    def _release(self, peer) -> None:
        n = self._inflight.get(peer, 0) - 1
        if n > 0:
            self._inflight[peer] = n
        else:
            self._inflight.pop(peer, None)

    def _delivered(self, b: _Batch, now: float) -> None:
        """Every block of the batch is here."""
        if b.state == "fetching":
            self._release(b.peer)
            if not b.attempts:  # a whole batch from one peer: its pace
                took = (now - b.sent_at) / len(b.hashes)
                old = self._pace.get(b.peer)
                self._pace[b.peer] = (
                    took if old is None else 0.75 * old + 0.25 * took
                )
            self._stall_timeout = max(
                self.cfg.stall_timeout,
                STALL_TIMEOUT_DECAY * self._stall_timeout,
            )
        b.state = "delivered"
        b.peer = None
        b.delivered_at = now
        self._fetched_blocks += b.hi - b.lo + 1
        metrics.inc("ibd.blocks", b.hi - b.lo + 1)
        self._wake.set()

    def _failed(self, b: _Batch, why: str) -> None:
        """The peer will not complete the batch: what is missing goes
        back to the queue, for another peer."""
        peer = b.peer
        self._release(peer)
        b.state = "queued"
        b.peer = None
        b.tried.add(peer)
        b.attempts += 1
        metrics.inc("ibd.batch_failures")
        events.emit(
            "ibd.batch_failed", lo=b.lo, hi=b.hi,
            attempts=b.attempts, missing=len(b.missing), why=why,
            peer=getattr(peer, "label", "?"),
        )
        self._wake.set()
