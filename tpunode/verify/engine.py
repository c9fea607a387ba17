"""Async batch verification engine: the queue between ingest and the TPU.

The north-star integration point (BASELINE.json): block/mempool ingest
submits VerifyItem tuples (ECDSA / BCH Schnorr / BIP340 — see
tpunode/verify/raw.py); the engine accumulates them into
fixed-shape batches (static shapes = no XLA recompilation), dispatches to
the TPU kernel — or the C++ CPU engine for small batches / no device — and
resolves per-item futures.

Streaming pipeline (ISSUE 10): queued submissions are no longer dispatched
FIFO-coalesced — a lane-packing scheduler (:mod:`tpunode.verify.sched`)
bins pending payloads into full ``device_batch`` lanes across submission
boundaries with priority classes (block > mempool > bulk) and a
max-linger deadline, and up to ``VerifyConfig.pipeline_depth`` packed
lanes are in flight at once, each in its own worker thread.  JAX device
dispatch is asynchronous, so lane N+1's host prep and transfer overlap
lane N's kernel; the asyncio event loop (the P2P side) never blocks.
``pipeline_depth=1`` restores strictly serial dispatch for A/B runs.
Small remainders pack with later submissions instead of defaulting to the
CPU rung; ``min_tpu_batch`` is a shed-only floor applied when a lingering
partial lane finally dispatches.  With ``mesh_devices > 1`` the device
rung shards packed lanes over a local device mesh
(:func:`multichip.dispatch_raw_sharded`).

Pod scale (ISSUE 13): ``mesh_hosts >= 2`` promotes the pipeline into a
cross-host fleet — the device set is carved into that many host groups
(a ``(host, chip)`` hybrid mesh, :func:`multichip.make_hybrid_mesh`),
each host runs ``pipeline_depth`` dispatch workers pulling packed lanes
from a work-stealing :class:`sched.FleetDispatcher` (idle hosts steal
whole lanes from the deepest peer queue), and each host carries its OWN
circuit breaker and device sub-mesh so one sick host degrades alone.
Degradation is chip-by-chip: a device loss shrinks that host's sub-mesh
to the largest still-healthy half (re-grown when its breaker's canary
closes); a host partition re-queues the lane onto a healthy peer
(exactly once — the lane delivered nothing), deactivates the host, and
a cooldown-paced canary rejoin re-grows the fleet.  With every host
dark, lanes fall through the local ladder so waiters still resolve.

Device survival discipline (VERDICT r2 item 4 + ISSUE 7): the TPU path is
only used after an off-queue **warmup** (backend init + XLA compile at the
fixed batch shape + a verdict cross-check against the oracle) completes in
a background thread.  Until then ``backend="auto"`` batches flow to the
CPU engine, so a box with a broken or slow TPU backend still produces
verdicts with nothing blocked and the decision logged; a failed warmup is
re-probed on a timer (``warmup_retry``), never terminal.  Compiles go
through a persistent compilation cache so a restart reuses earlier work.

Self-healing dispatch (ISSUE 7): under ``backend="auto"`` a batch that
fails on one backend re-dispatches down the ladder (tpu -> cpu-native ->
python oracle), so waiters get verdicts — not exceptions — for transient
faults; only a batch that fails on EVERY rung fails its waiters (and only
its own: the queue loop survives to serve the next batch).  A forced
``backend="tpu"`` never ladders down: nothing stands in for the chip, the
device error reaches the waiters.  Device-rung failures feed
a :class:`CircuitBreaker` (``ready -> degraded -> open -> probing ->
ready``): repeated failures inside a window open the breaker and route
all traffic to the CPU, then a periodic half-open canary batch re-probes
the device and restores the fast path when it recovers.  The state
machine is observable as ``verify.breaker`` events, the
``verify.breaker_state`` gauge, engine ``stats()`` and ``/health``.

Mirrors the role the reference's synchronous libsecp256k1 callout plays, but
asynchronous and batched (SURVEY.md §2.3: this IS the data-parallel north
star path).
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import logging
import os
import threading
import time
from dataclasses import dataclass
from typing import Callable, Optional, Sequence

import numpy as np

from .. import threadsan
from ..actors import spawn_supervised
from ..chaos import ChaosPartition, chaos
from ..events import events
from ..metrics import metrics
from ..trace import span
from ..tracectx import activate as _activate_trace, current as _trace_current
from .ecdsa_cpu import Point, verify_batch_cpu
from .raw import as_raw_batch, concat_raw
from .sched import (
    OCCUPANCY_BUCKETS as _OCCUPANCY_BUCKETS,
    FleetDispatcher,
    LanePacker,
    PackedLane,
    Submission,
)

__all__ = [
    "CircuitBreaker",
    "HostLost",
    "VerifyConfig",
    "VerifyEngine",
    "VerifyItem",
    "enable_compile_cache",
]


class HostLost(RuntimeError):
    """A fleet host is unreachable (ISSUE 13): the dispatch ladder must
    NOT serve the lane locally on this host's behalf — the worker
    re-queues it onto a healthy peer and deactivates the host.  Today
    raised for an injected ``mesh.dispatch:partition``; a real pod's
    RPC/transport failures map here too."""

# (pubkey, z, r, s) for ECDSA; 5-tuples append "schnorr" (BCH) or
# "bip340" (taproot) with the precomputed challenge in the z position.
VerifyItem = tuple  # see raw.pack_items for the per-algorithm rules

# RawBatch.present -> the algorithm's name ("none": an auto-invalid row)
_ALGORITHMS = ("none", "ecdsa", "schnorr", "bip340")


def _count_algorithms(payloads: list) -> None:
    """``verify.items_in{algo=}``: the device items of a lane by signature
    algorithm, counted by the dispatch thread that takes the lane (ISSUE
    42; never on the loop: a numpy call there gives the GIL up in the
    middle of a hold).  All four series move together, so each exists
    from the first lane on."""
    n = [0, 0, 0, 0]
    for p in payloads:
        present = getattr(p, "present", None)
        if present is not None:
            for i, k in enumerate(np.bincount(present, minlength=4).tolist()):
                n[i] += k
        else:
            for it in p:
                if it[0] is None:
                    n[0] += 1
                else:
                    n[_ALGORITHMS.index(it[4]) if len(it) > 4 else 1] += 1
    metrics.inc_batch(
        ("verify.items_in", k, {"algo": a}) for a, k in zip(_ALGORITHMS, n)
    )


log = logging.getLogger("tpunode.verify")

_DEFAULT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache (idempotent) and return
    the directory in force.  The directory is placed from outside: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and none is
    set in code; otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so a
    directory that moves never hits).  Any process on this machine (engine
    warmup, chip_smoke.py, tests) then reuses the first successful compile.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return jax.config.jax_compilation_cache_dir


def _device_warmup(batch_size: int, device_batch: int = 0) -> str:
    """Default warmup body (runs in a daemon thread): init the backend,
    compile every program the dispatcher can select — the full and the
    ECDSA-only (``schnorr_free``) variant, each at the engine's two fixed
    batch shapes (the small ``batch_size`` shape first, then the big
    ``device_batch`` steady-state shape) — and cross-check each against
    the oracle.  Returns the device kind string.  Raises on any failure —
    a compile error, or a verdict mismatch, which must disqualify the
    device path."""
    import jax

    enable_compile_cache()
    devs = [d for d in jax.devices() if d.platform == "tpu"]
    if not devs:
        raise RuntimeError("no TPU device visible")
    from .ecdsa_cpu import (
        CURVE_N,
        GENERATOR,
        bip340_challenge,
        lift_x,
        point_mul,
        schnorr_challenge,
        sign,
        sign_bip340,
        sign_schnorr,
    )
    from .kernel import verify_batch_tpu

    items = []
    expect = []
    for i in range(8):
        priv = (0xA11CE + i) % CURVE_N
        pub = point_mul(priv, GENERATOR)
        z = (0xD00D << i) % CURVE_N
        # every algorithm's lane compiles + cross-checks in the one program
        if i % 4 == 1:
            r, s = sign_schnorr(priv, z, 0xC0FFEE + i)
            if i % 3 == 2:
                z ^= 1
            items.append((pub, schnorr_challenge(r, pub, z), r, s, "schnorr"))
            expect.append(i % 3 != 2)
            continue
        if i % 4 == 3:
            r, s = sign_bip340(priv, z, 0xC0FFEE + i)
            if i % 3 == 2:
                z ^= 1
            items.append(
                (lift_x(pub.x), bip340_challenge(r, pub.x, z), r, s, "bip340")
            )
            expect.append(i % 3 != 2)
            continue
        r, s = sign(priv, z, 0xC0FFEE + i)
        if i % 3 == 2:
            z ^= 1
        items.append((pub, z, r, s))
        expect.append(i % 3 != 2)
    # the ECDSA-only subset selects the schnorr_free program variant
    plain = [it for it in items if len(it) == 4]
    plain_expect = [ok for it, ok in zip(items, expect) if len(it) == 4]
    shapes = [batch_size]
    if device_batch and device_batch != batch_size:
        shapes.append(device_batch)
    for shape in shapes:
        for schnorr_free, its, want in (
            (False, items, expect),
            (True, plain, plain_expect),
        ):
            t0 = time.perf_counter()
            got = verify_batch_tpu(its, pad_to=shape)
            # first call per program: trace + compile (or persistent-cache
            # load) + one step — set-up time, never a rate
            events.emit(
                "verify.compile", batch=shape, schnorr_free=schnorr_free,
                seconds=round(time.perf_counter() - t0, 3),
            )
            if got != want:
                raise RuntimeError(
                    f"device/oracle verdict mismatch during warmup at "
                    f"batch {shape}"
                )
    return f"{devs[0].platform}:{devs[0].device_kind}"


class CircuitBreaker:
    """Device-path health state machine (ISSUE 7).

    States (``STATES`` order is the ``verify.breaker_state`` gauge
    encoding):

    * ``ready``    — device path in use, no recent failures.
    * ``degraded`` — failures seen inside the window (< threshold); the
      device is still used, each failed batch already re-ran on the CPU
      rung via the dispatch ladder.
    * ``open``     — threshold reached: all traffic to the CPU, the
      device isn't attempted at all until the cooldown elapses.
    * ``probing``  — cooldown elapsed: exactly one live batch is routed
      to the device as a half-open canary.  Success closes the breaker
      (``ready``, recovery latency observed); failure re-opens it and
      restarts the cooldown.

    Thread-safe: transitions happen on the engine's dispatch worker
    thread (ladder outcomes) and the queue loop (backend picks).  Every
    transition emits one ``verify.breaker`` event and updates the
    ``verify.breaker_state`` gauge.
    """

    STATES = ("ready", "degraded", "open", "probing")

    def __init__(
        self,
        threshold: int = 3,
        window: float = 30.0,
        cooldown: float = 5.0,
        name: str = "",
    ):
        self.threshold = max(1, threshold)
        self.window = window
        self.cooldown = cooldown
        # Fleet host identity (ISSUE 13): named breakers label their
        # gauge/events with host= so one sick host's transitions don't
        # masquerade as engine-wide device health.
        self.name = name
        # Reentrant: _transition emits verify.breaker with the lock held,
        # and a synchronous event observer (the flight recorder freezing
        # a bundle on the open transition) calls back into stats() on the
        # same thread — a plain Lock would self-deadlock there (the PR 14
        # hang, now pinned via threadsan in tests/test_threadsan.py).
        # Per-host breakers register under their own name so the fleet's
        # host->engine acquisition edges don't alias into self-loops.
        self._lock = threadsan.rlock(
            f"verify.breaker.{name}" if name else "verify.breaker"
        )
        self._state = "ready"
        self._failures: collections.deque[float] = collections.deque()
        self._opened_at: Optional[float] = None
        self._last_error: Optional[str] = None
        self.opens = 0
        self.closes = 0

    @property
    def state(self) -> str:
        return self._state

    def allow_device(self) -> bool:
        """May this batch take the device path?  ``open -> probing`` when
        the cooldown has elapsed — the caller's batch becomes the canary
        (exactly one: while ``probing``, everyone else stays on cpu)."""
        with self._lock:
            if self._state in ("ready", "degraded"):
                return True
            if self._state == "probing":
                return False  # a canary is already in flight
            now = time.monotonic()
            if (
                self._opened_at is not None
                and now - self._opened_at >= self.cooldown
            ):
                self._transition("probing")
                return True
            return False

    def record_success(self) -> bool:
        """A device batch completed: close toward ``ready``.  Returns
        True when this success CLOSED an open/probing breaker (the
        fleet's re-grow hook — a successful canary restores the host's
        full sub-mesh)."""
        with self._lock:
            self._failures.clear()
            if self._state == "ready":
                return False
            fields = {}
            if self._opened_at is not None:
                recovery = time.monotonic() - self._opened_at
                metrics.observe("verify.breaker_recovery_seconds", recovery)
                fields["recovery_seconds"] = round(recovery, 3)
            closed = self._state in ("open", "probing")
            if closed:
                self.closes += 1
                metrics.inc("verify.breaker_closes")
            self._opened_at = None
            self._last_error = None
            self._transition("ready", **fields)
            return closed

    def record_failure(self, error: str = "") -> None:
        """A device batch failed (the ladder already re-dispatched it)."""
        with self._lock:
            now = time.monotonic()
            self._failures.append(now)
            while self._failures and now - self._failures[0] > self.window:
                self._failures.popleft()
            self._last_error = error or None
            if (
                self._state == "probing"
                or len(self._failures) >= self.threshold
            ):
                # a failed canary re-opens immediately; repeated failures
                # inside the window open from ready/degraded
                self._opened_at = now
                if self._state != "open":
                    self.opens += 1
                    metrics.inc("verify.breaker_opens")
                    self._transition(
                        "open", failures=len(self._failures), error=error,
                    )
            elif self._state == "ready":
                self._transition(
                    "degraded", failures=len(self._failures), error=error,
                )

    def trip(self, error: str = "") -> None:
        """Force the breaker OPEN immediately (ISSUE 13: a host
        partition is not three strikes — the host is gone NOW; the
        cooldown/canary recovery machinery applies unchanged)."""
        with self._lock:
            now = time.monotonic()
            self._failures.append(now)
            self._last_error = error or None
            self._opened_at = now
            if self._state != "open":
                self.opens += 1
                metrics.inc("verify.breaker_opens")
                self._transition("open", error=error, forced=True)

    def _transition(self, to: str, **fields) -> None:
        # lock held by the caller
        frm, self._state = self._state, to
        metrics.set_gauge(
            "verify.breaker_state",
            float(self.STATES.index(to)),
            labels={"host": self.name} if self.name else None,
        )
        if self.name:
            fields = {"host": self.name, **fields}
        log.warning("[Engine] breaker %s -> %s %s", frm, to, fields or "")
        events.emit("verify.breaker", **{"from": frm, "to": to, **fields})

    def stats(self) -> dict:
        with self._lock:
            out = {
                "state": self._state,
                "failures_in_window": len(self._failures),
                "threshold": self.threshold,
                "opens": self.opens,
                "closes": self.closes,
                "last_error": self._last_error,
            }
            if self._opened_at is not None:
                out["open_age_seconds"] = round(
                    time.monotonic() - self._opened_at, 3
                )
            return out


@dataclass
class VerifyConfig:
    """Knobs (gated behind NodeConfig like the reference's config surface,
    Node.hs:74-96; see BASELINE.json north_star 'gated behind the existing
    NodeConfig hooks')."""

    backend: str = "auto"  # auto | tpu | cpu | oracle
    batch_size: int = 4096  # small device shape / queue coalescing threshold
    # Steady-state device shape: the Pallas kernel's measured sweet spot is
    # 32768 (210.9k sigs/s vs 54.5k at 4096 — PERF.md r3 table; VERDICT r3
    # item 4).  Work under ``batch_size`` pads to the small shape, bigger
    # work is chunked at this size; warmup compiles both shapes.
    device_batch: int = 32768
    # seconds a class with a waiter lingers for a fuller lane; a class's
    # multiple of it is sched.LINGER (ibd backfill: 2)
    max_wait: float = 0.025
    # Streaming pipeline width (ISSUE 10): how many packed lanes may be
    # in flight at once, each in its own dispatch thread.  2 overlaps
    # lane N+1's host prep + transfer with lane N's kernel (JAX async
    # dispatch); 1 restores the serial pre-pipeline dispatch for A/B.
    pipeline_depth: int = 2
    # Mesh-aware device rung (ISSUE 10): >1 shards each packed lane over
    # a mesh of that many local devices (multichip.dispatch_raw_sharded);
    # fewer visible devices than asked for is an error, not a smaller
    # mesh.  0/1 keeps single-chip dispatch.  The mesh program compiles on
    # first dispatch (warmup compiles the single-chip shapes only).
    mesh_devices: int = 0
    # Pod-scale fleet dispatch (ISSUE 13): >= 2 carves the device set
    # into this many host groups (a (host, chip) hybrid mesh —
    # multichip.make_hybrid_mesh; with mesh_devices set, only that many
    # devices are carved) and runs pipeline_depth work-stealing dispatch
    # workers PER HOST (sched.FleetDispatcher), each host with its own
    # circuit breaker and device sub-mesh so one sick host degrades
    # alone.  0 (default) keeps the single-host pipeline.  1 is
    # rejected: a one-host fleet is the single-host pipeline.
    mesh_hosts: int = 0
    # Per-host assigned-lane cap (lanes): how deep the scheduler may
    # pre-assign packed lanes onto one host's queue before waiting.
    # Shallow queues keep late high-priority submissions packing ahead
    # of un-cut work; the work-stealing makes depth mostly latency, not
    # throughput.
    fleet_queue: int = 2
    # Below this, the CPU engine beats a device step padded to batch_size:
    # the device pays one full fixed-shape step regardless of occupancy,
    # while the C++ engine verifies ~4.8k sigs/s — crossover near
    # batch_size/4.  Small remainder chunks also route to CPU.
    min_tpu_batch: int = 1024
    # CPU-fallback verify parallelism: 1 = serial (the measurement-honest
    # default on this 1-core dev box), 0 = all hardware threads, N = N OS
    # threads (secp_verify_batch_mt; each MSM row is independent).
    cpu_threads: int = 1
    # device warmup discipline
    # backend=tpu: max wait for warmup.  A cold warmup compiles four
    # programs (two variants x two shapes); see PERF.md "On the chip" for
    # the measured compile seconds this has to cover.
    warmup_timeout: float = 600.0
    warmup: bool = True  # start warmup thread on engine start
    # A failed warmup is re-probed after this many seconds (ISSUE 7: a
    # terminal `failed` state would outlive a transient device fault).
    # 0 disables re-probing.
    warmup_retry: float = 60.0
    # Circuit breaker on the device dispatch path (ISSUE 7):
    # `breaker_threshold` failures inside `breaker_window` seconds open
    # the breaker (all traffic to cpu); after `breaker_cooldown` seconds
    # one live batch probes the device and, on success, restores the
    # fast path.
    breaker_threshold: int = 3
    breaker_window: float = 30.0
    breaker_cooldown: float = 5.0

    def __post_init__(self):
        if self.device_batch < self.batch_size:
            self.device_batch = self.batch_size
        if self.pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        if self.mesh_hosts == 1 or self.mesh_hosts < 0:
            raise ValueError(
                "mesh_hosts: 0 disables the fleet, >= 2 enables it"
            )
        if self.fleet_queue < 1:
            raise ValueError("fleet_queue must be >= 1")


class _HostState:
    """Per-host fleet state (ISSUE 13): its breaker, its device
    sub-mesh (with the current healthy width), and the lost/rejoin
    machinery.  Mesh fields are guarded by the engine's ``_mesh_lock``
    (dispatch worker threads race on first build / shrink / re-grow);
    ``lost`` is written on the event loop and in dispatch threads but
    only ever flips through the engine's ``_host_down`` /
    ``_host_rejoin`` which the worker task serializes per host."""

    __slots__ = (
        "name", "index", "breaker", "lost", "lost_at",
        "mesh", "chips", "full_chips", "shrunk_at", "event",
    )

    def __init__(self, name: str, index: int, cfg: "VerifyConfig"):
        self.name = name
        self.index = index
        self.breaker = CircuitBreaker(
            threshold=cfg.breaker_threshold,
            window=cfg.breaker_window,
            cooldown=cfg.breaker_cooldown,
            name=name,
        )
        self.lost = False
        self.lost_at = 0.0
        self.mesh = None  # lazily-built 1-D sub-mesh over this host's row
        self.chips = 0  # current healthy sub-mesh width (0 = not built yet)
        self.full_chips = 0  # the full row width (re-grow target)
        self.shrunk_at = 0.0  # last shrink time (paces the re-grow probe)
        self.event: Optional[asyncio.Event] = None  # lane-assigned wakeup


metrics.describe(
    "verify.cost_seconds",
    "wall-clock rung seconds charged to each priority class, pro-rated "
    "by item count",
)


class CostLedger:
    """Per-class cost attribution (ISSUE 17): every dispatched lane's
    wall-clock rung time is charged back to the priority classes of the
    submissions it carried, pro-rated by item count.  The charge is cut
    from the ONE measured ``dt`` around :meth:`VerifyEngine._run_ladder`,
    so conservation holds by construction: summed charged seconds equal
    total rung busy seconds (the pin in tests/test_slo.py allows 5% for
    float accumulation, nothing more).

    Thread-safe — charges arrive from every dispatch worker thread;
    snapshots from stats()/the flight recorder."""

    def __init__(self):
        self._lock = threadsan.lock("verify.ledger")
        # (priority, rung) -> [charged seconds, items]
        self._cells: dict[tuple[str, str], list] = {}
        self._busy = 0.0  # total measured rung busy seconds
        # host -> charged seconds: per-host attribution (ISSUE 19) —
        # charged to the EXECUTING host, so a stolen lane bills the
        # thief and per-host shares stay truthful under heavy stealing
        self._by_host: dict[str, float] = {}
        # tenant -> [charged seconds, items]: serve-layer attribution
        # (ISSUE 20).  Unattributed items bill to the node itself under
        # the "" key, so conservation holds over the tenant axis too.
        self._by_tenant: dict[str, list] = {}

    def charge(
        self,
        class_counts: dict[str, int],
        total: int,
        dt: float,
        rung: str,
        host: Optional[str] = None,
        tenants: Optional[dict] = None,
    ) -> None:
        if total <= 0 or dt < 0:
            return
        shares = [
            (p, n, dt * n / total) for p, n in class_counts.items() if n > 0
        ]
        tenant_shares = []
        if tenants:
            tenant_items = 0
            for t, n in tenants.items():
                if n > 0:
                    tenant_shares.append((t, n, dt * n / total))
                    tenant_items += n
            rest = total - tenant_items
            if rest > 0:
                tenant_shares.append(("", rest, dt * rest / total))
        with self._lock:
            self._busy += dt
            if host is not None:
                self._by_host[host] = self._by_host.get(host, 0.0) + dt
            for p, n, share in shares:
                cell = self._cells.get((p, rung))
                if cell is None:
                    cell = self._cells[(p, rung)] = [0.0, 0]
                cell[0] += share
                cell[1] += n
            for t, n, share in tenant_shares:
                cell = self._by_tenant.get(t)
                if cell is None:
                    cell = self._by_tenant[t] = [0.0, 0]
                cell[0] += share
                cell[1] += n
        host_labels = {} if host is None else {"host": host}
        metrics.inc_batch(
            (
                (
                    "verify.cost_seconds",
                    share,
                    {"priority": p, "rung": rung, **host_labels},
                )
                for p, _, share in shares
            )
        )

    def snapshot(self) -> dict:
        """The ``engine.stats()["ledger"]`` / flight-recorder section:
        per-(class, rung) charged seconds + items, per-class
        items-weighted share of the total, and the busy-seconds pin."""
        with self._lock:
            cells = {k: list(v) for k, v in self._cells.items()}
            busy = self._busy
            by_host = dict(self._by_host)
            by_tenant = {k: list(v) for k, v in self._by_tenant.items()}
        charged = sum(v[0] for v in cells.values())
        by_class: dict[str, dict] = {}
        for (p, rung), (secs, items) in sorted(cells.items()):
            c = by_class.setdefault(
                p, {"seconds": 0.0, "items": 0, "rungs": {}}
            )
            c["seconds"] += secs
            c["items"] += items
            c["rungs"][rung] = {
                "seconds": round(secs, 6), "items": items,
            }
        for c in by_class.values():
            c["seconds"] = round(c["seconds"], 6)
            c["share"] = round(c["seconds"] / charged, 4) if charged else 0.0
        out = {
            "busy_seconds": round(busy, 6),
            "charged_seconds": round(charged, 6),
            "by_class": by_class,
        }
        if by_host:
            # fleet mode only (ISSUE 19): busy seconds by EXECUTING host
            out["by_host"] = {
                h: round(s, 6) for h, s in sorted(by_host.items())
            }
        if by_tenant:
            # serve mode only (ISSUE 20): charged seconds + items by
            # tenant ("" = the node's own share of tenant-mixed lanes)
            out["by_tenant"] = {
                t: {"seconds": round(v[0], 6), "items": v[1]}
                for t, v in sorted(by_tenant.items())
            }
        return out


class VerifyEngine:
    """Submit items, await verdicts.

    Usage::

        engine = VerifyEngine(VerifyConfig())
        async with engine:
            ok = await engine.verify(items)   # list[bool]
    """

    # Test seam: replace to simulate slow/broken device warmup.
    _warmup_fn: Callable[[int], str] = staticmethod(_device_warmup)

    def __init__(self, cfg: Optional[VerifyConfig] = None):
        self.cfg = cfg or VerifyConfig()
        # Lane-packing scheduler (ISSUE 10): submissions (with their
        # futures and trace positions) queue here; the pipeline loop
        # pops packed lanes from it.
        self._packer = LanePacker(
            small=self.cfg.batch_size, max_wait=self.cfg.max_wait
        )
        # Per-inflight dispatch start times keyed by a monotonic token
        # (ISSUE 10 watchdog satellite): with pipeline_depth > 1 a single
        # scalar would misattribute or miss stalls — the watchdog's
        # dispatch-stall signal reports the OLDEST in-flight dispatch.
        # Written by the queue loop and the lane tasks, read by the
        # watchdog thread: guarded by _inflight_lock.
        self._inflight: dict[int, float] = {}
        self._inflight_lock = threadsan.lock("verify.inflight")
        self._inflight_seq = 0
        # Cost-attribution ledger (ISSUE 17) + the per-dispatch-thread
        # slot carrying the lane's class counts into _dispatch_multi
        # (threading.local, not a parameter: tests and subclasses pin
        # _dispatch_multi's (payloads, target) call shape).
        self._ledger = CostLedger()
        self._tls = threading.local()
        self._last_rung = "none"  # rung of the latest served batch
        self._lane_tasks: set[asyncio.Task] = set()
        self._slots: Optional[asyncio.Semaphore] = None
        self._kick: Optional[asyncio.Event] = None
        self._task: Optional[asyncio.Task] = None
        # sharded device rung (cfg.mesh_devices): lazily-built mesh.
        # Init races between concurrent dispatch worker threads
        # (pipeline_depth > 1) are serialized by _mesh_lock — without
        # it two lanes would double-build (and double-compile).
        self._mesh_obj = None
        self._mesh_lock = threadsan.lock("verify.mesh")
        # Pod-scale fleet (ISSUE 13, cfg.mesh_hosts >= 2): per-host
        # states + the work-stealing dispatcher, built in __aenter__;
        # the hybrid mesh's device rows are carved lazily on the first
        # device dispatch (guarded by _mesh_lock).
        self._fleet: Optional[FleetDispatcher] = None
        self._hosts: dict[str, _HostState] = {}
        self._fleet_hybrid = None  # the (host, chip) Mesh, carved lazily
        self._room: Optional[asyncio.Event] = None
        if self.cfg.mesh_hosts >= 2:
            # canonical names from sched.py (ISSUE 19): the affinity
            # map's rendezvous seeds hash these strings, so the naming
            # scheme must be stable across layers
            from .sched import host_names

            self._hosts = {
                name: _HostState(name, i, self.cfg)
                for i, name in enumerate(host_names(self.cfg.mesh_hosts))
            }
            self._fleet = FleetDispatcher(
                list(self._hosts), self._packer,
                max_queue=self.cfg.fleet_queue,
            )
            metrics.set_gauge(
                "mesh.active_hosts", float(len(self._hosts))
            )
        self._cpu = None
        if self.cfg.backend in ("auto", "cpu"):
            from .cpu_native import load_native_verifier

            self._cpu = load_native_verifier()
        # device readiness state machine: cold -> warming -> ready | failed
        # (failed re-probes on the warmup_retry timer — never terminal)
        self._device_state = "cold"
        self._device_kind = ""
        self._device_error: Optional[str] = None
        self._warmup_started = 0.0
        self._warmup_failed_at = 0.0
        self._warmup_lock = threadsan.lock("verify.warmup")
        self._warmup_done = threading.Event()
        self._slow_logged = False
        # device-dispatch circuit breaker (ISSUE 7): engaged only once
        # the device is warm; open = all traffic on the cpu rungs
        self._breaker = CircuitBreaker(
            threshold=self.cfg.breaker_threshold,
            window=self.cfg.breaker_window,
            cooldown=self.cfg.breaker_cooldown,
        )
        if self.cfg.warmup and self.cfg.backend in ("auto", "tpu"):
            self.start_warmup()

    # -- device warmup -------------------------------------------------------

    def start_warmup(self) -> None:
        """Kick off device warmup in a daemon thread (idempotent).  The
        thread is never joined on the hot path: if compile stalls, dispatch
        simply keeps using the CPU engine; if it eventually succeeds, the
        device path switches on."""
        if self._device_state != "cold":
            return
        self._device_state = "warming"
        self._warmup_started = time.monotonic()

        def run() -> None:
            try:
                if chaos.on:  # injected compile/init failure (ISSUE 7)
                    chaos.maybe_raise("engine.warmup")
                kind = type(self)._warmup_fn(
                    self.cfg.batch_size, self.cfg.device_batch
                )
            except Exception as e:  # noqa: BLE001 — any failure disables tpu
                self._device_error = f"{type(e).__name__}: {e}"
                self._warmup_failed_at = time.monotonic()
                self._device_state = "failed"
                log.warning(
                    "[Engine] device warmup failed (re-probe in %.0fs): %s",
                    self.cfg.warmup_retry,
                    self._device_error,
                )
                events.emit(
                    "verify.device", state="failed", error=self._device_error
                )
            else:
                self._device_kind = kind
                self._device_state = "ready"
                dt = time.monotonic() - self._warmup_started
                log.info("[Engine] device ready (%s) after %.1fs", kind, dt)
                events.emit(
                    "verify.device", state="ready", kind=kind,
                    warmup_seconds=round(dt, 3),
                )
            finally:
                self._warmup_done.set()

        threading.Thread(target=run, name="verify-warmup", daemon=True).start()

    def _retry_warmup(self) -> None:
        """Re-probe a failed device warmup (ISSUE 7: `failed` is a
        cooldown, not a verdict).  Called from the dispatch path once the
        retry interval elapses; idempotent and thread-safe — exactly one
        caller flips failed -> cold and relaunches the warmup thread."""
        with self._warmup_lock:
            if self._device_state != "failed":
                return
            if (
                time.monotonic() - self._warmup_failed_at
                < self.cfg.warmup_retry
            ):
                return
            log.info(
                "[Engine] re-probing device warmup after failure: %s",
                self._device_error,
            )
            events.emit("verify.device", state="reprobe",
                        error=self._device_error)
            # fresh latch: forced-tpu waiters must block on THIS attempt
            self._warmup_done = threading.Event()
            self._slow_logged = False
            self._device_state = "cold"
            self.start_warmup()

    @property
    def device_state(self) -> str:
        return self._device_state

    @property
    def breaker(self) -> CircuitBreaker:
        return self._breaker

    @property
    def breaker_state(self) -> str:
        """Device-path breaker state (``/health``): the warmup machine's
        view until the device is warm, the breaker's after."""
        if self._device_state != "ready":
            return self._device_state
        return self._breaker.state

    def queue_depth(self) -> dict:
        """Current backlog: queued submissions, total unclaimed items,
        and the per-priority split (``by_priority`` is itself a dict).
        Fleet mode aggregates the central + per-host packers."""
        if self._fleet is not None:
            return {
                "batches": self._fleet.batches(),
                "items": self._fleet.uncut_pending(),
                "by_priority": self._fleet.depths(),
            }
        return {
            "batches": self._packer.batches(),
            "items": self._packer.pending(),
            "by_priority": self._packer.depths(),
        }

    def dispatch_inflight_seconds(self) -> float:
        """Age of the OLDEST in-flight dispatch across the pipeline
        (0.0 when idle) — the stall watchdog's signal.  A wedged device
        backend pins the oldest entry while younger lanes (and the event
        loop) stay healthy."""
        with self._inflight_lock:
            if not self._inflight:
                return 0.0
            return time.monotonic() - min(self._inflight.values())

    def dispatch_inflight(self) -> int:
        """How many packed lanes are currently in dispatch threads."""
        with self._inflight_lock:
            return len(self._inflight)

    def ledger(self) -> dict:
        """Cost-attribution snapshot (ISSUE 17): per-class charged rung
        seconds + the conservation pin — also under stats()["ledger"]."""
        return self._ledger.snapshot()

    @property
    def last_rung(self) -> str:
        """The ladder rung that served the most recent batch ("none"
        before any dispatch) — what a verdict receipt binds (ISSUE 20)."""
        return self._last_rung

    def stats(self) -> dict:
        """Telemetry snapshot for Node.stats()/health()."""
        out = {
            "backend": self.cfg.backend,
            "device_state": self._device_state,
            "device_kind": self._device_kind or None,
            "device_error": self._device_error,
            "device_batch": self.cfg.device_batch,
            "backlog": self.queue_depth(),
            "dispatch_inflight_seconds": round(
                self.dispatch_inflight_seconds(), 3
            ),
            "dispatch_inflight": self.dispatch_inflight(),
            "pipeline_depth": self.cfg.pipeline_depth,
            "lanes": metrics.get("sched.lanes"),
            "batches": metrics.get("verify.batches"),
            "items": metrics.get("verify.items"),
            "errors": metrics.get("verify.dispatch_errors"),
            "failovers": metrics.get("verify.failovers"),
            "breaker": self._breaker.stats(),
        }
        if self._fleet is not None:
            out["fleet"] = {
                "hosts": len(self._hosts),
                "active": self._fleet.active_hosts(),
                "depths": self._fleet.host_depths(),
                "steals": self._fleet.steals,
                "host_steals": dict(self._fleet.host_steals),
                "requeued": self._fleet.requeued,
                "queued_lanes": self._fleet.queued_lanes(),
                "breakers": {
                    name: hs.breaker.state
                    for name, hs in self._hosts.items()
                },
                "chips": {
                    name: hs.chips for name, hs in self._hosts.items()
                },
                # host-affine feed surface (ISSUE 19)
                "feed_depths": self._fleet.feed_depths(),
                "feed_idle": {
                    h: round(v, 4)
                    for h, v in self._fleet.feed_idle().items()
                },
                "affinity": {
                    "routed": self._fleet.affinity_routed,
                    "spilled": self._fleet.affinity_spilled,
                },
            }
        occ = metrics.histogram("verify.occupancy")
        if occ is not None:
            out["occupancy"] = occ.summary()
        pack = metrics.histogram("sched.pack_efficiency")
        if pack is not None:
            out["pack_efficiency"] = pack.summary()
        disp = metrics.histogram("span.verify.dispatch")
        if disp is not None:
            out["dispatch_seconds"] = disp.summary()
        out["ledger"] = self._ledger.snapshot()
        return out

    # -- lifecycle -----------------------------------------------------------

    async def __aenter__(self) -> "VerifyEngine":
        self._kick = asyncio.Event()
        self._slots = asyncio.Semaphore(self.cfg.pipeline_depth)
        self._closing = False  # task-registry owner convention (actors.py)
        if self._fleet is not None:
            self._room = asyncio.Event()
            for hs in self._hosts.values():
                hs.event = asyncio.Event()
                for _ in range(self.cfg.pipeline_depth):
                    t = spawn_supervised(
                        self._host_worker(hs),
                        name=f"verify-host-{hs.name}",
                        owner=self,
                    )
                    self._lane_tasks.add(t)
                    t.add_done_callback(self._lane_tasks.discard)
        # ISSUE 3 satellite: the queue loop was a bare create_task handle —
        # registry-supervised now, cancelled+awaited in __aexit__ below
        self._task = spawn_supervised(
            self._run(), name="verify-engine", owner=self
        )
        return self

    async def __aexit__(self, *exc) -> None:
        self._closing = True
        if self._task is not None:
            self._task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await self._task
        # in-flight lanes + fleet workers: cancel + await (their dispatch
        # threads finish behind the cancelled await; verdicts for
        # cancelled lanes are dropped with the futures below)
        for t in list(self._lane_tasks):
            t.cancel()
        for t in list(self._lane_tasks):
            with contextlib.suppress(asyncio.CancelledError, Exception):
                await t
        self._lane_tasks.clear()
        # lanes still assigned to host queues (cut from the packer but
        # never taken — incl. lanes re-queued mid-steal by a dying host):
        # cancel their carried futures exactly like queued submissions;
        # Submission.deliver tolerates a done/cancelled future, so a
        # concurrent late delivery cannot double-resolve (ISSUE 13
        # lane-requeue hardening).
        if self._fleet is not None:
            for lane in self._fleet.drain_lanes():
                for sub, _, _ in lane.slices:
                    if not sub.fut.done():
                        sub.fut.cancel()
            # stragglers across the central AND per-host packers
            for sub in self._fleet.drain_submissions():
                if not sub.fut.done():
                    sub.fut.cancel()
            # Permanent host retirement (ISSUE 19 labeled-series
            # lifecycle): engine teardown is the one point a fleet's
            # hosts deactivate for good — drop their host= series from
            # the registry (and, via the registry's drop hooks, from
            # any Timeline sampler) so fleet churn across engine
            # lifetimes can't grow label cardinality unboundedly.
            for name in self._hosts:
                metrics.drop_label("host", name)
        else:
            # fail any stragglers still queued (or partially claimed)
            for sub in self._packer.drain():
                if not sub.fut.done():
                    sub.fut.cancel()

    # -- API -----------------------------------------------------------------

    async def verify(
        self,
        items: Sequence[VerifyItem],
        priority: str = "bulk",
        affinity: Optional[int] = None,
        tenant: Optional[str] = None,
    ) -> list[bool]:
        """Queue items; resolves when their lanes have been verified.
        ``priority``: ``block`` > ``mempool`` > ``bulk`` (sched.py) — the
        class whose lanes pack and dispatch first under saturation.
        ``affinity`` (fleet mode, ISSUE 19): a ``sched.affinity_key``
        routing this submission to its home host's packer — a placement
        hint only, never a correctness input.  ``tenant`` (serve mode,
        ISSUE 20): the registered tenant this submission's rung time
        bills to in the cost ledger."""
        return await self._enqueue(list(items), priority, affinity, tenant)

    async def verify_raw(
        self,
        raw,
        priority: str = "bulk",
        affinity: Optional[int] = None,
        tenant: Optional[str] = None,
    ) -> list[bool]:
        """Queue a packed batch (RawBatch, or anything `as_raw_batch`
        coerces, e.g. txextract.RawSigItems): the native-extract fast path —
        no per-item Python objects anywhere between wire bytes and device."""
        return await self._enqueue(as_raw_batch(raw), priority, affinity,
                                   tenant)

    async def _enqueue(
        self,
        payload,
        priority: str = "bulk",
        affinity: Optional[int] = None,
        tenant: Optional[str] = None,
    ) -> list[bool]:
        if not len(payload):
            return []
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        act = _trace_current()
        if act is not None:
            # queue-wait + dispatch as one span in the submitter's trace:
            # closed when the submission's future resolves, however it
            # resolves — per payload even when the packer slices it
            # across several lanes (ISSUE 10 trace satellite)
            tr = act[0]
            rec = tr.begin("verify.queue", act[1], items=len(payload))
            fut.add_done_callback(lambda _f, tr=tr, rec=rec: tr.end(rec))
        sub = Submission(payload, fut, act, priority, affinity=affinity,
                         tenant=tenant)
        if self._fleet is not None:
            # host-affine route (ISSUE 19): keyed submissions land in
            # their home host's packer; keyless work stays central
            self._fleet.push(sub)
        else:
            self._packer.push(sub)
        assert self._kick is not None, "engine not started"
        self._kick.set()
        return await fut

    # -- host-affine feed surface (ISSUE 19) ----------------------------------

    def route_host(self, key: int) -> Optional[str]:
        """The ACTIVE host an affinity key routes to right now (None
        without a fleet, or with every host dark) — upstream ingest
        sharding partitions parse/prep work by this."""
        if self._fleet is None:
            return None
        return self._fleet.affinity.route(key, self._fleet.active_hosts())

    def _feed_limit(self) -> int:
        """Per-host feed-depth ceiling for intake gating: the host's
        queue allowance plus one lane of headroom, in items."""
        return (self.cfg.fleet_queue + 1) * self._lane_target()

    def host_pressured(self, key: int) -> bool:
        """Is the TARGET host of ``key`` over its feed ceiling?  The
        per-host backpressure signal (ISSUE 19): intake for one slow
        host's keys defers without stalling the rest of the fleet.
        False without a fleet or with every host dark — callers fall
        back to their global gates."""
        if self._fleet is None:
            return False
        host = self._fleet.affinity.route(key, self._fleet.active_hosts())
        if host is None:
            return False
        return self._fleet.feed_depth(host) >= self._feed_limit()

    def hosts_all_pressured(self) -> bool:
        """Every ACTIVE host over its feed ceiling (the fleet-wide
        intake gate: one slow host alone must never trip it)."""
        if self._fleet is None:
            return False
        active = self._fleet.active_hosts()
        if not active:
            return False
        limit = self._feed_limit()
        return all(self._fleet.feed_depth(h) >= limit for h in active)

    def verify_sync(self, items: Sequence[VerifyItem]) -> list[bool]:
        """Blocking verification (benchmarks, scripts): no queueing."""
        return self._dispatch(list(items))

    def verify_raw_sync(self, raw) -> list[bool]:
        """Blocking raw-batch verification (benchmarks, scripts)."""
        return self._dispatch(as_raw_batch(raw))

    # -- internals -----------------------------------------------------------

    def _lane_target(self) -> int:
        """Pack/fill goal: the steady-state device shape once the device
        is up, the small shape before."""
        return (
            self.cfg.device_batch
            if self._device_state == "ready"
            else self.cfg.batch_size
        )

    def _uncut_pending(self) -> int:
        """Unclaimed queued items across every packer (fleet mode sums
        the central + per-host packers — ISSUE 19)."""
        if self._fleet is not None:
            return self._fleet.uncut_pending()
        return self._packer.pending()

    def _uncut(self):
        """Who holds the uncut work: the fleet's packers, or the one."""
        return self._fleet if self._fleet is not None else self._packer

    async def _run(self) -> None:
        """Pipeline scheduler loop: linger toward full lanes, then keep up
        to ``pipeline_depth`` packed lanes in flight (each in its own
        dispatch thread — lane N+1's host prep and transfer overlap lane
        N's kernel under JAX async dispatch).  In fleet mode the same
        linger feeds the work-stealing dispatcher instead: each cut lane
        is assigned to the shallowest active host queue, and the per-host
        workers (not this loop) own dispatch."""
        assert self._kick is not None and self._slots is not None
        while True:
            # The loop's three waits are spans (sched.starved / linger /
            # slot_wait): one of them is open whenever it is not cutting
            # a lane, so the device's idle time has a name on the
            # profiler's clock.  Nothing queued: upstream has not fed us.
            with span("sched.starved"):
                while not self._uncut_pending():
                    await self._kick.wait()
                    self._kick.clear()
            # Event-driven fill (VERDICT r4 weak #6 — the former 2 ms poll
            # burned ≤500 wakes/s per linger window): sleep until either a
            # new enqueue kicks, or the linger deadline passes.  Both the
            # deadline and the fill goal belong to the CLASS of what is
            # queued (sched.decide_lane, ISSUE 37): each class's oldest
            # submission may wait LINGER[class] x max_wait and the
            # earliest deadline cuts, so a remainder lingers for later
            # submissions to pack with only while its own class allows
            # (ISSUE 10: max-linger — a lone small batch still dispatches
            # promptly); ibd backfill, which nobody awaits block by block,
            # waits longer and is full at the small shape.
            with span("sched.linger"):
                while self._uncut_pending():
                    d = self._uncut().decide(
                        self._lane_target(), time.monotonic()
                    )
                    if d.cut is not None:
                        break
                    try:
                        await asyncio.wait_for(
                            self._kick.wait(), timeout=d.wait
                        )
                    except asyncio.TimeoutError:
                        break
                    self._kick.clear()
            if not self._uncut_pending():
                continue
            if self._fleet is not None:
                await self._feed_fleet()
                continue
            # admission: a free pipeline slot (more work keeps queueing —
            # and packing fuller lanes — while every slot is busy)
            await self._acquire_slot()
            lane = self._packer.cut(self._lane_target())
            if lane is None:
                self._slots.release()
                continue
            self._spawn_lane_task(lane)

    async def _acquire_slot(self) -> None:
        """Wait for a free pipeline slot: a lane could be cut, but
        ``pipeline_depth`` lanes are in flight."""
        assert self._slots is not None
        with span("sched.slot_wait"):
            await self._slots.acquire()

    def _spawn_lane_task(self, lane: PackedLane) -> None:
        """Spawn one locally-dispatched lane task (the caller holds a
        pipeline slot; _dispatch_lane releases it)."""
        task = spawn_supervised(
            self._dispatch_lane(lane), name="verify-lane", owner=self
        )
        self._lane_tasks.add(task)
        task.add_done_callback(self._lane_tasks.discard)

    async def _feed_fleet(self) -> None:
        """Cut ONE lane and hand it to the fleet (ISSUE 13, host-affine
        since ISSUE 19).  ``cut_next`` picks the globally most-urgent
        feedable source — an active host's HOME packer (lane lands on
        that host's own queue) or the central packer (lane lands on the
        shallowest queue) — so per-host packing preserves the global
        priority order.  Admission is a feedable source (shallow queues
        keep late high-priority submissions packing ahead of un-cut
        work); with every host lost, lanes are served through the LOCAL
        ladder under the ordinary pipeline slots — a fully-dark fleet
        still produces verdicts."""
        assert self._fleet is not None and self._room is not None
        assert self._slots is not None
        with span("sched.slot_wait"):  # every active host's queue is full
            while not self._fleet.feedable() and self._fleet.active_hosts():
                self._room.clear()
                await self._room.wait()
        if not self._fleet.active_hosts():
            # no active host at all: local fallback, traffic never stops
            lane = self._fleet.pop_any(self._lane_target())
            if lane is None:
                return
            await self._acquire_slot()
            self._spawn_lane_task(lane)
            return
        lane, host = self._fleet.cut_next(self._lane_target())
        if lane is None:
            return
        if host is None:
            # cut from the central packer but no queue had room (raced
            # with other cuts): serve locally rather than re-queueing —
            # the lane exists now and must resolve exactly once
            await self._acquire_slot()
            self._spawn_lane_task(lane)
            return
        self._wake_fleet()

    def _wake_fleet(self) -> None:
        """Wake every host worker (a new/re-queued lane may be stolen by
        ANY idle host, not just the one it was assigned to)."""
        for hs in self._hosts.values():
            if hs.event is not None:
                hs.event.set()

    async def _host_worker(self, hs: _HostState) -> None:
        """One host's dispatch worker (``pipeline_depth`` of these run
        per host): pull lanes — own queue first, then steal from the
        deepest peer — and dispatch them over this host's sub-mesh with
        this host's breaker.  A lost host's workers pace the canary
        rejoin instead of pulling work."""
        assert self._fleet is not None and self._room is not None
        while True:
            if hs.lost:
                # cooldown-paced rejoin, anchored on the LOSS time (not
                # on when this worker noticed — several workers share
                # one host): after breaker_cooldown the host re-enters
                # the active set with its breaker open — the next lane
                # a worker takes is the half-open canary, and a
                # still-dead host just gets deactivated again.
                remain = (
                    hs.lost_at + self.cfg.breaker_cooldown
                    - time.monotonic()
                )
                await asyncio.sleep(max(0.01, remain))
                if hs.lost:
                    self._host_rejoin(hs)
                continue
            lane = self._fleet.take(hs.name)
            if lane is None:
                self._room.set()
                assert hs.event is not None
                await hs.event.wait()
                hs.event.clear()
                continue
            self._room.set()
            await self._dispatch_lane(lane, host=hs, slot=False)

    async def _dispatch_lane(
        self,
        lane: PackedLane,
        host: Optional[_HostState] = None,
        slot: bool = True,
    ) -> None:
        """Run one packed lane end to end: dispatch in a worker thread
        (the ladder/breaker/failover semantics of :meth:`_run_ladder`
        apply per in-flight lane), then deliver each slice's verdicts to
        its submission.  A lane that fails on every rung fails exactly
        the submissions it carries slices of.

        Fleet mode (``host`` set): the lane runs with that host's
        breaker and sub-mesh; a :class:`HostLost` deactivates the host
        and RE-QUEUES the lane onto a healthy peer — exactly once, since
        nothing was delivered and the lane now lives in exactly one peer
        queue.  A lane that has already bounced through every host (or
        finds no healthy peer) falls through the LOCAL cpu ladder so its
        waiters still resolve."""
        assert self._kick is not None and self._slots is not None
        # verify.lane is the lane's life on the loop side, entry to last
        # delivery; less its children verify.dispatch (worker thread) and
        # verify.deliver, what is left is the two thread hops — where a
        # busy loop or a held GIL shows first.  Every end of a lane but a
        # re-queue is one verify.deliver: verdicts, failure or cancel.
        with span("verify.lane"):
            payloads = lane.payloads()
            total = lane.total
            metrics.inc("verify.batches")
            metrics.inc("verify.items", total)
            with self._inflight_lock:
                self._inflight_seq += 1
                token = self._inflight_seq
                self._inflight[token] = time.monotonic()
            try:
                classes = lane.class_counts()
                tenants = lane.tenant_counts()
                try:
                    results = await asyncio.to_thread(
                        self._dispatch_traced, payloads, lane.target,
                        lane.act0, host, None, classes, tenants,
                    )
                except HostLost as e:
                    assert host is not None and self._fleet is not None
                    self._host_down(host, str(e))
                    if (
                        lane.requeues < len(self._hosts)
                        and self._fleet.requeue(host.name, lane) is not None
                    ):
                        self._wake_fleet()
                        return
                    # no healthy peer (or the lane is orbiting dying
                    # hosts): serve it locally, skipping the device rungs
                    # entirely
                    results = await asyncio.to_thread(
                        self._dispatch_traced, payloads, lane.target,
                        lane.act0, None,
                        "cpu" if self._cpu is not None else "oracle",
                        classes, tenants,
                    )
            except asyncio.CancelledError:
                # engine teardown mid-dispatch: waiters must not hang on
                # a future nobody will resolve
                with span("verify.deliver", cpu=True):
                    for sub, _, _ in lane.slices:
                        if not sub.fut.done():
                            sub.fut.cancel()
                raise
            except Exception as e:  # all rungs failed: the waiters learn it
                log.error("[Engine] lane of %d failed: %s", total, e)
                with span("verify.deliver", cpu=True):
                    for sub, _, _ in lane.slices:
                        sub.fail(e)
                return
            finally:
                with self._inflight_lock:
                    self._inflight.pop(token, None)
                if slot:
                    self._slots.release()
                if self._room is not None:
                    self._room.set()
                self._kick.set()  # a freed slot may unblock the scheduler
            with span("verify.deliver", cpu=True):
                pos = 0
                for sub, lo, hi in lane.slices:
                    sub.deliver(lo, results[pos : pos + (hi - lo)])
                    pos += hi - lo

    def _dispatch(self, payload) -> list[bool]:
        """Pick an execution engine and run one payload (worker thread)."""
        return self._dispatch_multi([payload])

    def _dispatch_traced(
        self,
        payloads: list,
        target: Optional[int],
        act: Optional[tuple],
        host: Optional[_HostState] = None,
        backend: Optional[str] = None,
        classes: Optional[dict] = None,
        tenants: Optional[dict] = None,
    ) -> list[bool]:
        """Worker-thread entry: re-activate the submitting item's trace
        (contextvars do not cross ``to_thread`` from the queue loop — the
        loop's own context has no trace) so the dispatch/prepare/transfer/
        kernel/readback spans land in the item's pipeline tree.
        ``classes`` (the lane's per-priority item counts) and ``tenants``
        (per-tenant counts, serve mode) ride a thread-local into
        _dispatch_multi's ledger charge — this IS the dispatch thread."""
        self._tls.classes = classes
        self._tls.tenants = tenants
        _count_algorithms(payloads)
        try:
            with _activate_trace(act):
                if host is None and backend is None:
                    # keep the 2-arg call shape: tests (and subclasses)
                    # spy on _dispatch_multi with (payloads, target)
                    # signatures
                    return self._dispatch_multi(payloads, target)
                return self._dispatch_multi(
                    payloads, target, host=host, backend=backend
                )
        finally:
            self._tls.classes = None
            self._tls.tenants = None

    def _pick(self, n: int, host: Optional[_HostState] = None) -> str:
        """Resolve the starting backend rung for one batch.  Never blocks
        except for the forced-tpu backend, which waits (bounded) for
        warmup.  The device path additionally passes through the circuit
        breaker — the HOST's own breaker in fleet mode, so one sick
        host degrades alone: open = cpu, one canary batch while probing."""
        backend = self.cfg.backend
        if (
            backend in ("auto", "tpu")
            and self._device_state == "failed"
            and self.cfg.warmup_retry > 0
        ):
            self._retry_warmup()  # no-op until the retry interval elapses
        if backend == "tpu":
            if self._device_state == "cold":  # cfg.warmup=False: warm lazily
                self.start_warmup()
            if self._device_state == "warming":
                remain = self.cfg.warmup_timeout - (
                    time.monotonic() - self._warmup_started
                )
                self._warmup_done.wait(timeout=max(0.0, remain))
            if self._device_state != "ready":
                raise RuntimeError(
                    "tpu backend unavailable: "
                    + (self._device_error or "warmup timed out")
                )
            return "tpu"
        if backend != "auto":
            return backend
        breaker = host.breaker if host is not None else self._breaker
        if (
            n >= self.cfg.min_tpu_batch
            and self._device_state == "ready"
            and breaker.allow_device()
        ):
            return "tpu"
        if (
            self._device_state == "warming"
            and not self._slow_logged
            and time.monotonic() - self._warmup_started > 30.0
        ):
            self._slow_logged = True
            log.info("[Engine] device warmup still running; batches on cpu")
        return "cpu" if self._cpu is not None else "oracle"

    # Linear occupancy buckets (0.05 steps) shared with the packer's
    # sched.pack_efficiency histogram so the two stay comparable.
    OCCUPANCY_BUCKETS = _OCCUPANCY_BUCKETS

    def _dispatch_multi(
        self,
        payloads: list,
        target: Optional[int] = None,
        host: Optional[_HostState] = None,
        backend: Optional[str] = None,
    ) -> list[bool]:
        """Verify a coalesced batch of payloads (tuple lists and/or raw
        batches) on one backend; results are in payload order.  ``target``
        is the fill goal the queue lingered for (None on the synchronous
        paths) — it sizes the occupancy observation.  ``host`` routes the
        batch through that fleet host's breaker and sub-mesh (ISSUE 13);
        ``backend`` forces the starting rung (the fleet's local-fallback
        path pins "cpu" so a dark fleet never re-enters device picks)."""
        with span("verify.dispatch", cpu=True):
            total = sum(len(p) for p in payloads)
            occupancy = total / target if target else None
            if occupancy is not None:
                metrics.observe(
                    "verify.occupancy",
                    min(1.0, occupancy),
                    buckets=self.OCCUPANCY_BUCKETS,
                )
            picked = backend or self._pick(total, host)
            t0 = time.perf_counter()
            out, served = self._run_ladder(picked, payloads, total, host)
            dt = time.perf_counter() - t0
            # Ledger charge (ISSUE 17): the ONE measured rung time is cut
            # across the lane's carried classes; the sync/no-lane paths
            # (verify_sync, warmup canaries) have no class counts and
            # charge to "bulk".
            classes = getattr(self._tls, "classes", None)
            self._ledger.charge(
                classes if classes else {"bulk": total}, total, dt, served,
                host=host.name if host is not None else None,
                tenants=getattr(self._tls, "tenants", None),
            )
            # the rung that actually served the latest batch: what a
            # verdict receipt binds (ISSUE 20) — best-effort under
            # concurrency, exact in the serve bench's cpu-proxy shape
            self._last_rung = served
            events.emit(
                "verify.dispatch", backend=served, size=total,
                occupancy=round(occupancy, 4) if occupancy is not None else None,
                seconds=round(dt, 6),
                **({"host": host.name} if host is not None else {}),
            )
            return out

    # Failover order (ISSUE 7): each rung is strictly more available and
    # strictly slower than the one above it; the python oracle cannot
    # fail for device/native reasons, so transient faults never surface
    # to waiters as exceptions.
    _LADDER = ("tpu", "cpu", "oracle")

    def _run_ladder(
        self,
        backend: str,
        payloads: list,
        total: int,
        host: Optional[_HostState] = None,
    ) -> tuple[list[bool], str]:
        """Run one coalesced batch starting at ``backend``, re-dispatching
        the SAME batch down the ladder on failure.  Device-rung outcomes
        feed the circuit breaker (the HOST's in fleet mode).  Returns
        (results, rung that served).  Only a batch that fails on every
        rung raises — and then fails just this batch's waiters; the
        queue loop survives (pinned by tests/test_engine.py).  A forced
        ``backend="tpu"`` has one rung.

        Fleet specifics (ISSUE 13): a host partition
        (:class:`HostLost` / injected ``mesh.dispatch:partition``)
        escapes the ladder immediately — the host's CPU is as gone as
        its chips, so laddering down locally would serve a dead host's
        lane; the worker re-queues it instead.  A device LOSS on a
        multi-chip host additionally shrinks its sub-mesh to the largest
        still-healthy half before the ladder re-serves the batch on cpu;
        a successful canary re-grows it."""
        breaker = host.breaker if host is not None else self._breaker
        start = self._LADDER.index(backend) if backend in self._LADDER else 0
        rungs = [
            r
            for r in self._LADDER[start:]
            if r != "cpu" or self._cpu is not None
        ]
        if backend == "tpu" and self.cfg.backend == "tpu":
            # forced tpu means tpu: no rung stands in for the chip, the
            # device error reaches the waiters
            rungs = ["tpu"]
        for i, rung in enumerate(rungs):
            try:
                if chaos.on:  # injected batch/device failure (ISSUE 7/13)
                    if host is not None:
                        chaos.maybe_raise(
                            "mesh.dispatch",
                            f"{host.name}:{rung}:chips{host.chips}",
                        )
                    chaos.maybe_raise("engine.dispatch", rung)
                # 3-arg call shape kept when hostless: tests (and
                # subclasses) wrap _run_backend with (rung, payloads,
                # total) signatures
                out = (
                    self._run_backend(rung, payloads, total)
                    if host is None
                    else self._run_backend(rung, payloads, total, host)
                )
            except HostLost:
                raise
            except ChaosPartition as e:
                raise HostLost(str(e)) from e
            except Exception as e:
                err = f"{type(e).__name__}: {e}"[:300]
                metrics.inc("verify.dispatch_errors")
                events.emit(
                    "verify.failure", where="dispatch", backend=rung,
                    size=total, error=err,
                    **({"host": host.name} if host is not None else {}),
                )
                if rung == "tpu":
                    breaker.record_failure(err)
                    if host is not None:
                        # ANY device-rung failure on a multi-chip fleet
                        # host probes the smaller sub-mesh — real device
                        # losses surface as assorted XLA runtime errors
                        # that cannot be reliably classified (review
                        # r13: keying on ChaosDeviceLoss alone left real
                        # hardware pinned at CPU speed).  A wrong shrink
                        # self-heals via the cooldown-paced re-grow; a
                        # missed one parks the host on the cpu rung.
                        self._host_shrink(host)
                if i + 1 >= len(rungs):
                    raise  # every rung failed: the waiters learn it
                metrics.inc("verify.failovers")
                events.emit(
                    "verify.failover", source=rung, target=rungs[i + 1],
                    size=total, error=err,
                )
                log.warning(
                    "[Engine] batch of %d failed on %s, retrying on %s: %s",
                    total, rung, rungs[i + 1], err,
                )
                continue
            if rung == "tpu":
                closed = breaker.record_success()
                if host is not None and (
                    closed
                    or (
                        # Re-grow is NOT gated on a full breaker
                        # open/close cycle (review r13: a single device
                        # loss shrinks from 'degraded', which closes
                        # with closed=False — the host would run at
                        # half capacity forever): any device success on
                        # a shrunken host re-probes the full row once
                        # per breaker cooldown; a repeat loss just
                        # shrinks again.
                        0 < host.chips < host.full_chips
                        and time.monotonic() - host.shrunk_at
                        >= self.cfg.breaker_cooldown
                    )
                ):
                    self._host_regrow(host)
            return out, rung
        raise RuntimeError("no verify backend available")  # unreachable

    def _run_backend(
        self,
        rung: str,
        payloads: list,
        total: int,
        host: Optional[_HostState] = None,
    ) -> list[bool]:
        """Execute one ladder rung over the coalesced payloads."""
        if rung == "tpu":
            # counts tpu/cpu items per chunk
            return self._run_tpu(payloads, host)
        if rung == "cpu" and self._cpu is not None:
            out = self._cpu.verify_raw(
                concat_raw([as_raw_batch(p) for p in payloads]),
                nthreads=self.cfg.cpu_threads,
            )
            metrics.inc("verify.cpu_items", total)
            return out
        out = []
        for p in payloads:
            out.extend(
                verify_batch_cpu(
                    p if isinstance(p, list) else as_raw_batch(p).to_tuples()
                )
            )
        metrics.inc("verify.oracle_items", total)
        return out

    def _mesh_device_count(self) -> int:
        """How many devices the configured mesh spans: ``mesh_devices``
        when set — fewer visible is an error, never a smaller mesh — else
        every visible device."""
        import jax

        want, seen = self.cfg.mesh_devices, len(jax.devices())
        if seen < want:
            raise RuntimeError(
                f"mesh_devices={want} but only {seen} device(s) visible"
            )
        return want or seen

    def _mesh(self):
        """Lazily-built device mesh for the sharded tpu rung (ISSUE 10):
        None when ``mesh_devices`` is off.  A mesh that was asked for and
        cannot be built raises — it never degrades to one chip.
        Thread-safe: concurrent lanes race to be the first dispatch."""
        if self.cfg.mesh_devices < 2:
            return None
        with self._mesh_lock:
            if self._mesh_obj is None:
                from .multichip import make_mesh

                n = self._mesh_device_count()
                self._mesh_obj = make_mesh(n)
                events.emit("verify.mesh", state="ready", devices=n)
            return self._mesh_obj

    # -- fleet host health / sub-meshes (ISSUE 13) ---------------------------

    def _host_down(self, hs: _HostState, error: str) -> None:
        """Deactivate a lost host: trip its breaker (instant open — the
        cooldown/canary recovery machinery applies unchanged), move its
        queued lanes to active peers, and wake the fleet.  Idempotent —
        concurrent lanes observing the same partition deactivate once."""
        assert self._fleet is not None
        if hs.lost:
            return
        hs.lost = True
        hs.lost_at = time.monotonic()
        hs.breaker.trip(error[:300])
        moved = self._fleet.deactivate(hs.name)
        active = len(self._fleet.active_hosts())
        metrics.inc("mesh.host_losses")
        metrics.set_gauge("mesh.active_hosts", float(active))
        events.emit(
            "mesh.host_down", host=hs.name, error=error[:200],
            requeued_lanes=moved, active_hosts=active,
        )
        log.warning(
            "[Engine] fleet host %s lost (%d active): %s",
            hs.name, active, error,
        )
        self._wake_fleet()
        if self._room is not None:
            self._room.set()

    def _host_rejoin(self, hs: _HostState) -> None:
        """Cooldown elapsed: the host re-enters the active set with its
        breaker open — the first lane it takes is the half-open canary
        (success closes the breaker and re-grows the sub-mesh; a
        still-dead host is deactivated again by the next HostLost)."""
        assert self._fleet is not None
        hs.lost = False
        self._fleet.activate(hs.name)
        active = len(self._fleet.active_hosts())
        metrics.set_gauge("mesh.active_hosts", float(active))
        events.emit("mesh.host_up", host=hs.name, active_hosts=active,
                    probing=True)
        self._wake_fleet()
        if self._room is not None:
            self._room.set()

    def _host_shrink(self, hs: _HostState) -> None:
        """Device loss on a multi-chip host: rebuild its sub-mesh as the
        largest still-healthy half (8→4→2→1 chips) instead of failing
        soft to single-chip in one step.  The failed batch itself is
        re-served by the ladder's cpu rung; later lanes use the smaller
        mesh."""
        with self._mesh_lock:
            if not hs.full_chips:
                # the loss can precede the first sub-mesh build (chips
                # still 0): resolve this host's row width so there is a
                # known-good whole to halve
                hybrid = self._fleet_hybrid_mesh()
                hs.full_chips = int(hybrid.devices.shape[-1])
                hs.chips = hs.full_chips
            if hs.chips <= 1:
                return
            hs.chips //= 2
            hs.shrunk_at = time.monotonic()
            hs.mesh = None  # rebuilt lazily at the new width
            chips = hs.chips
        metrics.inc("mesh.shrinks")
        self._chips_gauge(hs.name, chips)
        events.emit("mesh.shrink", host=hs.name, chips=chips)
        log.warning(
            "[Engine] host %s sub-mesh shrunk to %d chip(s)", hs.name, chips
        )

    def _host_regrow(self, hs: _HostState) -> None:
        """Restore the host's full device row — on a breaker canary
        close, or (review r13) on any device success once a breaker
        cooldown has passed since the shrink, so a loss that never
        opened the breaker (degraded at the default threshold) cannot
        pin the host at reduced width forever.  The chips that caused
        the shrink get re-probed by ordinary traffic — a repeat loss
        just shrinks again, at most once per cooldown."""
        with self._mesh_lock:
            if not hs.full_chips or hs.chips >= hs.full_chips:
                return
            hs.chips = hs.full_chips
            hs.mesh = None
            chips = hs.chips
        metrics.inc("mesh.regrows")
        self._chips_gauge(hs.name, chips)
        events.emit("mesh.regrow", host=hs.name, chips=chips)
        log.info(
            "[Engine] host %s sub-mesh re-grown to %d chip(s)", hs.name, chips
        )

    @staticmethod
    def _chips_gauge(host: str, chips: int) -> None:
        # per-host sub-mesh width as a labeled gauge: the fleet timeline
        # (tpunode/timeseries.py) samples it, so an 8→4→8 shrink/regrow
        # is reconstructible after the fact
        metrics.set_gauge(
            "mesh.host_chips", float(chips), labels={"host": host}
        )

    def _fleet_hybrid_mesh(self):
        """The fleet's (host, chip) hybrid mesh, carved lazily on first
        device dispatch.  Caller holds ``_mesh_lock``.  Raises when the
        requested grid cannot be built from the visible devices — fleet
        hosts never silently share one chip."""
        if self._fleet_hybrid is None:
            from .multichip import make_hybrid_mesh

            hosts = self.cfg.mesh_hosts
            chips = max(1, self._mesh_device_count() // hosts)
            self._fleet_hybrid = make_hybrid_mesh(hosts, chips)
            events.emit(
                "verify.mesh", state="ready", hosts=hosts,
                chips_per_host=chips,
            )
        return self._fleet_hybrid

    def _host_mesh(self, hs: _HostState):
        """This host's 1-D device sub-mesh at its current healthy width
        (its hybrid-mesh row via :func:`multichip.host_submesh`).
        Thread-safe: dispatch worker threads race on first build and
        after shrink/re-grow."""
        with self._mesh_lock:
            if hs.mesh is None:
                from .multichip import host_submesh

                hybrid = self._fleet_hybrid_mesh()
                if not hs.full_chips:
                    hs.full_chips = int(hybrid.devices.shape[-1])
                    hs.chips = hs.full_chips
                hs.mesh = host_submesh(hybrid, hs.index, chips=hs.chips)
                self._chips_gauge(hs.name, hs.chips)
            return hs.mesh

    def _dispatch_chunk(self, chunk, pad_to: int,
                        host: Optional[_HostState] = None):
        """Async device dispatch of one fixed-shape chunk: sharded over
        the host's sub-mesh in fleet mode, the local mesh when
        configured, single-chip otherwise.  Returns the (device array,
        count) handle for :func:`collect_verdicts`."""
        mesh = self._host_mesh(host) if host is not None else self._mesh()
        if mesh is not None:
            from .multichip import dispatch_raw_sharded

            return dispatch_raw_sharded(chunk, mesh, pad_to=pad_to)
        from .kernel import dispatch_batch_tpu_raw

        return dispatch_batch_tpu_raw(chunk, pad_to=pad_to)

    def _run_tpu(
        self, payloads: list, host: Optional[_HostState] = None
    ) -> list[bool]:
        """Device dispatch in fixed-size chunks: every call is one of the
        two shapes the warmup compiled (``device_batch`` steady-state,
        ``batch_size`` for small tails) — no surprise recompiles on the hot
        path.  Dispatch is pipelined at two levels: chunk N+1 is
        host-prepped while chunk N runs on the device (JAX async
        dispatch), and whole lanes overlap via ``pipeline_depth`` worker
        threads.  The packer keeps remainders queued for later
        submissions; ``min_tpu_batch`` is the shed-only floor applied
        when a lingered partial lane finally lands here (forced-tpu
        backend excepted)."""
        from .kernel import collect_verdicts

        raw = concat_raw([as_raw_batch(p) for p in payloads])
        B = self.cfg.device_batch
        # (device array, count) handles | list[bool] (cpu-shed tails)
        pending: list = []
        for i in range(0, len(raw), B):
            chunk = raw.slice(i, i + B)
            if (
                len(chunk) < self.cfg.min_tpu_batch
                and self.cfg.backend != "tpu"
                and self._cpu is not None
            ):
                pending.append(self._cpu.verify_raw(chunk))
                metrics.inc("verify.cpu_items", len(chunk))
            else:
                # small tails take the small compiled shape, not a mostly
                # empty device_batch step
                pad = B if len(chunk) > self.cfg.batch_size else self.cfg.batch_size
                pending.append(
                    self._dispatch_chunk(chunk, pad_to=pad, host=host)
                )
                metrics.inc("verify.tpu_items", len(chunk))
                metrics.inc("verify.tpu_slots", pad)
        out: list[bool] = []
        for p in pending:
            out.extend(p if isinstance(p, list) else collect_verdicts(*p))
        return out
