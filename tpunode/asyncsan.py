"""asyncsan runtime sanitizers: what the static rules can't see.

The static half (tpunode/analysis) catches the hazard *patterns*; this
module catches the hazard *instances* that only exist at runtime:

* **Loop debug mode** — :func:`install` (gated behind the
  ``TPUNODE_ASYNCSAN`` env var via :func:`enabled`) switches the running
  event loop into asyncio debug mode with a tight
  ``slow_callback_duration`` (``TPUNODE_ASYNCSAN_SLOW``, default 0.1s),
  so any callback that holds the loop logs itself with source location.
  Node and the test harness (tests/conftest.py) both wire it.
* **The loop's clock** — :class:`LoopAttributor` (always on: the node
  starts one whenever its watchdog runs, env gate or not) wraps the
  loop's selector ``select`` with two clock reads, so the loop accounts
  for its own time: idle in the selector, and each iteration's busy
  length.  A sampling daemon thread watches those stamps; when the loop
  has been out of ``select`` for ``threshold`` (50 ms) it captures the
  loop thread's CURRENT Python stack via ``sys._current_frames`` and
  names the layer it is in (:data:`WHERE`).  When the iteration ends the
  loop records the hold whole: the ``loop.hold`` span, the
  ``loop.hold_seconds{where=}`` counter and a ``loop.hold`` event with
  the frames.  The stall watchdog (tpunode/watchdog.py) attaches the
  same frames to its ``watchdog.stall`` event — upgrading "the loop
  stalled" to "the loop stalled HERE".  Beside the clock, read only when
  somebody reads the registry (``Metrics.on_collect``): the loop
  thread's CPU time, the process's CPU time by thread role
  (:data:`ROLES`), and the collector's pauses by generation.
* **Task-leak reporting** rides the supervision registry in
  tpunode/actors.py (``spawn_supervised`` / ``task_registry``): leaks
  surface as ``asyncsan.task_leak`` events at node shutdown regardless
  of this env gate — reporting is cheap; only the debug/attributor
  machinery is opt-in.

Everything here is jax-free (pinned by tests/test_metrics.py) and, but
for the package's own registry, event log and span helpers, stdlib-only:
the sanitizers must load in the bench driver and any CI box.
"""

from __future__ import annotations

import asyncio
import gc
import logging
import os
import resource
import sys
import threading
import time
from typing import Optional

from . import threadsan, trace
from .events import EventLog, events
from .metrics import metrics

__all__ = [
    "enabled",
    "install",
    "slow_callback_duration",
    "LoopAttributor",
    "SLOW_CALLBACK_DURATION",
    "WHERE",
    "ROLES",
    "where_of",
]

log = logging.getLogger("tpunode.asyncsan")

#: Default slow-callback threshold (``TPUNODE_ASYNCSAN_SLOW`` overrides).
SLOW_CALLBACK_DURATION = 0.1


def enabled() -> bool:
    """True iff the opt-in ``TPUNODE_ASYNCSAN`` env var is set truthy."""
    return os.environ.get("TPUNODE_ASYNCSAN", "") not in ("", "0", "false", "no")


def slow_callback_duration() -> float:
    """The configured slow-callback threshold — read from the environment
    at call time (like :func:`enabled`), so tests and embedders can set
    ``TPUNODE_ASYNCSAN_SLOW`` after import."""
    try:
        return float(
            os.environ.get("TPUNODE_ASYNCSAN_SLOW", SLOW_CALLBACK_DURATION)
        )
    except ValueError:
        return SLOW_CALLBACK_DURATION


def install(loop: Optional[asyncio.AbstractEventLoop] = None) -> None:
    """Wire asyncio debug mode + slow-callback reporting into ``loop``
    (default: the running loop).  Idempotent; call only when
    :func:`enabled` — debug mode adds per-callback overhead."""
    if loop is None:
        loop = asyncio.get_running_loop()
    loop.set_debug(True)
    loop.slow_callback_duration = slow_callback_duration()
    log.info(
        "[asyncsan] loop debug mode on (slow_callback_duration=%.3fs)",
        loop.slow_callback_duration,
    )


# What a hold of the loop is filed under: the layer of the innermost frame
# of the callback the loop is running (the frames inside ``_run_once``)
# that lies in this package, in the benchmark's harness or in the tests;
# ``asyncio`` where the loop's own machinery is all there is (its self-pipe,
# a transport's read: the loop thread is then mostly waiting for the GIL);
# ``other`` where the sampler never got to look (a hold a few milliseconds
# over the threshold, or the GIL held in C throughout);
# ``gc`` where the collector ran, in any thread, for over half of the hold.
# A closed set (the ``where=`` label of ``loop.hold_seconds``): a module
# not listed here reads ``other`` until it is given a line, never a label
# of its own making.
_TELEMETRY = ("blackbox", "debugsrv", "events", "metrics", "slo",
              "timeseries", "trace", "tracectx", "watchdog")
_MODULE_WHERE = {
    "actors": "actors",
    # this module's frame on the loop thread is the selector wrap: a hold
    # there is a poll that waited for the GIL, the loop's own machinery
    "asyncsan": "asyncio",
    "chain": "chain", "headers": "chain",
    "ibd": "ibd",
    "mempool": "mempool", "seenlru": "mempool",
    "node": "node",
    "peer": "peer", "peermgr": "peer", "wire": "peer",
    "receipts": "serve", "serve": "serve",
    "store": "store",
    "txextract": "txextract", "native": "txextract",
    "utxo": "utxo",
    "sighash": "verify", "txverify": "verify",
    "verify/engine": "engine",
    "verify/sched": "sched",
    **dict.fromkeys(_TELEMETRY, "telemetry"),
}
# lock wrappers and helpers: a hold inside one belongs to its caller
_TRANSPARENT = frozenset({"threadsan", "util"})
WHERE = tuple(sorted({*_MODULE_WHERE.values(), "verify", "harness", "test",
                      "asyncio", "gc", "other"}))

# Whose CPU time the process's is (the ``role=`` label of ``cpu.seconds``),
# by thread: the loop's, the extraction pool's, asyncio's default executor's
# (the engine's dispatch ``to_thread`` calls, the UTXO connect on the
# fallback), the store's group-commit writer's, every other Python
# thread's, and what is left of the process's: threads Python did not
# start, i.e. the XLA / PJRT / libtpu runtime's.
ROLES = ("loop", "extract", "executor", "store", "python_other", "runtime")
_ROLE_PREFIXES = (("extract", "extract"), ("asyncio_", "executor"),
                  ("logkv-commit", "store"))


def where_of(filename: str) -> Optional[str]:
    """The :data:`WHERE` label of a code file, or None for one that is
    nobody's (the standard library, a dependency) or transparent."""
    path = filename.replace(os.sep, "/")
    i = path.rfind("/tpunode/")
    if i >= 0:
        module = path[i + len("/tpunode/"):-len(".py")]
        if module in _TRANSPARENT:
            return None
        if module.startswith("verify/"):
            return _MODULE_WHERE.get(module, "verify")
        return _MODULE_WHERE.get(module, "other")
    if "/chipbench/" in path:
        return "harness"
    if "/tests/" in path:
        return "test"
    return None


class LoopAttributor:
    """The loop's clock: idle time, holds by name, and the CPU beside them.

    :meth:`start` (on the loop thread) shadows the loop's selector
    ``select`` with :meth:`_select`: two ``perf_counter`` reads round the
    real call add up the seconds the loop was blocked in it and its
    iterations, in plain attributes, and stamp when the loop came out
    (``_out_since``; 0 while it is inside).  That stamp is the beat: a
    daemon sampler thread looks at it every ``interval`` seconds, and
    when the loop has been out of ``select`` for ``threshold`` it
    snapshots the loop thread's stack, once an iteration — the snapshot
    taken *during* a hold is exactly the code that holds, information
    that is gone by the time anything on the loop could look.  The loop
    side, at its next ``select``, records the iteration's whole busy
    length under the label the sampler found (``gc`` where the collector
    ran, in whichever thread, for over half of it: a collection holds the
    GIL, so the loop waits and the sampler cannot look).  A loop without
    ``_selector`` (uvloop, Proactor) reads nothing.

    What idle means: seconds in a ``select`` that was given time to wait.
    A poll (``select(0)``: callbacks are ready) is not idle, whatever it
    took — it takes long only when another thread has the GIL when it
    returns.  After a real wait the same can happen, and that stretch, up
    to the interpreter's switch interval a wake unless a thread holds the
    GIL in C, is counted as idle: Python cannot see it.

    :meth:`collect` is registered with ``metrics.on_collect``: it flushes
    the accumulators into the registry and reads the CPU clocks.  A
    thread that ends between two collects loses what it burned since the
    last one to ``runtime`` (pools live through a benchmark's window).
    Consumers of the captures read :meth:`last_blocked`.
    """

    def __init__(
        self,
        threshold: float = 0.05,
        interval: float = 0.05,
        max_frames: int = 12,
        long_hold: float = 0.2,
        log_: Optional[EventLog] = None,
    ):
        self.threshold = threshold
        self.interval = interval
        self.max_frames = max_frames
        self.long_hold = long_hold
        self.log = log_ if log_ is not None else events
        self._loop_thread_id: Optional[int] = None
        self._selector = None  # the wrapped selector, while wrapped
        self._inner_select = None
        self._stopped = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # the loop's side: written by the loop thread only
        self._out_since = 0.0  # perf_counter at select's return; 0 inside
        self._idle = 0.0  # seconds blocked in select
        self._iters = 0
        self._event_at = -float("inf")  # last loop.hold event
        self._events_suppressed = 0
        # the sampler's find for the iteration that began at [0]:
        # (out_since, where, frames, annotation or None)
        self._hold: Optional[tuple] = None
        # gc.callbacks entry: written by whichever thread collects (one at
        # a time: a collection holds the GIL)
        self._gc_t0 = 0.0  # when the collection that runs now began, else 0
        self._gc_pause = [0.0, 0.0, 0.0]
        self._gc_count = [0, 0, 0]
        self._gc_iter = (0.0, 0.0)  # (iteration's out_since, seconds in it)
        # collect(): what has been flushed, under its own lock (two readers
        # of the registry may collect at once)
        self._flush_lock = threadsan.lock("asyncsan.collect")
        # (idle, iterations, gc pauses, gc counts) as of the last flush
        self._flushed = (0.0, 0, (0.0, 0.0, 0.0), (0, 0, 0))
        self._threads: dict = {}  # native id -> [clock id, role, last read]
        self._process_cpu = 0.0
        # newest capture: {"age_seconds", "frames", "captured_at"}
        self._last: Optional[dict] = None

    # -- lifecycle (call from the loop thread) -------------------------------

    def start(self, loop: Optional[asyncio.AbstractEventLoop] = None) -> None:
        if self._thread is not None:
            return
        if loop is None:
            loop = asyncio.get_running_loop()
        self._loop_thread_id = threading.get_ident()
        sel = getattr(loop, "_selector", None)
        inner = getattr(sel, "select", None)
        if inner is None or isinstance(
            getattr(inner, "__self__", None), LoopAttributor
        ):
            # no selector to time, or another node's clock is on this
            # loop already (one beat a loop): this one reads nothing
            log.info("[asyncsan] %s: no selector of its own to time, the "
                     "loop's clock reads nothing", type(loop).__name__)
            return
        self._inner_select = inner
        self._selector = sel
        self._out_since = time.perf_counter()
        sel.select = self._select
        # first in the list: a callback before this one may give the GIL up
        # (jax's does, in its "stop"), and the loop would then end a hold
        # before this one had put the collection on the books
        gc.callbacks.insert(0, self._on_gc)
        with self._flush_lock:
            self._read_cpu()  # the baseline: nothing before now counts
        metrics.on_collect(self.collect)
        self._thread = threading.Thread(
            target=self._sample_loop, name="asyncsan-attributor", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stopped.set()
        if self._thread is not None:
            self._thread.join(timeout=1.0)
            self._thread = None
        sel = self._selector
        if sel is None:
            return
        self.collect()
        self._selector = None
        if vars(sel).get("select") == self._select:
            del sel.select  # the class's own method shows again
        gc.callbacks.remove(self._on_gc)
        self._drop_find(self._hold)
        self._hold = None

    # -- loop side -----------------------------------------------------------

    def _select(self, timeout=None):
        t0 = time.perf_counter()
        busy = t0 - self._out_since
        if busy >= self.threshold:
            self._end_hold(busy)
            t0 = time.perf_counter()
        # a poll (select(0): callbacks are ready) is not a wait: through it
        # the sampler and the gc callback go on seeing the iteration, and
        # one that takes long (the GIL was taken meanwhile) is a hold
        if timeout != 0:
            self._out_since = 0.0
        try:
            return self._inner_select(timeout)
        finally:
            t1 = time.perf_counter()
            if timeout != 0:
                self._idle += t1 - t0
            elif t1 - t0 >= self.threshold:
                self._end_hold(t1 - t0)
            self._iters += 1
            self._out_since = t1

    def _end_hold(self, busy: float) -> None:
        """The iteration that is ending (or the poll after it) held the
        loop for ``busy`` seconds: record it whole, under the sampler's
        label."""
        began = self._out_since
        where, frames = "other", []
        find = self._hold
        if find is not None:
            # the find stays for the poll that may follow the iteration
            # (same stamp); its annotation ends with the first hold
            if find[0] == began:
                where, frames = find[1], find[2]
            if find[3] is not None:
                self._hold = find[:3] + (None,)
                self._drop_find(find)
        gc_began, gc_seconds = self._gc_iter
        if gc_began == began and gc_seconds > 0.5 * busy:
            where = "gc"
        trace.record_span("loop.hold", busy)
        counts = [("loop.hold_seconds", busy, {"where": where})]
        if busy >= self.long_hold:
            counts.append(("loop.holds_long", 1.0, None))
        metrics.inc_batch(counts)
        # the event is for eyes: every long hold, the short ones at most
        # one a second
        now = time.monotonic()
        if busy < self.long_hold and now - self._event_at < 1.0:
            self._events_suppressed += 1
            return
        self._event_at = now
        self.log.emit(
            "loop.hold", seconds=round(busy, 4), where=where, frames=frames,
            suppressed=self._events_suppressed,
        )
        self._events_suppressed = 0

    @staticmethod
    def _drop_find(find: Optional[tuple]) -> None:
        if find is not None and find[3] is not None:
            find[3].__exit__(None, None, None)

    def _on_gc(self, phase: str, info: dict) -> None:
        # every collection of every thread: two clock reads, two adds
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._gc_t0
        self._gc_t0 = 0.0
        gen = info["generation"]
        self._gc_pause[gen] += dt
        self._gc_count[gen] += 1
        out = self._out_since
        if out:
            # the loop is out of select: it ran this collection or waited
            # for the GIL through it
            began, seconds = self._gc_iter
            self._gc_iter = (out, seconds + dt if began == out else dt)

    # -- sampler thread ------------------------------------------------------

    def _sample_loop(self) -> None:
        # ONE capture an iteration, at the first look that finds the loop
        # out of select for the threshold: that one runs mid-hold and
        # names the offender.  An iteration younger than the threshold is
        # looked at again the moment it would become a hold, so none of a
        # threshold and a few milliseconds is missed.
        wait = self.interval
        sampled = 0.0
        while not self._stopped.wait(wait):
            wait = self.interval
            began = self._out_since
            if not began or began == sampled:
                continue
            age = time.perf_counter() - began
            if age < self.threshold:
                wait = min(wait, self.threshold - age + 0.001)
                continue
            where, frames = self._capture()
            if self._out_since != began:
                continue  # it ended meanwhile: the stack is not the hold's
            sampled = began
            if self._gc_t0:
                where = "gc"
            self._drop_find(self._hold)
            self._hold = (began, where, frames,
                          trace.open_annotation("loop.hold"))
            if frames:
                self._last = {
                    "age_seconds": round(age, 4),
                    "frames": frames,
                    "captured_at": time.monotonic(),
                }

    def _capture(self) -> "tuple[str, list[str]]":
        """The loop thread's stack now: its :data:`WHERE` label and its
        frames innermost first (the blocking call is the headline).  No
        source line is read."""
        frame = sys._current_frames().get(self._loop_thread_id)
        if frame is None:
            return "other", []
        where = None
        in_callback = True  # the frames below _run_once started the loop
        frames: list[str] = []
        while frame is not None and (
            in_callback or len(frames) < self.max_frames
        ):
            code = frame.f_code
            if in_callback:
                if code.co_name == "_run_once":
                    in_callback = False
                else:
                    where = where_of(code.co_filename)
                    in_callback = where is None
            if len(frames) < self.max_frames:
                frames.append(
                    f"{os.path.basename(code.co_filename)}:{frame.f_lineno}"
                    f" in {code.co_name}"
                )
            frame = frame.f_back
        # no frame of anybody's inside the callback: the loop's own machinery
        return where or "asyncio", frames

    # -- collector (any thread, when the registry is read) -------------------

    def collect(self) -> None:
        """Bring the registry up to date: the selector's and the
        collector's accumulators since the last flush, and the CPU clocks.
        Every series of the closed label sets is touched, so that a reader
        finds a 0 where nothing happened and nothing where nothing
        measures."""
        if self._selector is None:
            return
        if threading.get_ident() == self._loop_thread_id:
            # a reader on the loop (a benchmark marking its window, the
            # timeline's tick) cuts the iteration here: what held the loop
            # up to now is on the books before the registry is read
            began = self._out_since
            busy = time.perf_counter() - began
            if busy >= self.threshold:
                self._end_hold(busy)
                self._out_since = now = time.perf_counter()
                find = self._hold
                if find is not None and find[0] == began:
                    self._hold = (now,) + find[1:]  # the rest is its too
        with self._flush_lock:
            now = (self._idle, self._iters, tuple(self._gc_pause),
                   tuple(self._gc_count))
            idle, iters, pauses, collections = now
            was_idle, was_iters, was_pauses, was_collections = self._flushed
            self._flushed = now
            trace.record_span_totals(
                "loop.idle", idle - was_idle, iters - was_iters)
            # the series a hold writes, there before the first hold
            trace.record_span_totals("loop.hold", 0.0, 0.0)
            counts = [("loop.holds_long", 0.0, None)]
            counts += [("loop.hold_seconds", 0.0, {"where": w}) for w in WHERE]
            for gen in range(3):
                labels = {"gen": str(gen)}
                counts.append(("gc.pause_seconds",
                               pauses[gen] - was_pauses[gen], labels))
                counts.append(("gc.collections",
                               collections[gen] - was_collections[gen], labels))
            by_role, process = self._read_cpu()
            counts += [("cpu.seconds", by_role[r], {"role": r}) for r in ROLES]
            counts.append(("cpu.process_seconds", process, None))
            counts.append(("loop.cpu_seconds", by_role["loop"], None))
            metrics.inc_batch(counts)

    def _read_cpu(self) -> "tuple[dict, float]":
        """CPU seconds since the last read, by role, and their sum.  Each
        live Python thread's own clock, then the process's
        (``RUSAGE_SELF``, read last so that it is never behind them);
        ``runtime`` is what no Python thread accounts for.  Caller holds
        the flush lock."""
        by_role = dict.fromkeys(ROLES, 0.0)
        threads = self._threads
        live = set()
        for t in threading.enumerate():
            tid = t.native_id
            if tid is None or isinstance(t, threading._DummyThread):
                continue  # not started yet, or not Python's: the runtime's
            entry = threads.get(tid)
            try:
                if entry is None:
                    entry = threads[tid] = [
                        time.pthread_getcpuclockid(t.ident), self._role(t),
                        0.0,
                    ]
                now = time.clock_gettime(entry[0])
            except OSError:
                continue  # it ended under our eyes
            live.add(tid)
            by_role[entry[1]] += max(0.0, now - entry[2])
            entry[2] = now
        for tid in threads.keys() - live:
            del threads[tid]
        ru = resource.getrusage(resource.RUSAGE_SELF)
        process = ru.ru_utime + ru.ru_stime
        python = sum(by_role.values())
        by_role["runtime"] = max(0.0, process - self._process_cpu - python)
        self._process_cpu = process
        return by_role, python + by_role["runtime"]

    def _role(self, t: threading.Thread) -> str:
        if t.ident == self._loop_thread_id:
            return "loop"
        for prefix, role in _ROLE_PREFIXES:
            if t.name.startswith(prefix):
                return role
        return "python_other"

    # -- consumer ------------------------------------------------------------

    def last_blocked(self, max_age: float = 120.0) -> Optional[dict]:
        """The newest capture no older than ``max_age`` seconds, as
        ``{"age_seconds", "frames"}`` (frames innermost-first) — or None.
        The watchdog merges this into its ``watchdog.stall`` event."""
        last = self._last
        if last is None:
            return None
        if time.monotonic() - last["captured_at"] > max_age:
            return None
        return {
            "age_seconds": last["age_seconds"],
            "frames": list(last["frames"]),
        }
