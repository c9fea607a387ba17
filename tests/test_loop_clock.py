"""The loop's clock (ISSUE 38): the event loop accounts for its own time
(idle in the selector, on a CPU, waiting), every hold of 50 ms or more
gets a length and a name, the process's CPU is split by thread role, and
collections are counted — all read when the registry is.  CPU only."""

from __future__ import annotations

import asyncio
import functools
import gc
import resource
import threading
import time

import numpy as np
import pytest

from tpunode import asyncsan, trace
from tpunode.blackbox import FlightRecorder, FlightRecorderConfig
from tpunode.events import EventLog
from tpunode.metrics import Metrics, metrics


def _delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0.0) for k in after}


def _clock(log: EventLog | None = None, **kw) -> asyncsan.LoopAttributor:
    att = asyncsan.LoopAttributor(log_=log if log is not None else EventLog(),
                                  **kw)
    att.start()
    return att


# --- idle, on a CPU, waiting --------------------------------------------------


@pytest.mark.asyncio
async def test_idle_cpu_and_wait_add_up_to_the_window():
    att = _clock()
    try:
        before, t0 = metrics.snapshot(), time.monotonic()
        await asyncio.sleep(0.3)  # idle: blocked in select
        c0 = time.thread_time()
        while time.thread_time() - c0 < 0.3:  # on a CPU, for 0.3 s of it
            pass
        await asyncio.sleep(0)
        time.sleep(0.3)  # out of select and off a CPU
        await asyncio.sleep(0)
        mid = metrics.snapshot()
        t = time.monotonic()
        while time.monotonic() - t < 0.1:  # thousands of select(0) polls
            await asyncio.sleep(0)
        after, window = metrics.snapshot(), time.monotonic() - t0
    finally:
        att.stop()
    d = _delta(before, after)
    idle, on_cpu = d["span.loop.idle.seconds"], d["loop.cpu_seconds"]
    wait = window - idle - on_cpu
    shares = [round(100 * x / window, 1) for x in (idle, on_cpu, wait)]
    # each turn took what it was given; on a loaded box the spin also
    # waits for a CPU, and that wait is the third part's, as it should be
    assert 0.29 <= idle <= 0.36, shares
    assert 0.30 <= on_cpu <= 0.47, shares
    assert 0.29 <= wait, shares
    polls = _delta(mid, after)
    assert polls["span.loop.idle.count"] > 1000  # iterations, all polls:
    assert polls["span.loop.idle.seconds"] == 0  # ready work is not idle
    assert sum(shares) == pytest.approx(100, abs=0.2)
    assert d["span.loop.idle.count"] >= 3
    assert d["cpu.seconds{role=\"loop\"}"] == pytest.approx(on_cpu)


# --- holds --------------------------------------------------------------------


@pytest.mark.asyncio
async def test_a_sleep_in_a_callback_is_one_hold_with_its_line():
    log = EventLog()
    att = _clock(log)
    loop = asyncio.get_running_loop()
    try:
        before = metrics.snapshot()
        hist0 = metrics.histogram("span.loop.hold")
        n0 = hist0.count if hist0 is not None else 0

        def held() -> None:
            time.sleep(0.12)  # THE-HELD-LINE

        line = held.__code__.co_firstlineno + 1
        loop.call_soon(held)
        await asyncio.sleep(0.3)
        after = metrics.snapshot()
    finally:
        att.stop()
    d = _delta(before, after)
    assert d["span.loop.hold.count"] == 1
    assert 0.11 <= d["span.loop.hold.seconds"] <= 0.2
    assert metrics.histogram("span.loop.hold").count == n0 + 1
    assert d['loop.hold_seconds{where="test"}'] == pytest.approx(
        d["span.loop.hold.seconds"])
    assert sum(v for k, v in d.items()
               if k.startswith("loop.hold_seconds")) == pytest.approx(
        d["span.loop.hold.seconds"])
    assert d["loop.holds_long"] == 0
    (ev,) = log.tail(10, type="loop.hold")
    assert ev["where"] == "test" and 0.11 <= ev["seconds"] <= 0.2
    assert ev["frames"][0] == f"test_loop_clock.py:{line} in held", ev
    # the watchdog's consumer reads the same capture
    assert att.last_blocked()["frames"] == ev["frames"]


@pytest.mark.asyncio
async def test_a_read_on_the_loop_cuts_the_hold_it_falls_into():
    """A window's edge is a ``snapshot()`` on the loop: the hold before it
    (the benchmark's own ``gc.collect()``) is counted before it, not in
    the window that opens after."""
    att = _clock()
    try:
        before = metrics.snapshot()
        time.sleep(0.1)
        edge = metrics.snapshot()  # mid-iteration
        time.sleep(0.02)
        await asyncio.sleep(0)
        after = metrics.snapshot()
    finally:
        att.stop()
    assert 0.1 <= _delta(before, edge)["span.loop.hold.seconds"] <= 0.15
    assert _delta(edge, after)["span.loop.hold.count"] == 0


@pytest.mark.asyncio
async def test_long_holds_are_counted_and_short_events_rate_limited():
    log = EventLog()
    att = _clock(log)
    try:
        before = metrics.snapshot()
        for _ in range(3):
            time.sleep(0.07)
            await asyncio.sleep(0)
        time.sleep(0.25)
        await asyncio.sleep(0)
        d = _delta(before, metrics.snapshot())
    finally:
        att.stop()
    assert d["span.loop.hold.count"] == 4
    assert d["loop.holds_long"] == 1
    evs = log.tail(10, type="loop.hold")
    # one short hold in a second is reported, the long one always is and
    # says how many went unreported before it
    assert [e["seconds"] >= 0.2 for e in evs] == [False, True]
    assert evs[1]["suppressed"] == 2


@pytest.mark.asyncio
async def test_a_quiet_loop_has_no_hold_and_every_series_reads_zero():
    m0 = metrics.snapshot()
    att = _clock()
    try:
        await asyncio.sleep(0.12)
        d = _delta(m0, metrics.snapshot())
    finally:
        att.stop()
    assert d["span.loop.hold.count"] == 0 and d["loop.holds_long"] == 0
    # the closed label sets are all there for a reader to find a 0 in
    for where in asyncsan.WHERE:
        assert d[f'loop.hold_seconds{{where="{where}"}}'] == 0
    for role in asyncsan.ROLES:
        assert f'cpu.seconds{{role="{role}"}}' in d
    for gen in "012":
        assert f'gc.pause_seconds{{gen="{gen}"}}' in d
    assert att.last_blocked() is None


@pytest.mark.parametrize("path,where", [
    ("/x/tpunode/mempool.py", "mempool"),
    ("/x/tpunode/seenlru.py", "mempool"),
    ("/x/tpunode/node.py", "node"),
    ("/x/tpunode/peermgr.py", "peer"),
    ("/x/tpunode/verify/engine.py", "engine"),
    ("/x/tpunode/verify/sched.py", "sched"),
    ("/x/tpunode/verify/kernel.py", "verify"),
    ("/x/tpunode/timeseries.py", "telemetry"),
    ("/x/tpunode/blackbox.py", "telemetry"),
    ("/x/tpunode/watchdog.py", "telemetry"),
    ("/x/tpunode/asyncsan.py", "asyncio"),  # the selector wrap itself
    ("/x/tpunode/torture.py", "other"),
    ("/x/tpunode/threadsan.py", None),  # a lock wrapper: look further out
    ("/x/tpunode/util.py", None),
    ("/x/chipbench/drivers/open.py", "harness"),
    ("/x/tests/test_node.py", "test"),
    ("/usr/lib/python3.12/asyncio/events.py", None),
])
def test_where_is_a_closed_set_by_module(path, where):
    assert asyncsan.where_of(path) == where
    assert where is None or where in asyncsan.WHERE


# --- the collector ------------------------------------------------------------


@pytest.mark.asyncio
async def test_a_collection_on_the_loop_is_a_gc_pause_and_a_gc_hold():
    log = EventLog()
    junk = [[] for _ in range(1_200_000)]
    for j in junk:
        j.append(junk)  # cycles: a full collection has to walk them all
    att = _clock(log)
    try:
        before = metrics.snapshot()
        await asyncio.sleep(0)
        gc.collect()
        await asyncio.sleep(0)
        d = _delta(before, metrics.snapshot())
    finally:
        att.stop()
        del junk, j
    assert d['gc.collections{gen="2"}'] >= 1
    pause = d['gc.pause_seconds{gen="2"}']
    assert pause >= 0.05, pause
    # the sampler cannot look while the collector holds the GIL: the loop
    # side names the hold from the pause the callback timed
    assert d['loop.hold_seconds{where="gc"}'] >= pause
    assert d['loop.hold_seconds{where="gc"}'] == pytest.approx(
        d["span.loop.hold.seconds"])
    assert [e["where"] for e in log.tail(10, type="loop.hold")] == ["gc"]


@pytest.mark.asyncio
async def test_a_collection_in_another_thread_holds_the_loop_as_gc():
    """A collection holds the GIL whoever runs it: the loop, busy with
    something else, waits through it, and the hold is the collector's."""
    log = EventLog()
    junk = [[] for _ in range(1_200_000)]
    for j in junk:
        j.append(junk)
    att = _clock(log)
    try:
        await asyncio.sleep(0)
        done = threading.Event()
        collector = threading.Thread(
            target=lambda: (gc.collect(), done.set()))
        collector.start()
        while not done.is_set():  # the loop has work; it cannot do it
            pass
        await asyncio.sleep(0)
        collector.join(5.0)
    finally:
        att.stop()
        del junk, j
    (ev,) = log.tail(10, type="loop.hold")
    assert ev["where"] == "gc" and ev["seconds"] >= 0.05, ev


@pytest.mark.asyncio
async def test_the_frames_that_started_the_loop_name_nothing():
    """Every stack of the loop thread ends in whoever called
    ``asyncio.run`` — here ``tests/conftest.py``, in the benchmark
    ``chipbench/run.py``: a hold with no labelled frame inside the
    callback is the loop machinery's, not theirs."""
    log = EventLog()
    att = _clock(log)
    try:
        asyncio.get_running_loop().call_soon(time.sleep, 0.12)
        await asyncio.sleep(0.2)
    finally:
        att.stop()
    (ev,) = log.tail(10, type="loop.hold")
    assert ev["where"] == "asyncio", ev
    assert ev["frames"][0].startswith("events.py:") and any(
        f.startswith("conftest.py:") for f in ev["frames"]), ev


@pytest.mark.asyncio
async def test_a_poll_that_waits_for_the_gil_is_a_hold_not_idle_time():
    """``select(0)`` gives the GIL up; a thread that keeps it (in C: a
    collection, a compile) makes the poll take long.  Stood in for by an
    inner select that sleeps."""
    log = EventLog()
    att = _clock(log)
    inner = att._inner_select
    slow = [True]

    def select(timeout=None):
        if timeout == 0 and slow:
            slow.clear()
            time.sleep(0.12)
        return inner(timeout)

    att._inner_select = select
    try:
        before = metrics.snapshot()
        await asyncio.sleep(0)  # ready work: the loop polls
        await asyncio.sleep(0.1)
        d = _delta(before, metrics.snapshot())
    finally:
        att.stop()
    assert not slow
    (ev,) = log.tail(10, type="loop.hold")
    assert 0.11 <= ev["seconds"] <= 0.2, ev
    # the stand-in is this file's; without it the innermost frame is the
    # wrap itself, which reads "asyncio" (test_where_is_a_closed_set…)
    assert ev["where"] == "test" and "in _select" in ev["frames"][1], ev
    assert d["span.loop.idle.seconds"] <= 0.11  # the later sleep alone


@pytest.mark.asyncio
async def test_the_poll_after_a_hold_and_the_rest_after_a_read_keep_its_name():
    log = EventLog()
    att = _clock(log)
    loop = asyncio.get_running_loop()
    inner = att._inner_select
    slow = [True]

    def select(timeout=None):
        if timeout == 0 and slow:
            slow.clear()
            time.sleep(0.21)
        return inner(timeout)

    def held() -> None:
        time.sleep(0.21)
        att._inner_select = select
        loop.call_soon(lambda: None)  # ready work: the loop polls next

    try:
        loop.call_soon(held)
        await asyncio.sleep(0.5)
        att._inner_select = inner
        time.sleep(0.21)
        metrics.snapshot()  # a read on the loop cuts the hold ...
        time.sleep(0.21)  # ... and what follows is still this line's
        await asyncio.sleep(0)
    finally:
        att.stop()
    evs = log.tail(10, type="loop.hold")
    assert [e["where"] for e in evs] == ["test"] * 4, evs
    assert evs[1]["frames"] == evs[0]["frames"]  # the sampler looked once
    assert " in held" in evs[0]["frames"][0]


def test_the_gc_callback_costs_two_clock_reads():
    """Its cost is paid by every generation-0 collection of every thread:
    keep it under a microsecond and a half a pair here."""
    att = asyncsan.LoopAttributor()
    info = {"generation": 0, "collected": 0, "uncollectable": 0}
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        att._on_gc("start", info)
        att._on_gc("stop", info)
    per_pair = (time.perf_counter() - t0) / n
    assert att._gc_count[0] == n
    assert per_pair < 5e-6, per_pair


# --- CPU by thread role -------------------------------------------------------


def _burn_numpy(cpu_seconds: float) -> None:
    """Spend that much of the calling thread's own CPU time, most of it
    with the GIL given up."""
    a = np.ones((128, 128))
    t = time.thread_time()
    while time.thread_time() - t < cpu_seconds:
        a @ a


@pytest.mark.asyncio
async def test_cpu_by_role_sums_to_the_process_and_threads_land_by_name():
    att = _clock()
    try:
        before = metrics.snapshot()
        ru0 = resource.getrusage(resource.RUSAGE_SELF)
        stop = threading.Event()

        def until_stopped() -> None:
            _burn_numpy(0.05)
            stop.wait(10.0)  # alive when the registry is read

        named = threading.Thread(target=until_stopped, name="extract-0")
        pooled = threading.Thread(target=until_stopped, name="asyncio_3")
        writer = threading.Thread(target=until_stopped,
                                  name="logkv-commit:node.log")
        unnamed = threading.Thread(target=until_stopped)
        gone = threading.Thread(target=_burn_numpy, args=(0.05,))
        for t in (named, pooled, writer, unnamed, gone):
            t.start()
        await asyncio.to_thread(gone.join, 10.0)
        assert not gone.is_alive()
        for _ in range(500):  # until the four have burned their share
            d = _delta(before, metrics.snapshot())
            ru1 = resource.getrusage(resource.RUSAGE_SELF)
            if min(d[f'cpu.seconds{{role="{r}"}}'] for r in (
                    "extract", "executor", "store", "python_other")) >= 0.05:
                break
            await asyncio.sleep(0.01)
        stop.set()
        for t in (named, pooled, writer, unnamed):
            await asyncio.to_thread(t.join, 5.0)
            assert not t.is_alive()
    finally:
        att.stop()
    roles = {r: d[f'cpu.seconds{{role="{r}"}}'] for r in asyncsan.ROLES}
    assert sum(roles.values()) == pytest.approx(d["cpu.process_seconds"])
    # the benchmark's host_cpu_ms_per_ksig reads the same clock at the
    # same moment: RUSAGE_SELF, user + system
    rusage = (ru1.ru_utime + ru1.ru_stime) - (ru0.ru_utime + ru0.ru_stime)
    assert d["cpu.process_seconds"] == pytest.approx(rusage, rel=0.02)
    for role in ("extract", "executor", "store", "python_other"):
        assert 0.05 <= roles[role] <= 0.2, roles
    # a thread that ended before anybody looked: its CPU is the process's
    # and no live Python thread's, so it reads as the runtime's
    assert roles["runtime"] >= 0.045, roles
    assert roles["loop"] == pytest.approx(d["loop.cpu_seconds"])


# --- Metrics.on_collect -------------------------------------------------------


def test_collectors_run_once_a_read_and_a_failing_one_is_counted():
    m = Metrics(disabled=False)
    calls = []

    class Source:
        def collect(self) -> None:
            calls.append(1)
            m.inc("loop.cpu_seconds", 0.5)

        def broken(self) -> None:
            raise RuntimeError("boom")

    src = Source()
    m.on_collect(src.collect)
    m.on_collect(src.broken)
    assert m.snapshot()["loop.cpu_seconds"] == 0.5 and len(calls) == 1
    assert m.flat_sample()["loop.cpu_seconds"] == 1.0 and len(calls) == 2
    assert "tpunode_loop_cpu_seconds 1.5" in m.render_prometheus()
    assert len(calls) == 3
    assert m.get("trace.collector_errors") == 3  # counted, never raised
    assert m.get("loop.cpu_seconds") == 1.5  # a point read collects nothing
    del src  # held weakly, like on_drop's hooks
    m.snapshot()
    assert len(calls) == 3 and m._collectors == []


# --- lifecycle ----------------------------------------------------------------


@pytest.mark.asyncio
async def test_one_clock_a_loop_and_the_selector_is_restored():
    loop = asyncio.get_running_loop()
    sel = loop._selector
    first = _clock()
    second = _clock()  # the loop has its clock: this one reads nothing
    assert vars(sel)["select"] == first._select
    assert second._thread is None and second._selector is None
    time.sleep(0.12)
    await asyncio.sleep(0.01)
    assert second.last_blocked() is None and first.last_blocked() is not None
    assert gc.callbacks[0] == first._on_gc  # before any that gives the GIL up
    second.stop()
    assert vars(sel)["select"] == first._select
    first.stop()
    assert "select" not in vars(sel)
    assert first._thread is None and first._on_gc not in gc.callbacks
    assert not [t for t in threading.enumerate()
                if t.name == "asyncsan-attributor"]
    await asyncio.sleep(0.01)  # the loop still turns on the class's own


@pytest.mark.asyncio
async def test_a_loop_without_a_selector_reads_nothing():
    class NoSelector:
        pass

    att = asyncsan.LoopAttributor()
    att.start(NoSelector())
    assert att._thread is None
    att.collect()
    att.stop()


@pytest.mark.asyncio
async def test_node_runs_the_clock_with_its_watchdog_and_takes_it_down(
        monkeypatch):
    from tests.fakenet import dummy_peer_connect
    from tests.fixtures import all_blocks
    from tpunode import BCH_REGTEST, Node, NodeConfig, Publisher
    from tpunode.store import MemoryKV

    monkeypatch.delenv("TPUNODE_ASYNCSAN", raising=False)
    loop = asyncio.get_running_loop()

    def cfg(pub, watchdog_interval):
        return NodeConfig(
            net=BCH_REGTEST, store=MemoryKV(), pub=pub, peers=["[::1]:18333"],
            connect=lambda sa: dummy_peer_connect(BCH_REGTEST, all_blocks()),
            stats_interval=0, watchdog_interval=watchdog_interval,
        )

    pub = Publisher(name="clock-events")
    async with pub.subscription():
        async with Node(cfg(pub, 0.05)) as node:
            # no env gate: the clock is on, asyncio's debug mode is not
            assert node._attributor is not None and not loop.get_debug()
            assert node._watchdog.attributor is node._attributor
            assert vars(loop._selector)["select"] == node._attributor._select
            before = metrics.snapshot()
            await asyncio.sleep(0.1)
            d = _delta(before, metrics.snapshot())
            assert d["span.loop.idle.seconds"] > 0.05
            assert d["span.loop.idle.count"] >= 2
        assert node._attributor is None
        assert "select" not in vars(loop._selector)
        assert not [t for t in threading.enumerate()
                    if t.name == "asyncsan-attributor"]
        # the watchdog off: no clock either (one switch for both)
        async with Node(cfg(pub, 0)) as node:
            assert node._attributor is None
            assert "select" not in vars(loop._selector)


# --- the flight recorder's bundle is built off the loop ------------------------


@pytest.mark.asyncio
async def test_a_stall_bundle_is_built_off_the_loop_it_reports_on(tmp_path):
    """The loop is held for 0.6 s, the watchdog's stall event fires on the
    loop, and the bundle — with a state source that takes 0.1 s — appears
    without a second hold: no ``loop.hold`` is telemetry's."""
    from tpunode.watchdog import Watchdog, WatchdogConfig

    log = EventLog()
    att = _clock(log)
    built_on = []
    rec = FlightRecorder(
        FlightRecorderConfig(dir=str(tmp_path)), log_=log,
        sources={"slow": functools.partial(time.sleep, 0.1),
                 "thread": lambda: built_on.append(
                     threading.current_thread().name)},
    )
    rec.attach()
    wd = Watchdog(WatchdogConfig(interval=0.05, lag_threshold=0.5),
                  log_=log, attributor=att)
    task = asyncio.ensure_future(wd.run())
    try:
        before = metrics.snapshot()
        await asyncio.sleep(0.1)
        time.sleep(0.6)
        for _ in range(200):
            if rec.records():
                break
            await asyncio.sleep(0.01)
        d = _delta(before, metrics.snapshot())
        # a trigger from a worker thread is still built where it fires,
        # and the rate limit is the same one
        await asyncio.to_thread(log.emit, "utxo.error", height=1, error="x")
        assert rec.stats()["suppressed"] == 1
    finally:
        task.cancel()
        rec.detach()
        att.stop()
    (bundle,) = rec.records()
    assert bundle["reason"] == "watchdog.stall"
    assert bundle["trigger"]["blocked_frames"], bundle["trigger"]
    assert bundle["path"] and built_on[0].startswith("blackbox")
    holds = log.tail(20, type="loop.hold")
    assert [e["where"] for e in holds] == ["test"], holds
    assert d['loop.hold_seconds{where="telemetry"}'] == 0
    assert not [t for t in threading.enumerate()
                if t.name.startswith("blackbox")]


@pytest.mark.asyncio
async def test_bundles_triggered_on_the_loop_keep_their_order():
    log = EventLog()
    rec = FlightRecorder(FlightRecorderConfig(min_interval=0.0), log_=log)
    rec.attach()
    try:
        for i in range(5):
            log.emit("watchdog.stall", kind="event_loop", n=i)
    finally:
        rec.detach()  # waits for what is being built
    assert [b["trigger"]["n"] for b in rec.records()] == [4, 3, 2, 1, 0]
    dumps = log.tail(20, type="blackbox.dump")
    assert [e["trigger_seq"] for e in dumps] == sorted(
        e["trigger_seq"] for e in dumps)


# --- on the device trace's clock ----------------------------------------------


def test_no_annotation_outside_a_capture():
    assert trace.open_annotation("loop.hold") is None


@pytest.mark.asyncio
async def test_a_hold_during_a_capture_is_annotated_and_closed(tmp_path,
                                                               monkeypatch):
    """While ``profile_to`` captures, the sampler opens an annotation at
    its look and the loop closes it when the hold ends."""
    opened, closed = [], []

    class Ann:
        def __init__(self, name):
            opened.append((name, threading.current_thread().name))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            closed.append(threading.current_thread().name)

    class Profiler:
        TraceAnnotation = Ann

    monkeypatch.setattr(trace, "_jax_profiler", Profiler)
    monkeypatch.setattr(trace, "_profiling", True)
    att = _clock()
    try:
        time.sleep(0.12)
        await asyncio.sleep(0.01)
    finally:
        att.stop()
    assert opened == [("loop.hold", "asyncsan-attributor")]
    assert closed == [threading.current_thread().name]
